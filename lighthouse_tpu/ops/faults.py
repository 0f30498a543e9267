"""Deterministic fault injection for the device offload path.

The fault-tolerant supervisor (crypto/bls/api.py) is only trustworthy if
its failure handling is exercised, and real device faults (XLA compile
errors, wedged kernels, relay drops) are neither deterministic nor
available on CI hardware.  This module is the switchboard: an installed
:class:`FaultPlan` makes the instrumented dispatch sites in
ops/bls_backend.py, parallel/bls_sharded.py and ops/dispatch_pipeline.py
fail on command — raise, stall past a watchdog deadline, return a
corrupt verdict, or fail "compilation" — at chosen chunk/batch indices.

Plans come from two places:

- **programmatic** (tests): :func:`install_plan` /
  ``lighthouse_tpu.testing.inject_fault`` — exact, per-test control;
- **environment** (operator chaos drills): the ``LHTPU_FAULT_*`` knobs
  registered in common/env.py, loaded lazily on first :func:`fire`.

Fault classes (``FaultPlan.mode``):

==========  =================================================================
mode        behaviour at a matching site
==========  =================================================================
raise       raise :class:`InjectedFault` (a generic device dispatch error)
compile     raise :class:`InjectedCompileFault` (an XLA compile failure)
hang        sleep ``hang_s`` seconds, then raise — the stall is what the
            caller's watchdog must cut off; the terminal raise guarantees
            an abandoned watchdog thread never continues into real device
            work (deterministic teardown for tests)
corrupt     return ``"corrupt"`` — the site substitutes
            :func:`corrupt_verdict` (or flips its computed verdict) to
            model a device that silently returned garbage
==========  =================================================================

This module is deliberately stdlib-only (no jax, no numpy): the BLS API
facade and the beacon processor import it without dragging in the device
stack.
"""

from __future__ import annotations

import contextvars
import threading
import time
from dataclasses import dataclass, field

from lighthouse_tpu.common import env as envreg


class DeviceFault(RuntimeError):
    """Base class for device-offload faults the supervisor recovers from."""


class InjectedFault(DeviceFault):
    """Raised by an installed :class:`FaultPlan` (mode raise / hang)."""


class InjectedCompileFault(InjectedFault):
    """Simulates an XLA compilation failure at dispatch time."""


class WatchdogTimeout(DeviceFault):
    """A supervised device call or verdict fetch exceeded its deadline."""


VALID_MODES = ("raise", "hang", "corrupt", "compile")

# sites instrumented in the offload modules (documented for operators;
# fire() accepts any string so tests can add ad-hoc sites)
KNOWN_SITES = ("tpu", "sharded", "chunk", "subgroup", "verdict")


@dataclass
class FaultPlan:
    """One injection directive; see the module table for ``mode``."""

    mode: str
    sites: frozenset = frozenset({"tpu"})
    indices: frozenset | None = None   # chunk/batch indices; None = every hit
    hang_s: float = 0.05
    max_fires: int | None = None       # stop injecting after N fires
    corrupt_value: bool = True         # verdict substituted on mode=corrupt
    fires: int = field(default=0)      # mutated under _LOCK

    def __post_init__(self):
        if self.mode not in VALID_MODES:
            raise ValueError(
                f"fault mode {self.mode!r} not in {VALID_MODES}")
        self.sites = frozenset(self.sites)
        if self.indices is not None:
            self.indices = frozenset(int(i) for i in self.indices)


_LOCK = threading.Lock()
_PLAN: FaultPlan | None = None
_ENV_LOADED = False


def install_plan(plan: FaultPlan | None) -> None:
    """Install (or, with None, clear) the process-wide fault plan.
    A programmatic plan always wins over the env-derived one."""
    global _PLAN, _ENV_LOADED
    with _LOCK:
        _PLAN = plan
        _ENV_LOADED = True  # explicit install suppresses the env load


def clear() -> None:
    """Remove any plan AND forget the env snapshot (next fire re-reads)."""
    global _PLAN, _ENV_LOADED
    with _LOCK:
        _PLAN = None
        _ENV_LOADED = False


_WARNED_ENV_PLAN = False


def plan_from_env() -> FaultPlan | None:
    """Build a plan from the LHTPU_FAULT_* knobs; None when unset.

    A malformed value (unknown mode, non-integer index) warns ONCE and
    disables injection — a typo'd chaos knob must not turn every
    dispatch site into a permanent fault generator."""
    global _WARNED_ENV_PLAN
    mode = envreg.get("LHTPU_FAULT_MODE")
    if not mode:
        return None
    sites = frozenset(
        s.strip() for s in (envreg.get("LHTPU_FAULT_SITE") or "tpu").split(",")
        if s.strip())
    try:
        raw_idx = envreg.get("LHTPU_FAULT_INDICES")
        indices = None
        if raw_idx:
            indices = frozenset(
                int(i) for i in raw_idx.split(",") if i.strip())
        return FaultPlan(
            mode=mode.strip(),
            sites=sites,
            indices=indices,
            hang_s=envreg.get_float("LHTPU_FAULT_HANG_S", 30.0),
            max_fires=envreg.get_int("LHTPU_FAULT_MAX_FIRES"),
        )
    except ValueError as e:
        if not _WARNED_ENV_PLAN:
            _WARNED_ENV_PLAN = True
            import sys

            print(f"lighthouse_tpu: ignoring malformed LHTPU_FAULT_* "
                  f"configuration ({e}); fault injection disabled",
                  file=sys.stderr)
        return None


def refresh_from_env() -> FaultPlan | None:
    """Force a re-read of the env knobs (tests mutate os.environ)."""
    global _PLAN, _ENV_LOADED
    plan = plan_from_env()
    with _LOCK:
        _PLAN = plan
        _ENV_LOADED = True
    return plan


def active_plan() -> FaultPlan | None:
    global _PLAN, _ENV_LOADED
    if _ENV_LOADED:
        return _PLAN
    with _LOCK:
        if not _ENV_LOADED:
            _PLAN = plan_from_env()
            _ENV_LOADED = True
        return _PLAN


def corrupt_verdict() -> bool:
    """The verdict a corrupt-mode site substitutes for its real answer."""
    plan = active_plan()
    return plan.corrupt_value if plan is not None else True


def _record_injection(site: str, mode: str) -> None:
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter(
            "offload_injected_faults_total",
            "faults injected by ops/faults, by site and mode",
        ).labels(site=site, mode=mode).inc()
        from lighthouse_tpu.common import flight_recorder as flight

        flight.emit("fault_injected", plane="offload", site=site,
                    mode=mode)
    except (AttributeError, KeyError, TypeError, ValueError):
        pass  # injection accounting must never mask the injected fault


def fire(site: str, index: int = 0) -> str | None:
    """Consult the active plan at an instrumented dispatch site.

    Returns None (no fault), returns ``"corrupt"`` (caller substitutes /
    flips its verdict), or raises the planned fault.  ``index`` is the
    chunk/batch ordinal at looped sites (site "chunk")."""
    plan = active_plan()
    if plan is None or site not in plan.sites:
        return None
    with _LOCK:
        if plan is not _PLAN:
            return None  # plan swapped underneath us; stale hit
        if plan.indices is not None and int(index) not in plan.indices:
            return None
        if plan.max_fires is not None and plan.fires >= plan.max_fires:
            return None
        plan.fires += 1
    _record_injection(site, plan.mode)
    if plan.mode == "corrupt":
        return "corrupt"
    if plan.mode == "compile":
        raise InjectedCompileFault(
            f"injected XLA compile failure at {site}[{index}]")
    if plan.mode == "hang":
        # stall (the watchdog's job is to cut this off), then fail: an
        # abandoned watchdog thread must never continue into device work
        time.sleep(plan.hang_s)
        raise InjectedFault(
            f"injected hang released after {plan.hang_s}s at {site}[{index}]")
    raise InjectedFault(f"injected device fault at {site}[{index}]")


# --- peer / network-plane faults ---------------------------------------------

VALID_PEER_MODES = ("stall", "empty", "truncate", "malformed",
                    "wrong_chain", "equivocate", "flap")

#: protocol tokens the rpc layer derives from its protocol ids (the
#: second-to-last path segment: "status", "beacon_blocks_by_range", ...)
KNOWN_PROTOCOL_TOKENS = (
    "status", "goodbye", "beacon_blocks_by_range", "beacon_blocks_by_root",
    "blob_sidecars_by_range", "blob_sidecars_by_root")


@dataclass
class PeerFaultPlan:
    """One adversarial-peer directive for the network plane.

    Consumed by the rpc request discipline (network/rpc.py) — the same
    reasoning as :class:`FaultPlan`: real Byzantine peers (withholding
    ranges, serving stale forks, stalling responses past deadlines,
    flapping mid-stream) are neither deterministic nor available on CI,
    so the sync/backfill supervision is exercised by injecting them on
    command at the requester's seam.

    ===========  ==============================================================
    mode         behaviour at a matching (peer, protocol, ordinal) request
    ===========  ==============================================================
    stall        response delayed ``stall_s`` seconds — the rpc deadline
                 watchdog must cut it off
    empty        the response chunks are withheld (served as ``[]``) — a
                 lying empty window the sync linkage machine must detect
    truncate     only the first half of the response chunks are served
    malformed    response bytes are corrupted (decode must fail, peer
                 downscored hard)
    wrong_chain  the request is transparently redirected to ``alt_peer``
                 (a node serving a consistent but non-canonical branch);
                 with no ``alt_peer`` the response is withheld
    equivocate   STATUS responses advertise a bogus head: ``head_slot``
                 lifted by ``lift`` and a fabricated ``head_root``
    flap         the peer disconnects mid-stream (request raises)
    ===========  ==============================================================

    ``peers``/``protocols``/``ordinals`` of None match everything; the
    ordinal is the per-(peer, protocol) request counter at the
    requesting endpoint, so "fail the third range request to peer X" is
    expressible exactly.
    """

    mode: str
    peers: frozenset | None = None       # peer ids; None = every peer
    protocols: frozenset | None = None   # protocol tokens; None = every one
    ordinals: frozenset | None = None    # request ordinals; None = every hit
    stall_s: float = 30.0
    max_fires: int | None = None
    alt_peer: str | None = None          # wrong_chain redirect target
    lift: int = 4096                     # equivocate head_slot lift
    fires: int = field(default=0)        # mutated under _LOCK

    def __post_init__(self):
        if self.mode not in VALID_PEER_MODES:
            raise ValueError(
                f"peer fault mode {self.mode!r} not in {VALID_PEER_MODES}")
        if self.peers is not None:
            self.peers = frozenset(self.peers)
        if self.protocols is not None:
            self.protocols = frozenset(self.protocols)
        if self.ordinals is not None:
            self.ordinals = frozenset(int(i) for i in self.ordinals)


_PEER_PLANS: tuple = ()
_PEER_ENV_LOADED = False
_WARNED_PEER_ENV = False


def install_peer_plans(plans) -> None:
    """Install (or, with None/(), clear) the process-wide peer fault
    plans.  Multiple plans may be active at once — the syncstorm drill
    arms one per fault class, each scoped to its own peer."""
    global _PEER_PLANS, _PEER_ENV_LOADED
    with _LOCK:
        _PEER_PLANS = tuple(plans) if plans else ()
        _PEER_ENV_LOADED = True  # explicit install suppresses the env load


def clear_peer_plans() -> None:
    """Remove all peer plans AND forget the env snapshot."""
    global _PEER_PLANS, _PEER_ENV_LOADED
    with _LOCK:
        _PEER_PLANS = ()
        _PEER_ENV_LOADED = False


def peer_plan_from_env() -> PeerFaultPlan | None:
    """Build a plan from the LHTPU_PEERFAULT_* knobs; None when unset.
    Malformed values warn once and disable injection (same discipline
    as :func:`plan_from_env`)."""
    global _WARNED_PEER_ENV
    mode = envreg.get("LHTPU_PEERFAULT_MODE")
    if not mode:
        return None

    def _set(name):
        raw = envreg.get(name)
        if not raw:
            return None
        return frozenset(s.strip() for s in raw.split(",") if s.strip())

    try:
        raw_ord = envreg.get("LHTPU_PEERFAULT_ORDINALS")
        ordinals = None
        if raw_ord:
            ordinals = frozenset(
                int(i) for i in raw_ord.split(",") if i.strip())
        return PeerFaultPlan(
            mode=mode.strip(),
            peers=_set("LHTPU_PEERFAULT_PEERS"),
            protocols=_set("LHTPU_PEERFAULT_PROTOCOLS"),
            ordinals=ordinals,
            stall_s=envreg.get_float("LHTPU_PEERFAULT_STALL_S", 30.0),
            max_fires=envreg.get_int("LHTPU_PEERFAULT_MAX_FIRES"),
        )
    except ValueError as e:
        if not _WARNED_PEER_ENV:
            _WARNED_PEER_ENV = True
            import sys

            print(f"lighthouse_tpu: ignoring malformed LHTPU_PEERFAULT_* "
                  f"configuration ({e}); peer fault injection disabled",
                  file=sys.stderr)
        return None


def active_peer_plans() -> tuple:
    global _PEER_PLANS, _PEER_ENV_LOADED
    if _PEER_ENV_LOADED:
        return _PEER_PLANS
    with _LOCK:
        if not _PEER_ENV_LOADED:
            plan = peer_plan_from_env()
            _PEER_PLANS = (plan,) if plan is not None else ()
            _PEER_ENV_LOADED = True
        return _PEER_PLANS


def _record_peer_injection(mode: str, protocol: str) -> None:
    try:
        from lighthouse_tpu.common.metrics import REGISTRY

        REGISTRY.counter(
            "peer_faults_injected_total",
            "peer faults injected by ops/faults, by mode and protocol",
        ).labels(mode=mode, protocol=protocol).inc()
        from lighthouse_tpu.common import flight_recorder as flight

        flight.emit("fault_injected", plane="peer", mode=mode,
                    protocol=protocol)
    except (AttributeError, KeyError, TypeError, ValueError):
        pass  # injection accounting must never mask the injected fault


def consult_peer(peer: str, protocol_token: str,
                 ordinal: int) -> PeerFaultPlan | None:
    """First active plan matching this (peer, protocol, ordinal) request
    at the requesting endpoint, with its fire accounted; None = serve
    honestly."""
    plans = active_peer_plans()
    if not plans:
        return None
    for plan in plans:
        if plan.peers is not None and peer not in plan.peers:
            continue
        if plan.protocols is not None \
                and protocol_token not in plan.protocols:
            continue
        with _LOCK:
            if plan.ordinals is not None \
                    and int(ordinal) not in plan.ordinals:
                continue
            if plan.max_fires is not None and plan.fires >= plan.max_fires:
                continue
            plan.fires += 1
        _record_peer_injection(plan.mode, protocol_token)
        return plan
    return None


def peer_fires_by_mode() -> dict:
    """{mode: fires} across the active plans (drill assertions: every
    armed fault class actually fired)."""
    out: dict = {}
    for plan in active_peer_plans():
        out[plan.mode] = out.get(plan.mode, 0) + plan.fires
    return out


# --- ingest-path storms ------------------------------------------------------

VALID_INGEST_MODES = ("burst", "stall", "dup", "invalid")


@dataclass
class IngestPlan:
    """A hostile-peer / overload scenario for the attestation firehose.

    Consumed by the firehose driver (processor/firehose.py) and the
    ``bench.py --child-firehose`` scenario; the point is the same as
    :class:`FaultPlan`'s — real storms (a peer replaying a slot's gossip,
    a wedged disk stalling the consumer, an attacker flooding garbage
    signatures) are neither deterministic nor available on CI, so the
    drills synthesize them on command and assert the admission ladder's
    response.

    ======  ===================================================================
    mode    behaviour while the storm window is open
    ======  ===================================================================
    burst   arrival rate multiplied by ``factor`` (sustained over-delivery)
    stall   the batch consumer sleeps ``stall_s`` per batch (slow-consumer:
            queues back up even at the honest arrival rate)
    dup     every attestation delivered ``factor`` times (byte-identical
            copies — the pre-BLS dedup stage's storm)
    invalid ``factor`` invalid-signature copies ride along with each honest
            attestation (hostile peer; the batch must bisect them out and
            the ladder must recover once the storm ends)
    ======  ===================================================================
    """

    mode: str
    factor: float = 4.0
    duration_s: float = 2.0
    stall_s: float = 0.05

    def __post_init__(self):
        if self.mode not in VALID_INGEST_MODES:
            raise ValueError(
                f"ingest mode {self.mode!r} not in {VALID_INGEST_MODES}")


_INGEST_PLAN: IngestPlan | None = None
_INGEST_EXPIRES_AT: float | None = None


def install_ingest_plan(plan: IngestPlan | None,
                        duration_s: float | None = None) -> None:
    """Install (or clear) the process-wide ingest storm plan.

    ``duration_s`` bounds the storm: after that many seconds the plan
    self-expires on the next :func:`active_ingest_plan` read.  The
    env-armed path passes the plan's own ``duration_s`` (a drill knob
    must not wedge the consumer forever); drill drivers that bound
    their phases themselves install without one."""
    global _INGEST_PLAN, _INGEST_EXPIRES_AT
    with _LOCK:
        _INGEST_PLAN = plan
        _INGEST_EXPIRES_AT = (
            time.monotonic() + duration_s
            if plan is not None and duration_s and duration_s > 0
            else None)


def snapshot_ingest_plan() -> tuple:
    """(plan, expiry) snapshot for save/restore around a drill phase —
    restoring through :func:`restore_ingest_plan` preserves an env-armed
    storm's remaining expiry window instead of unbounding it."""
    with _LOCK:
        return (_INGEST_PLAN, _INGEST_EXPIRES_AT)


def restore_ingest_plan(snapshot: tuple) -> None:
    global _INGEST_PLAN, _INGEST_EXPIRES_AT
    plan, expires = snapshot
    with _LOCK:
        _INGEST_PLAN = plan
        _INGEST_EXPIRES_AT = expires  # already-lapsed deadlines clear
        #                               on the next active read


def active_ingest_plan() -> IngestPlan | None:
    global _INGEST_PLAN, _INGEST_EXPIRES_AT
    plan = _INGEST_PLAN
    expires = _INGEST_EXPIRES_AT
    if plan is not None and expires is not None \
            and time.monotonic() >= expires:
        with _LOCK:
            if _INGEST_PLAN is plan:
                _INGEST_PLAN = None
                _INGEST_EXPIRES_AT = None
        return None
    return plan


_WARNED_INGEST_ENV = False


def ingest_plan_from_env() -> IngestPlan | None:
    """Build an ingest storm from the LHTPU_INGEST_* knobs; None when
    unset or malformed (malformed warns once, same discipline as
    :func:`plan_from_env`)."""
    global _WARNED_INGEST_ENV
    mode = envreg.get("LHTPU_INGEST_FAULT_MODE")
    if not mode:
        return None
    try:
        return IngestPlan(
            mode=mode.strip(),
            factor=envreg.get_float("LHTPU_INGEST_FAULT_FACTOR", 4.0),
            duration_s=envreg.get_float("LHTPU_INGEST_FAULT_S", 2.0),
            stall_s=envreg.get_float("LHTPU_INGEST_STALL_S", 0.05),
        )
    except ValueError as e:
        if not _WARNED_INGEST_ENV:
            _WARNED_INGEST_ENV = True
            import sys

            print(f"lighthouse_tpu: ignoring malformed LHTPU_INGEST_* "
                  f"configuration ({e}); ingest storm disabled",
                  file=sys.stderr)
        return None


def clear_all_plans() -> None:
    """Disarm every process-wide fault plane in one call — offload,
    peer, and ingest.  The chaos controller's quiesce and drill
    teardown seam: install semantics (the env-derived plans stay
    suppressed until an explicit clear()/clear_peer_plans())."""
    install_plan(None)
    install_peer_plans(())
    install_ingest_plan(None)


def consumer_stall_s() -> float:
    """Per-batch consumer stall the slow-consumer drill injects (0 when
    no stall-mode ingest plan is active or the storm window expired)."""
    plan = active_ingest_plan()
    return plan.stall_s if plan is not None and plan.mode == "stall" else 0.0


# --- watchdog execution ------------------------------------------------------

_UNDER_WATCHDOG = threading.local()


def under_watchdog() -> bool:
    """True on a thread spawned by :func:`run_with_deadline` — nested
    deadlines are redundant there (the outer watchdog already converts a
    hang into a recoverable fault)."""
    return getattr(_UNDER_WATCHDOG, "value", False)


def run_with_deadline(fn, timeout_s: float, thread_name: str, what: str):
    """Run ``fn()`` on a daemon watchdog thread; raise
    :class:`WatchdogTimeout` after ``timeout_s``.

    The single implementation of the deadline idiom (supervised backend
    calls, deferred verdict fetches).  On timeout the thread is
    abandoned — daemonic, its late result or exception is discarded.
    Exceptions from ``fn`` re-raise on the caller.

    ``fn`` runs inside a copy of the caller's ``contextvars`` context, so
    the spans it opens nest under the caller's open span and inherit its
    slot (``common/tracing``): a watchdogged request is one tree, and the
    hand-off (thread start, the wait on ``done``) is the caller span's
    self time.  An abandoned thread that finishes late closes its spans
    into a parent that has closed already: it appends to a filed tree,
    which raises nothing and which nobody reads again."""
    box: dict = {}
    done = threading.Event()
    ctx = contextvars.copy_context()

    def _run():
        _UNDER_WATCHDOG.value = True
        try:
            box["ok"] = ctx.run(fn)
        except BaseException as e:  # lhlint: allow(LH902) — not swallowed:
            box["exc"] = e          # re-raised on the caller thread below
        finally:
            done.set()

    threading.Thread(target=_run, daemon=True, name=thread_name).start()
    if not done.wait(timeout_s):
        raise WatchdogTimeout(
            f"{what} exceeded its {timeout_s:.3f}s watchdog deadline")
    if "exc" in box:
        raise box["exc"]
    return box.get("ok")


#: a fault of the PROGRAM, not of the device: a device module that does
#: not import or trace (a moved jax API, a typo, a wrong arity).  The
#: supervised seams re-raise these instead of filing a breaker fault —
#: a ladder that absorbs them serves every batch from the reference
#: rung with exit code 0 and nobody learns the device path is dead.
PROGRAM_FAULTS = (ImportError, AttributeError, NameError, TypeError)


def classify(exc: BaseException) -> str:
    """Fault classes for metrics/health accounting: hang | compile | raise."""
    if isinstance(exc, WatchdogTimeout):
        return "hang"
    if isinstance(exc, InjectedCompileFault):
        return "compile"
    text = f"{type(exc).__name__}: {exc}"
    if "compil" in text.lower():  # XlaRuntimeError compile failures
        return "compile"
    return "raise"
