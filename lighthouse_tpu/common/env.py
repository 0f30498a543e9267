"""Central registry of every ``LHTPU_*`` environment variable.

One definition per knob: name, default, and an operator-facing
description.  Call sites read through :func:`get` / :func:`get_int` /
:func:`get_bool` instead of ``os.environ`` directly, so the full tuning
surface is enumerable (the README env-var table is generated from this
registry) and machine-checked: lhlint's env pass (rule LH401) flags any
``os.environ``/``os.getenv`` read of an ``LHTPU_*`` name that is not
registered here, and LH402 flags registry entries missing from the
README.

This module must stay importable before anything else in the package
(cache_guard reads it pre-XLA): stdlib only, no jax, no numpy, no other
lighthouse_tpu imports.
"""

from __future__ import annotations

import os
from dataclasses import dataclass


@dataclass(frozen=True)
class EnvVar:
    name: str
    default: str | None
    description: str


ENV_VARS: dict[str, EnvVar] = {}


def _register(name: str, default: str | None, description: str) -> None:
    ENV_VARS[name] = EnvVar(name, default, description)


# -- the registry (one _register call per knob; lhlint parses these) ----------

_register("LHTPU_BLS_BACKEND", None,
          "Force the BLS backend (tpu|reference|fake|sharded); unset = "
          "auto (device pipeline on TPU, pure-Python reference on CPU).")
_register("LHTPU_BLS_CHUNK", None,
          "Overlapped-pipeline chunk size in signature sets; unset = "
          "512 (dispatch_pipeline.DEFAULT_CHUNK_SETS), 0 disables "
          "chunking (monolithic single-dispatch).")
_register("LHTPU_NO_CACHE_GUARD", None,
          "Any non-empty value disables the vm.max_map_count raise the "
          "test suite makes before XLA:CPU compiles (ops/cache_guard).")
_register("LHTPU_SHA_DEVICE_MIN", None,
          "Pin the device-vs-host SHA-256 routing threshold (pair "
          "count); unset = one-shot startup micro-calibration.")
_register("LHTPU_MXU_REDC", "auto",
          "1/0 forces the MXU Montgomery-reduction path on/off; "
          "auto picks by platform (ops/bigint).")
_register("LHTPU_NATIVE_BLS", "1",
          "0/false disables the native C++ BLS helper library "
          "(decompression, final exp); falls back to pure Python.")
_register("LHTPU_DRYRUN_BLS", "1",
          "0 skips the sharded-BLS compile in the multi-chip dryrun "
          "worker (the first-ever compile costs minutes on CPU).")
_register("LHTPU_BENCH_TIMEOUT", "420",
          "Per-child timeout in seconds for bench.py stage children.")
_register("LHTPU_BLS_SETS", None,
          "bench.py BLS child batch size (the parent walks a "
          "degradation ladder when unset).")
_register("LHTPU_FULL_SCALE", None,
          "1 forces bench.py spec-scale runs (32k-attestation flood, "
          "1M-validator registry).")
_register("LHTPU_SLOW", None,
          "1 enables slow opt-in tests that compile extra device "
          "shapes (test_das 32k scan, test_device_pairing).")
_register("LHTPU_ISOLATED", None,
          "Set by the test conftest in per-file child processes; marks "
          "a child so it runs tests in-process instead of re-forking.")

# -- fault injection + offload supervisor (ops/faults, crypto/bls/api,
#    processor/beacon_processor) ----------------------------------------------

_register("LHTPU_FAULT_MODE", None,
          "Inject device faults (raise|hang|corrupt|compile) at the "
          "instrumented offload sites (ops/faults); unset disables "
          "injection.")
_register("LHTPU_FAULT_SITE", "tpu",
          "Comma-separated sites the injected fault fires at "
          "(tpu, sharded, chunk, subgroup, verdict).")
_register("LHTPU_FAULT_INDICES", None,
          "Comma-separated chunk/batch indices the fault fires at; "
          "unset = every matching hit.")
_register("LHTPU_FAULT_HANG_S", "30",
          "Stall seconds for mode=hang before the injected fault is "
          "raised (the watchdog should cut the stall off first).")
_register("LHTPU_FAULT_MAX_FIRES", None,
          "Stop injecting after N fires; unset = unlimited.")
_register("LHTPU_SUPERVISOR", "1",
          "0 disables the BLS offload supervisor (watchdog, backend "
          "health ladder, reference recovery) — device backends are "
          "then called directly and their faults propagate.")
_register("LHTPU_WATCHDOG_S", "900",
          "Watchdog deadline in seconds for one supervised device batch "
          "and for deferred verdict fetches; 0 disables the deadline.")
_register("LHTPU_SUPERVISOR_AUDIT", "0",
          "Probability [0..1] that a supervised device verdict is "
          "cross-checked against the reference backend (a mismatch "
          "counts as a corrupt-verdict fault and opens the circuit).")
_register("LHTPU_SUPERVISOR_FAILS", "1",
          "Consecutive device-backend faults that open its circuit "
          "breaker.")
_register("LHTPU_SUPERVISOR_BACKOFF_S", "1",
          "Initial circuit-breaker backoff seconds; doubles on every "
          "re-open (half-open probe failure).")
_register("LHTPU_SUPERVISOR_BACKOFF_MAX_S", "60",
          "Circuit-breaker backoff ceiling in seconds.")
_register("LHTPU_SUPERVISOR_LADDER", "tpu,sharded,reference",
          "Degradation ladder for supervised batch verification, "
          "healthiest first; reference is always the implicit last "
          "rung.")
_register("LHTPU_DISPATCH_WEDGE_S", "600",
          "Beacon-processor dispatch-thread wedge deadline in seconds; "
          "0 disables the dispatch-thread supervisor.")
_register("LHTPU_DISPATCH_RESTART_MAX", "3",
          "Dispatch-thread restarts allowed per window before batch "
          "work pins to the synchronous worker-pool path.")
_register("LHTPU_DISPATCH_RESTART_WINDOW_S", "300",
          "Restart-storm window seconds for the dispatch-thread "
          "limiter.")

# -- peer fault injection + rpc/sync/backfill discipline (ops/faults,
#    network/rpc, network/sync, network/backfill, bench --child-syncstorm) ----

_register("LHTPU_PEERFAULT_MODE", None,
          "Inject Byzantine peer faults (stall|empty|truncate|malformed|"
          "wrong_chain|equivocate|flap) at the rpc request seam "
          "(ops/faults.PeerFaultPlan); unset disables injection.")
_register("LHTPU_PEERFAULT_PEERS", None,
          "Comma-separated peer ids the peer fault fires against; "
          "unset = every peer.")
_register("LHTPU_PEERFAULT_PROTOCOLS", None,
          "Comma-separated protocol tokens (status, "
          "beacon_blocks_by_range, beacon_blocks_by_root, ...) the peer "
          "fault fires on; unset = every protocol.")
_register("LHTPU_PEERFAULT_ORDINALS", None,
          "Comma-separated per-(peer,protocol) request ordinals the "
          "fault fires at; unset = every matching request.")
_register("LHTPU_PEERFAULT_STALL_S", "30",
          "Response delay seconds for peer fault mode=stall (the rpc "
          "deadline should cut the stall off first).")
_register("LHTPU_PEERFAULT_MAX_FIRES", None,
          "Stop injecting peer faults after N fires; unset = unlimited.")
_register("LHTPU_RPC_DEADLINE_S", "5",
          "Per-request deadline in seconds for outbound rpc requests "
          "(watchdog-enforced); 0 disables the deadline.")
_register("LHTPU_RPC_FAILS", "3",
          "Consecutive request failures against one peer that trip its "
          "quarantine window (network/rpc backoff ladder).")
_register("LHTPU_RPC_BACKOFF_S", "0.5",
          "Initial per-peer quarantine window in seconds; doubles on "
          "every re-quarantine (exponential backoff ladder).")
_register("LHTPU_RPC_BACKOFF_MAX_S", "30",
          "Per-peer quarantine window ceiling in seconds.")
_register("LHTPU_SYNC_BATCH_SIZE", "32",
          "Slots per BlocksByRange batch in the range-sync state "
          "machine (and the backfill reverse fill).")
_register("LHTPU_SYNC_BATCH_ATTEMPTS", "5",
          "Download+process attempts per range-sync batch across the "
          "peer pool before the chain attempt is abandoned.")
_register("LHTPU_SYNC_STALL_S", "20",
          "Range-sync progress watchdog: a syncing chain with no batch "
          "progress for this many seconds is abandoned and its peers "
          "re-pooled; 0 disables the watchdog.")
_register("LHTPU_SYNC_CHAIN_ATTEMPTS", "3",
          "Abandoned-chain attempts per sync target before that target "
          "is skipped (per-target accounting, PR 8 ladder shape).")
_register("LHTPU_SYNC_BACKFILL_ATTEMPTS", "3",
          "Peer-rotation attempts per backfill batch window before the "
          "backfill run abandons (resumes from the freezer cursor).")
_register("LHTPU_SYNCSTORM_SLOTS", "64",
          "bench.py --child-syncstorm honest-chain length in slots.")
_register("LHTPU_SYNCSTORM_BOUND_S", "180",
          "bench.py --child-syncstorm wall-clock bound in seconds "
          "(the convergence-under-chaos acceptance window).")

# -- admission control + degradation ladder (processor/admission,
#    processor/beacon_processor) ----------------------------------------------

_register("LHTPU_ADMIT_HIGH", "0.75",
          "High watermark (fraction of a governed queue's limit) the "
          "queue-depth EWMA must cross to escalate the shed ladder.")
_register("LHTPU_ADMIT_LOW", "0.25",
          "Low watermark: a sweep with every governed lane at or below "
          "it snaps the shed ladder back to normal; between the "
          "watermarks the rung holds (hysteresis).")
_register("LHTPU_ADMIT_EWMA_ALPHA", "0.4",
          "EWMA smoothing factor for the per-lane queue-depth pressure "
          "that drives the shed ladder (1.0 = instantaneous depth).")
_register("LHTPU_ADMIT_SWEEP_S", "0.05",
          "Admission-ladder sweep cadence in seconds (the processor's "
          "dedicated sweeper task).")
_register("LHTPU_ADMIT_RETRY_S", "0.25",
          "Base backoff hint (seconds) returned with reject-newest "
          "admission verdicts on RPC/API lanes; scales with queue "
          "fullness and the ladder rung.")
_register("LHTPU_SHED_UP_SWEEPS", "2",
          "Consecutive sweeps above the high watermark required to "
          "escalate the shed ladder one rung (breaker-style debounce).")
_register("LHTPU_SHED_COALESCE_FACTOR", "4",
          "Batch-flush deadline multiplier on the coalesce ladder rung "
          "(bigger sweeps, fewer device batches under pressure).")

# -- ingest storms + firehose bench (ops/faults, processor/firehose,
#    bench.py --child-firehose) ------------------------------------------------

_register("LHTPU_INGEST_FAULT_MODE", None,
          "Ingest-path storm for chaos drills (burst|stall|dup|invalid), "
          "armed at client build; stall wedges the live batch consumer, "
          "burst/dup/invalid shape firehose-driver arrival; unset "
          "disables the storm (ops/faults.IngestPlan).")
_register("LHTPU_INGEST_FAULT_FACTOR", "4",
          "Storm intensity: burst arrival multiplier, duplicate copies "
          "per attestation (dup), or invalid-signature copies per "
          "honest one (invalid).")
_register("LHTPU_INGEST_FAULT_S", "2",
          "Storm window in seconds for an env-armed ingest plan — the "
          "storm self-expires after this; <=0 leaves it blowing until "
          "cleared.")
_register("LHTPU_INGEST_STALL_S", "0.05",
          "Per-batch consumer stall for ingest mode=stall (the "
          "slow-consumer drill).")
_register("LHTPU_FIREHOSE_N", "8192",
          "Firehose bench in-flight target: attestations resident in "
          "the processor queues during the sustained-ingest phases.")
_register("LHTPU_FIREHOSE_SECONDS", "8",
          "Seconds of steady-state ingest per firehose bench phase on "
          "the CPU fallback (TPU runs use the full slot budget).")
_register("LHTPU_PRE_BLS", "1",
          "0 disables the pre-BLS coalescing stage (exact-duplicate "
          "dedup + blinded same-message merge in pool/pre_aggregation) "
          "so every signature set pays its own pairing.")

# -- wire-to-device ingest (ssz/columnar, chain/columnar_ingest,
#    chain/pubkey_plane, ops/pubkey_kernels) -----------------------------------

_register("LHTPU_INGEST_COLUMNAR", "1",
          "0 disables the columnar wire path everywhere: gossip "
          "attestation batches fall back to per-message scalar SSZ "
          "decode + the per-object verification pipeline (routers "
          "snapshot the switch at construction so one processor batch "
          "never mixes wire-bytes and object payloads).")
_register("LHTPU_PUBKEY_PLANE", "1",
          "0 is the pubkey-plane kill switch: every committee "
          "aggregate-pubkey fold answers on the host reference rung "
          "and never touches jax.  1 (default) lets the supervisor "
          "ladder route folds to the device-resident gather+MSM rungs "
          "per LHTPU_PUBKEY_BACKEND / the auto policy.")
_register("LHTPU_PUBKEY_BACKEND", None,
          "Force the pubkey-plane fold rung (device|sharded|"
          "reference); unset = auto (device/sharded on TPU above "
          "LHTPU_PUBKEY_DEVICE_MIN lanes, reference otherwise).")
_register("LHTPU_PUBKEY_DEVICE_MIN", "256",
          "Fold-lane count at or above which the pubkey-plane auto "
          "routing considers a device rung (smaller batches never "
          "import jax).")

# -- unified MSM plane (ops/msm, parallel/msm_sharded) ------------------------

_register("LHTPU_MSM_BUCKET_FLOOR", "1",
          "Minimum pow2 lane bucket for the unified MSM plane "
          "(ops/msm.bucket): smaller folds pad their zero-scalar tail "
          "lanes up to it so batch composition cannot churn compiles; "
          "rounded up to a power of two.")
_register("LHTPU_MSM_DEVICE_MIN", None,
          "Lane count at or above which msm_g1 auto routing picks the "
          "device fold over the host lincomb seam; set = operator pin "
          "for every track, unset = the persisted msm_calibration "
          "sidecar (or the static 256 default before calibration).")
_register("LHTPU_MSM_SHARDED", "1",
          "0 drops the sharded MSM rung (parallel/msm_sharded) from "
          "the pubkey-plane auto policy: multi-device TPU hosts fold "
          "on a single device instead of partitioning lanes over the "
          "mesh.  Forced rungs (LHTPU_PUBKEY_BACKEND=sharded) still "
          "work.")
_register("LHTPU_MSM_CALIBRATION", "1",
          "0 disables MSM device-threshold calibration at prewarm: no "
          "measurement, no msm_calibration sidecar adoption; routing "
          "uses the static default unless LHTPU_MSM_DEVICE_MIN pins "
          "it.")

# -- device epoch processing (state_transition/epoch_processing seam,
#    state_transition/epoch_device, ops/epoch_kernels) -------------------------

_register("LHTPU_EPOCH_BACKEND", None,
          "Force the epoch-processing backend (device|sharded|"
          "reference); unset = auto (fused device pass on TPU above "
          "the device-min threshold, numpy reference otherwise).")
_register("LHTPU_EPOCH_BUCKET_FLOOR", "256",
          "Minimum pow2 shape bucket for the fused epoch pass and the "
          "device shuffle (smaller registries pad up to it; rounded up "
          "to a power of two, floored at 256).")
_register("LHTPU_EPOCH_DEVICE_MIN", "131072",
          "Registry size at or above which the epoch/shuffle auto "
          "routing picks the device backend (TPU platforms only; the "
          "XLA-CPU fallback always stays on the numpy reference "
          "unless LHTPU_EPOCH_BACKEND forces a device rung).")

# -- store crash injection + startup recovery (store/crash, store/hot_cold) ---

_register("LHTPU_STORE_FAULT_MODE", None,
          "Inject store faults (crash|drop|flip|io) through "
          "CrashPointStore (store/crash); unset disables injection.")
_register("LHTPU_STORE_FAULT_BATCH", None,
          "Write-commit ordinal a crash/drop store fault fires at; "
          "unset = never (flip/io match by key instead).")
_register("LHTPU_STORE_FAULT_OP", "0",
          "For mode=drop: ops of the matching batch applied before the "
          "simulated death (0 = die at the boundary, nothing applied).")
_register("LHTPU_STORE_FAULT_KEY", None,
          "Substring a key must contain for flip/io store faults; "
          "unset = any key.")
_register("LHTPU_STORE_FAULT_BIT", "0",
          "For mode=flip: bit index flipped in the stored value.")
_register("LHTPU_STORE_SWEEP", None,
          "1 forces the store integrity sweep on every open, 0 disables "
          "it; unset = sweep only after a dirty shutdown.")

# -- the observatory plane: flight recorder, SLO engine, invariant
#    watchdog (common/flight_recorder, chain/slo, common/monitors) ------------

_register("LHTPU_OBS_ARMED", "1",
          "0 disarms the observatory plane (flight recorder, slow-span "
          "capture, SLO scoring, invariant monitor sweeps) for "
          "overhead A/B runs.")
_register("LHTPU_OBS_SWEEP_S", "1",
          "Invariant-watchdog sweep cadence in seconds "
          "(common/monitors); <=0 disables the background sweeper.")
_register("LHTPU_OBS_LABEL_MAX", "1024",
          "Hard bound on labeled children per metric family; a "
          "label-cardinality storm evicts the oldest child "
          "(tracing_evicted_total) instead of growing without bound.")
_register("LHTPU_FLIGHT_CAPACITY", "512",
          "Flight-recorder ring capacity in events (overflow rotates "
          "the oldest event out, counted in flight_evicted_total).")
_register("LHTPU_FLIGHT_DIR", None,
          "Directory trip-triggered flight-recorder dumps are written "
          "to; unset = <tmpdir>/lighthouse_flight.")
_register("LHTPU_FLIGHT_DUMPS", "8",
          "Newest trip dumps kept on disk; older dump files are "
          "pruned.")
_register("LHTPU_FLIGHT_SPAN_MS", "50",
          "Floor in milliseconds under the flight recorder's slow-request "
          "rule: a closing root span is judged only above it, and files "
          "one slow_request with its stage table when it is also over 1.5 "
          "times the second longest of its name's last 64 closures "
          "(until a name has 8 closures the floor alone decides "
          "and the root is filed as a slow_span).")
_register("LHTPU_SLO_BUDGET_MS", "4000",
          "Per-slot SLO budget in milliseconds for the full "
          "gossip-to-head block pipeline; per-stage budgets are fixed "
          "fractions of it (chain/slo.STAGE_FRACTIONS).")
_register("LHTPU_SLO_RING", "128",
          "Slots the SLO engine tracks concurrently (older unscored "
          "slots are evicted, counted in tracing_evicted_total).")
_register("LHTPU_SLO_RESERVOIR", "1024",
          "Per-stage latency samples kept for the p50/p99/p999 "
          "quantile surface (bounded reservoir, newest-wins).")

# -- the persistent AOT program store + prewarmer (ops/program_store,
#    ops/prewarm, bench --child-coldstart) ------------------------------------

_register("LHTPU_AOT_STORE", "1",
          "0 kills the AOT program store entirely: no stored program "
          "is consulted, no compiled program is committed, the "
          "prewarmer never starts.")
_register("LHTPU_AOT_STORE_DIR", None,
          "Directory the serialized AOT executables (and the sha256 "
          "calibration record) persist in; unset disables the store "
          "(the client builder defaults it to <datadir>/aot_programs).")
_register("LHTPU_AOT_PREWARM", "auto",
          "Background startup prewarmer: 1 always runs it, 0 never, "
          "auto runs it on TPU platforms or when LHTPU_AOT_STORE_DIR "
          "is set explicitly (stored programs still serve lazily on "
          "first dispatch either way).")
_register("LHTPU_AOT_PREWARM_SCALE", "auto",
          "Prewarm driver workload scale (tiny|production|auto): auto "
          "= production shape buckets on TPU platforms, tiny on the "
          "XLA-CPU fallback (where production-width compiles cost "
          "minutes each).")

# -- chain health + fleet observatory (chain/chain_health, simulator,
#    bench --child-fleetwatch) ------------------------------------------------

_register("LHTPU_REORG_TRIP_DEPTH", "3",
          "Reorg depth (slots from the old head back to the fork "
          "point) at or beyond which the deep_reorg flight trip dumps "
          "the black box.")
_register("LHTPU_FINALITY_STALL_EPOCHS", "4",
          "Finality lag (epochs between the slot clock and the "
          "finalized checkpoint) that fires the finality_stall flight "
          "trip, once per stall episode (re-arms when finality "
          "advances).")
_register("LHTPU_FLEET_NODES", "4",
          "Node count for the bench --child-fleetwatch drill (the "
          "partition phase splits them into two equal halves).")
_register("LHTPU_FLEET_STEADY_SLOTS", "34",
          "Steady-phase slot count for --child-fleetwatch, also the "
          "length of each armed/unarmed overhead A/B leg (4 minimal-"
          "spec epochs + 2 so finality reaches epoch >= 2 before the "
          "partition).")
_register("LHTPU_FLEET_PARTITION_SLOTS", "12",
          "Slots the --child-fleetwatch 2/2 partition is held open "
          "(kept under the 16-block unknown-parent chase bound so the "
          "post-heal by-root sync converges in one chase).")
_register("LHTPU_FLEET_HEAL_SLOTS", "26",
          "Slots run after healing the --child-fleetwatch partition "
          "(must cover reconvergence plus enough epochs for finality "
          "to resume).")

# -- the chaos soak: seeded fault-plane composition + node lifecycle
#    (chain/chaos, simulator lifecycle, bench --child-chaossoak) --------------

_register("LHTPU_CHAOS_SEED", "1337",
          "ChaosPlan seed: same seed => byte-identical fault schedule "
          "(chain/chaos.build_plan; the soak's determinism pin).")
_register("LHTPU_CHAOS_NODES", "4",
          "Node count for the bench --child-chaossoak soak (floored at "
          "3 so one node can die without losing quorum).")
_register("LHTPU_CHAOS_SLOTS", "44",
          "Slot budget of the all-planes-armed soak phase; the plan "
          "keeps a quiet tail (~1/4) chaos-free so finality recovers "
          "inside the measured window.")
_register("LHTPU_CHAOS_FINALITY_LAG", "6",
          "Finality-lag bound in epochs the soak's settle phase must "
          "end within (current epoch minus finalized epoch).")
_register("LHTPU_CHAOS_KILL_EVERY", "10",
          "Kill cadence in slots for the ChaosPlan crash plane "
          "(staggered: at most one node down at a time; floored at "
          "4).")

# -- the process fleet: N beacon nodes as real OS processes
#    (lighthouse_tpu/fleet, bench --child-socksoak) ---------------------------

_register("LHTPU_FLEET_PROC_NODES", "3",
          "Node count for the bench --child-socksoak process fleet "
          "(floored at 3 so one SIGKILLed node leaves quorum).")
_register("LHTPU_FLEET_PORT_BASE", "0",
          "Port base for fleet children: 0 = ephemeral everywhere (the "
          "parent reads ports back from the startup handshake); a "
          "nonzero base pins node i at base+2i (wire) / base+2i+1 "
          "(http).")
_register("LHTPU_FLEET_LAUNCH_S", "45",
          "Per-node launch deadline in seconds: the child must print "
          "its startup handshake (ports + peer id) within this or the "
          "fleet tears down and fails the launch.")
_register("LHTPU_FLEET_REJOIN_S", "90",
          "Rejoin deadline in seconds for a relaunched node to catch "
          "back up to the fleet head (the socksoak lifecycle gate).")
_register("LHTPU_FLEET_SLOT_S", "3",
          "Seconds per slot for fleet children (devnet override via "
          "the bn --seconds-per-slot flag): the process soak runs on "
          "a real wall clock, so shorter slots bound the drill.")

# -- the pull observatory: per-node scrape discipline (simulator
#    ScrapeDiscipline, bench --child-scrapewatch) -----------------------------

_register("LHTPU_SCRAPE_DEADLINE_S", "2.0",
          "Watchdog deadline in seconds for one node-scrape attempt "
          "(guarded transports only; the direct in-memory source runs "
          "inline).  Floored at 0.05.")
_register("LHTPU_SCRAPE_RETRIES", "1",
          "Extra scrape attempts after a timeout/error before the "
          "scrape counts as failed for this slot (0 = single "
          "attempt).")
_register("LHTPU_SCRAPE_UNREACHABLE_AFTER", "3",
          "Consecutive failed scrapes after which the observer "
          "classifies a node unreachable (a monitoring-plane state, "
          "distinct from lifecycle down; floored at 1).")
_register("LHTPU_SCRAPE_CADENCE_SLOTS", "1",
          "Observer snapshot cadence: scrape the fleet every Nth slot "
          "(1 = every slot, the default and the pre-scrape-plane "
          "behavior).")


# -- typed readers ------------------------------------------------------------

# operator typos must not be silent: an unparseable SET value falls back,
# but says so once (per name per process) on stderr — stdlib-only module,
# so no structured logger here
_WARNED_UNPARSEABLE: set[str] = set()


def _warn_unparseable(name: str, val: str, expected: str) -> None:
    if name in _WARNED_UNPARSEABLE:
        return
    _WARNED_UNPARSEABLE.add(name)  # lhlint: allow(LH1003) — warn-once set: GIL-atomic add; a lost race costs one duplicate stderr line
    import sys

    print(f"lighthouse_tpu: ignoring unparseable {name}={val!r} "
          f"(expected {expected}); using the fallback", file=sys.stderr)


def get(name: str) -> str | None:
    """Raw string value: process environment first, registry default
    otherwise.  Raises KeyError on unregistered names — reads of
    unknown knobs are programming errors, not operator errors."""
    var = ENV_VARS[name]
    val = os.environ.get(name)
    return val if val is not None else var.default


def get_int(name: str, fallback: int | None = None) -> int | None:
    """Integer value, or ``fallback`` when unset or unparseable (a set
    but unparseable value warns once on stderr)."""
    val = get(name)
    if val is None:
        return fallback
    try:
        return int(val)
    except ValueError:
        _warn_unparseable(name, val, "an integer")
        return fallback


def get_float(name: str, fallback: float | None = None) -> float | None:
    """Float value, or ``fallback`` when unset or unparseable."""
    val = get(name)
    if val is None:
        return fallback
    try:
        return float(val)
    except ValueError:
        _warn_unparseable(name, val, "a number")
        return fallback


_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off", "")


def get_bool(name: str, fallback: bool | None = None) -> bool | None:
    """Boolean value, or ``fallback`` when unset or unparseable."""
    val = get(name)
    if val is None:
        return fallback
    low = val.strip().lower()
    if low in _TRUE:
        return True
    if low in _FALSE:
        return False
    _warn_unparseable(name, val, "a boolean (1/0/true/false)")
    return fallback


def get_choice(name: str, choices: tuple[str, ...],
               fallback: str | None = None) -> str | None:
    """Enum value normalized to lowercase/stripped, or ``fallback`` when
    unset or not one of ``choices`` (a set but invalid value warns once
    on stderr — same discipline as the numeric readers)."""
    val = get(name)
    if val is None:
        return fallback
    low = val.strip().lower()
    if low in choices:
        return low
    _warn_unparseable(name, val, "one of " + "|".join(choices))
    return fallback


def table() -> list[EnvVar]:
    """Registry entries sorted by name — the source of truth the README
    env-var table is checked against (lhlint LH402 both ways, plus the
    row-level sync test in tests/test_lint.py)."""
    return [ENV_VARS[k] for k in sorted(ENV_VARS)]
