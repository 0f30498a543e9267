"""Persistent AOT program store: serialized XLA executables, keyed by
the jit shape manifest.

Every node start used to pay the full jit warm-up (~100 s of
trace+lower+compile on the CPU fallback; `time_to_first_verify_seconds`
= 485 s cold for the device pipeline) because compiled programs died
with the process.  This module makes them durable: when a manifest
entry (`tools/lint/shape_manifest.json` — every ``jax.jit``
construction in the package, PR 7) dispatches a shape it has not seen,
the program is AOT-compiled via ``fn.lower(...).compile()``, serialized
with ``jax.experimental.serialize_executable``, and committed to a
store directory; the next process deserializes it straight into the
dispatch memo, so the first real call is a cache hit instead of a
trace+compile.

Key format (one file per program)::

    <store dir>/<fingerprint>/<entry tag><key hash>.aotx
    entry tag = sha256(entry id)[:12]   (leading: group-filterable)
    key hash  = sha256(entry|backend|sig)[:28]

- ``fingerprint`` = sha256 over {jax, jaxlib, platform, device_kind,
  device_count} — a jax upgrade or platform change invalidates the
  WHOLE program population at once (stale executables are never even
  opened), mirroring the ISSUE key ``(entry, bucket, backend, jax
  version, platform fingerprint)``;
- ``entry`` = the manifest entry id; ``backend`` = its owning backend;
- ``sig`` = the dispatch signature: shape+dtype token per array
  argument (the shape bucket), ``repr`` token per static argument.

File format: the PR 5 envelope (``store/envelope``: MAGIC + crc32 +
len) around a pickled record ``{v, key, entry, backend, sig, data}``.
Corruption of any kind — truncation, bit flips, an unpicklable body, a
key mismatch — is a COUNTED miss (``aot_store_misses_total{reason}``)
followed by a recompile; the damaged file is quarantined (unlinked) and
nothing ever crashes the dispatch path.  Commits are atomic
(temp file + ``os.replace``), so a torn write is indistinguishable from
corruption and heals the same way.  The store payload is pickle: the
directory is in the same trust domain as the beacon DB — it defends
against rot and torn writes, not adversaries (same stance as the
envelope's crc32).

Dispatch integration: :func:`configure` installs :func:`_dispatch` as
``device_telemetry``'s AOT hook, so every instrumented jit entry
consults the in-process memo first (source ``store_hit`` or
``compiled``) and falls back to the plain ``jax.jit`` path on ANY
miss or failure.  Compile-and-commit is single-flight per (entry, sig):
a concurrent background prewarmer and a foreground dispatch racing on
the same program produce exactly one store commit.

``LHTPU_AOT_STORE=0`` is the kill switch: nothing is consulted,
nothing is committed.  The store only activates when a directory is
configured (``LHTPU_AOT_STORE_DIR`` or ``configure(path)`` — the
client builder passes its datadir) — bare library use never touches
disk.

This module never imports jax at module scope (the lint fast paths and
the zero-XLA tests import it freely); jax loads lazily inside the
compile/serialize helpers only.
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib
import pickle
import threading
from functools import partial

from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.common import flight_recorder as _flight
from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed


def _envelope():
    """The PR 5 checksum envelope, imported lazily: pulling the store
    package at module scope would drag the whole DB/ssz/jax stack into
    every module that registers an entry."""
    from lighthouse_tpu.store import envelope

    return envelope

PAYLOAD_VERSION = 1
FILE_SUFFIX = ".aotx"
CALIBRATION_RECORD = "sha_calibration"
MSM_CALIBRATION_RECORD = "msm_calibration"

# -- declarative entry registry (lhlint LH606) --------------------------------
#
# Every shape-manifest entry must be registered here by its owning
# module (``register_entry(id, driver=...)``): the prewarmer uses the
# driver tag to know which production-path driver compiles/loads the
# entry, and LH606 fails the tree when a manifest entry has no
# registration (a new jit site silently outside the store would
# re-open the cold-start hole).

_REGISTERED: dict[str, str] = {}


def register_entry(entry_id: str, *, driver: str) -> None:
    """Declare that ``entry_id`` (a shape-manifest id) is served by the
    program store, prewarmed by the named :mod:`ops/prewarm` driver."""
    _REGISTERED[entry_id] = driver  # lhlint: allow(LH1003) — import-time/prewarm registration: idempotent GIL-atomic setitem, each driver owns its own keys


def registered_entries() -> dict[str, str]:
    """{manifest entry id: prewarm driver tag} for every registration."""
    return dict(_REGISTERED)


# -- manifest facts (statics per entry) ---------------------------------------

_MANIFEST_INFO: dict[str, dict] | None = None


def manifest_info() -> dict[str, dict]:
    """{entry id: {backend, static_argnums, static_argnames}} from the
    checked-in shape manifest ({} when absent — installed package).
    The path is device_telemetry's — ONE place knows where the
    manifest lives."""
    global _MANIFEST_INFO
    if _MANIFEST_INFO is None:
        from lighthouse_tpu.common import device_telemetry as _dtel

        info: dict[str, dict] = {}
        try:
            data = json.loads(_dtel._manifest_path().read_text())
            for e in data.get("entries", []):
                info[e["id"]] = {
                    "backend": e.get("backend", "-"),
                    "static_argnums": tuple(e.get("static_argnums") or ()),
                    "static_argnames": tuple(e.get("static_argnames") or ()),
                }
        except (OSError, ValueError, KeyError, TypeError) as e:
            record_swallowed("program_store.manifest", e)
        _MANIFEST_INFO = info
    return _MANIFEST_INFO


# -- dispatch signatures ------------------------------------------------------


class _UnsupportedArgs(Exception):
    """An argument the signature scheme cannot key (exotic object):
    the dispatch falls back to the plain jit path."""


_SCALAR_TYPES = (bool, int, float, str, bytes, type(None))


def _sig_token(a) -> str:
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        weak = "~w" if getattr(a, "weak_type", False) else ""
        return "x".join(str(int(d)) for d in shape) + f":{dtype}{weak}"
    if isinstance(a, _SCALAR_TYPES):
        r = repr(a)
        if len(r) > 64:
            raise _UnsupportedArgs(type(a).__name__)
        return "s:" + r
    if isinstance(a, tuple):
        return "t(" + ",".join(_sig_token(x) for x in a) + ")"
    if isinstance(a, list):
        return "l(" + ",".join(_sig_token(x) for x in a) + ")"
    if isinstance(a, dict):
        return "d(" + ",".join(
            f"{k}={_sig_token(a[k])}" for k in sorted(a)) + ")"
    raise _UnsupportedArgs(type(a).__name__)


def signature(args, kwargs) -> str | None:
    """Stable dispatch-signature string for one call (shape buckets for
    arrays, ``repr`` for statics), or None when an argument defies the
    scheme — the caller then leaves the dispatch to plain jax.jit."""
    try:
        sig = ";".join(_sig_token(a) for a in args)
        if kwargs:
            sig += "|" + ";".join(
                f"{k}={_sig_token(kwargs[k])}" for k in sorted(kwargs))
        return sig
    except _UnsupportedArgs:
        return None


def store_key(entry: str, backend: str, sig: str) -> str:
    return f"{entry}|{backend}|{sig}"


def _entry_tag(entry: str) -> str:
    """Filename prefix for one manifest entry (12 hex chars)."""
    return hashlib.sha256(entry.encode()).hexdigest()[:12]


# -- serialization seam (monkeypatchable: the resilience tests run
#    zero-XLA through fake payloads) ------------------------------------------


def _serialize_compiled(compiled) -> bytes:
    from jax.experimental import serialize_executable as se

    return pickle.dumps(se.serialize(compiled))


def _deserialize_payload(data: bytes, entry: str = "-"):
    from jax.experimental import serialize_executable as se

    with _load_span("aot.load.unpickle", entry, "unpickle"):
        payload, in_tree, out_tree = pickle.loads(data)
    with _load_span("aot.load.deserialize", entry, "deserialize"):
        return se.deserialize_and_load(payload, in_tree, out_tree)


def _fingerprint() -> dict:
    """Platform identity the program population is keyed by — anything
    that could make a serialized executable stale invalidates the whole
    fingerprint directory at once."""
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": devices[0].platform,
        "device_kind": getattr(devices[0], "device_kind", "?"),
        "device_count": len(devices),
    }


# -- metrics ------------------------------------------------------------------

# (plain Registry calls: Registry._get memoizes families and
# Counter.labels caches children under the registry's own lock, and
# these paths run per compile/load, not per dispatch)


def _record_hit() -> None:
    try:
        REGISTRY.counter(
            "aot_store_hits_total",
            "stored AOT programs deserialized and served from the "
            "program store").inc()
    except Exception as e:
        record_swallowed("program_store.metric", e)


def _record_miss(reason: str) -> None:
    try:
        REGISTRY.counter(
            "aot_store_misses_total",
            "program-store lookups that could not serve a stored "
            "program, by reason (corruption is a miss plus a "
            "recompile, never a crash)").labels(reason=reason).inc()
    except Exception as e:
        record_swallowed("program_store.metric", e)


def _record_load_stage(entry: str, stage: str, seconds: float) -> None:
    try:
        REGISTRY.histogram(
            "aot_store_load_seconds",
            "wall time of bringing one stored program back, by entry and "
            "stage: read (file to record), unpickle, deserialize "
            "(deserialize_and_load), first_call (first execution of a "
            "store_hit program)",
            buckets=(0.001, 0.01, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 180.0,
                     600.0),
        ).labels(entry=entry, stage=stage).observe(seconds)
    except Exception as e:
        record_swallowed("program_store.metric", e)


def _load_span(name: str, entry: str, stage: str):
    """A span of the load path whose duration also feeds
    ``aot_store_load_seconds{entry,stage}``."""
    return tracing.span(
        name, entry=entry,
        observe=partial(_record_load_stage, entry, stage))


def _record_commit(outcome: str) -> None:
    try:
        REGISTRY.counter(
            "aot_store_commits_total",
            "serialized-program commits to the store directory, by "
            "outcome").labels(outcome=outcome).inc()
    except Exception as e:
        record_swallowed("program_store.metric", e)


# -- the on-disk store --------------------------------------------------------


class ProgramStore:
    """Directory of envelope-wrapped serialized executables, segmented
    by platform fingerprint.  All read paths treat damage as a counted
    miss; all write paths are atomic."""

    def __init__(self, root: str | os.PathLike):
        self.root = pathlib.Path(root)
        self._fp: dict | None = None
        self._fpdir: pathlib.Path | None = None
        self._lock = threading.Lock()
        # cheap live totals for the observatory endpoint (the counters
        # above are the metric surface); bumped under the lock — the
        # prewarm thread and foreground dispatches race these, and an
        # unlocked += loses counts (the PR 8 ProcessorMetrics lesson)
        self.hits = 0
        self.misses = 0
        self.commits = 0

    def _bump(self, attr: str) -> None:
        with self._lock:
            setattr(self, attr, getattr(self, attr) + 1)

    # fingerprint directory (lazy: computing it imports jax)

    def fingerprint(self) -> dict:
        with self._lock:
            if self._fp is None:
                self._fp = _fingerprint()
            return dict(self._fp)

    def fpdir(self) -> pathlib.Path:
        with self._lock:
            if self._fpdir is None:
                if self._fp is None:
                    self._fp = _fingerprint()
                tag = hashlib.sha256(json.dumps(
                    self._fp, sort_keys=True).encode()).hexdigest()[:16]
                d = self.root / tag
                d.mkdir(parents=True, exist_ok=True)
                meta = d / "fingerprint.json"
                if not meta.exists():
                    self._atomic_write(
                        meta, json.dumps(self._fp, indent=1).encode())
                self._fpdir = d
            return self._fpdir

    def _path(self, key: str) -> pathlib.Path:
        # <entry tag><key hash>.aotx — the leading entry tag lets the
        # prewarmer read ONLY one backend group's files (a multi-
        # hundred-MB store never has to be memory-resident at once)
        entry = key.split("|", 1)[0]
        name = (_entry_tag(entry)
                + hashlib.sha256(key.encode()).hexdigest()[:28])
        return self.fpdir() / (name + FILE_SUFFIX)

    @staticmethod
    def _atomic_write(path: pathlib.Path, data: bytes) -> None:
        tmp = path.with_name(path.name + f".tmp{os.getpid()}")
        tmp.write_bytes(data)
        os.replace(tmp, path)

    @staticmethod
    def _quarantine(path: pathlib.Path) -> None:
        try:
            path.unlink()
        except OSError as e:
            record_swallowed("program_store.quarantine", e)

    def _read_record(self, path: pathlib.Path, what: str) -> dict | None:
        """Envelope-checked record read; any damage is a counted miss
        plus a flight-recorder corruption event, never an exception."""
        try:
            data = path.read_bytes()
        except FileNotFoundError:
            self._bump("misses")
            _record_miss("absent")
            return None
        except OSError as e:
            record_swallowed("program_store.read", e)
            self._bump("misses")
            _record_miss("io")
            return None
        env = _envelope()
        try:
            payload = env.unwrap(data, what=what)
            rec = pickle.loads(payload)
            if (not isinstance(rec, dict)
                    or rec.get("v") != PAYLOAD_VERSION
                    or "data" not in rec):
                raise env.StoreCorruptionError(
                    f"{what}: not a v{PAYLOAD_VERSION} program record")
        except Exception as e:  # unpickling garbage raises ~anything
            record_swallowed("program_store.corrupt", e)
            self._bump("misses")
            _record_miss("corrupt")
            _flight.emit("aot_store_corrupt", record=what,
                         error=f"{type(e).__name__}: {e}"[:200])
            self._quarantine(path)
            return None
        return rec

    def get(self, key: str) -> dict | None:
        """The stored record for ``key`` ({v, key, entry, backend, sig,
        data}) or None (counted miss).  A record whose embedded key
        disagrees (hash collision, hand-copied file) is corruption.
        NOT counted as a hit here: the hit lands only once the payload
        actually deserializes into a serving program (a record whose
        executable the runtime rejects is a ``load_failed`` miss, never
        a hit+miss double-count)."""
        rec = self._read_record(self._path(key), key.split("|", 1)[0])
        if rec is None:
            return None
        if rec.get("key") != key:
            self._bump("misses")
            _record_miss("corrupt")
            _flight.emit("aot_store_corrupt", record=key,
                         error="embedded key mismatch")
            self._quarantine(self._path(key))
            return None
        return rec

    def record_served(self) -> None:
        """One stored program deserialized into the dispatch memo."""
        self._bump("hits")
        _record_hit()

    def put(self, key: str, entry: str, backend: str, sig: str,
            data: bytes) -> bool:
        rec = {"v": PAYLOAD_VERSION, "key": key, "entry": entry,
               "backend": backend, "sig": sig, "data": data}
        try:
            self._atomic_write(self._path(key),
                               _envelope().wrap(pickle.dumps(rec)))
        except OSError as e:
            record_swallowed("program_store.commit", e)
            _record_commit("failed")
            return False
        self._bump("commits")
        _record_commit("committed")
        return True

    def iter_records(self, entries=None, exclude=None):
        """Yield readable program records in the fingerprint dir
        (damaged files are counted misses and quarantined in passing).
        ``entries``/``exclude`` filter BY FILENAME PREFIX before any
        byte is read, so a group pass touches only its own files.  Each
        record carries its source path under ``"_path"`` so a payload
        that later fails to deserialize can be quarantined too."""
        try:
            paths = sorted(self.fpdir().glob("*" + FILE_SUFFIX))
        except OSError as e:
            record_swallowed("program_store.scan", e)
            return
        if entries is not None:
            tags = {_entry_tag(e) for e in entries}
            paths = [p for p in paths if p.name[:12] in tags]
        if exclude:
            extags = {_entry_tag(e) for e in exclude}
            paths = [p for p in paths if p.name[:12] not in extags]
        for path in paths:
            rec = self._read_record(path, path.name)
            if rec is not None:
                rec["_path"] = str(path)
                yield rec

    # -- calibration sidecars (sha256 / msm device thresholds) ------------

    def _calibration_path(
            self, record: str = CALIBRATION_RECORD) -> pathlib.Path:
        return self.fpdir() / f"{record}.json"

    def save_calibration(self, data: dict,
                         record: str = CALIBRATION_RECORD) -> bool:
        try:
            self._atomic_write(
                self._calibration_path(record),
                _envelope().wrap(json.dumps(data, sort_keys=True).encode()))
            return True
        except (OSError, TypeError, ValueError) as e:
            record_swallowed("program_store.calibration_save", e)
            return False

    def load_calibration(
            self, record: str = CALIBRATION_RECORD) -> dict | None:
        path = self._calibration_path(record)
        try:
            raw = path.read_bytes()
        except FileNotFoundError:
            return None
        except OSError as e:
            record_swallowed("program_store.calibration_read", e)
            return None
        env = _envelope()
        try:
            data = json.loads(env.unwrap(raw, what=record))
            if not isinstance(data, dict):
                raise env.StoreCorruptionError(
                    f"{record}: not a measurement object")
            return data
        except (env.StoreCorruptionError, ValueError) as e:
            record_swallowed("program_store.calibration_corrupt", e)
            _record_miss("corrupt")
            _flight.emit("aot_store_corrupt", record=record,
                         error=f"{type(e).__name__}: {e}"[:200])
            self._quarantine(path)
            return None


# -- loaded programs + the dispatch memo --------------------------------------


class _LoadedProgram:
    """One deserialized/compiled executable plus the calling convention
    (the ``jax.stages.Compiled`` signature drops static args)."""

    __slots__ = ("compiled", "static_argnums", "static_argnames", "source",
                 "first_call_pending")

    def __init__(self, compiled, info: dict, source: str):
        self.compiled = compiled
        self.static_argnums = frozenset(info.get("static_argnums") or ())
        self.static_argnames = frozenset(info.get("static_argnames") or ())
        self.source = source
        # a reloaded executable may finish loading on its first execution:
        # that call is timed as the load path's last stage
        self.first_call_pending = source == "store_hit"

    def call(self, args, kwargs):
        if self.static_argnums:
            args = tuple(a for i, a in enumerate(args)
                         if i not in self.static_argnums)
        if self.static_argnames and kwargs:
            kwargs = {k: v for k, v in kwargs.items()
                      if k not in self.static_argnames}
        return self.compiled(*args, **kwargs)


class _State:
    """The active store plus the in-process dispatch memo."""

    def __init__(self, store: ProgramStore):
        self.store = store
        self.memo: dict[tuple, _LoadedProgram] = {}
        self.bad: set[tuple] = set()
        self.lock = threading.Lock()
        self.key_locks: dict[tuple, threading.Lock] = {}


_STATE: _State | None = None
_STATE_LOCK = threading.Lock()


def enabled() -> bool:
    """The LHTPU_AOT_STORE kill switch (default on; the store still
    needs a configured directory to do anything)."""
    return envreg.get_bool("LHTPU_AOT_STORE", True) is not False


def configure(root: str | os.PathLike) -> ProgramStore | None:
    """Activate the store at ``root`` and install the AOT dispatch hook
    into device_telemetry.  Returns None (fully inert) when the
    LHTPU_AOT_STORE kill switch is off."""
    global _STATE
    if not enabled():
        return None
    from lighthouse_tpu.common import device_telemetry as _dtel

    with _STATE_LOCK:
        _STATE = _State(ProgramStore(root))
        _dtel.set_aot_dispatcher(_dispatch)
        return _STATE.store


def configure_from_env() -> ProgramStore | None:
    """Activate from LHTPU_AOT_STORE_DIR (None when unset or the kill
    switch is off) — the client builder and bench children call this."""
    if not enabled():
        return None
    root = envreg.get("LHTPU_AOT_STORE_DIR")
    if not root:
        return None
    return configure(root)


def deactivate() -> None:
    """Drop the active store and uninstall the dispatch hook (tests;
    also the error path when a configured directory proves unusable)."""
    global _STATE
    from lighthouse_tpu.common import device_telemetry as _dtel

    with _STATE_LOCK:
        _STATE = None
        _dtel.set_aot_dispatcher(None)


def active() -> ProgramStore | None:
    st = _STATE
    return st.store if st is not None else None


def memo_stats() -> dict:
    """{entry id: {source: programs}} over the loaded dispatch memo."""
    st = _STATE
    if st is None:
        return {}
    out: dict[str, dict] = {}
    with st.lock:
        for (entry, _sig), prog in st.memo.items():
            row = out.setdefault(entry, {})
            row[prog.source] = row.get(prog.source, 0) + 1
    return out


def status() -> dict:
    """Observatory surface: configuration + live store totals."""
    st = _STATE
    if st is None:
        return {"configured": False, "enabled": enabled()}
    with st.lock:
        programs = len(st.memo)
        bad = len(st.bad)
    return {
        "configured": True,
        "enabled": True,
        "dir": str(st.store.root),
        "fingerprint": dict(st.store._fp) if st.store._fp else None,
        "memo_programs": programs,
        "bad_signatures": bad,
        "hits": st.store.hits,
        "misses": st.store.misses,
        "commits": st.store.commits,
        "registered_entries": len(_REGISTERED),
    }


# -- the dispatch hook --------------------------------------------------------


def _dispatch(entry: str, fn, args, kwargs):
    """device_telemetry's AOT hook: serve ``entry``'s call from the
    memo, loading or single-flight compiling+committing on a miss.
    Returns (out, source, compiled_now) or None — None means "plain
    jax.jit path, please" and is the answer to EVERY failure mode."""
    st = _STATE
    if st is None:
        return None
    sig = signature(args, kwargs)
    if sig is None:
        return None
    mkey = (entry, sig)
    prog = st.memo.get(mkey)
    compiled_now = False
    if prog is None:
        if mkey in st.bad:
            return None
        prog, compiled_now = _load_or_compile(st, entry, fn, args,
                                              kwargs, sig, mkey)
        if prog is None:
            return None
    try:
        if prog.first_call_pending:
            prog.first_call_pending = False
            with _load_span("aot.first_call", entry, "first_call"):
                out = prog.call(args, kwargs)
        else:
            out = prog.call(args, kwargs)
    except Exception as e:
        # an aval/pytree mismatch or a runtime failure: evict so the
        # next call goes straight to jax.jit instead of failing again
        record_swallowed("program_store.call", e)
        _record_miss("call_failed")
        with st.lock:
            st.bad.add(mkey)
            st.memo.pop(mkey, None)
        return None
    return out, prog.source, compiled_now


def _load_or_compile(st: _State, entry: str, fn, args, kwargs, sig: str,
                     mkey: tuple):
    """Single-flight per (entry, sig): exactly one thread loads or
    compiles+commits; racers wait and adopt the winner's program."""
    with st.lock:
        klock = st.key_locks.setdefault(mkey, threading.Lock())
    with klock:
        prog = st.memo.get(mkey)
        if prog is not None:
            return prog, False
        if mkey in st.bad:
            return None, False
        info = manifest_info().get(entry, {})
        key = store_key(entry, info.get("backend", "-"), sig)
        try:
            with _load_span("aot.load.read", entry, "read"):
                rec = st.store.get(key)
        except OSError as e:
            # the directory itself is unusable (read-only fs, wrong
            # perms): deactivate rather than pay a failing mkdir +
            # swallowed exception on EVERY dispatch for process life —
            # the node keeps serving on plain jax.jit
            record_swallowed("program_store.store_io", e)
            _record_miss("io")
            deactivate()
            return None, False
        if rec is not None:
            try:
                compiled = _deserialize_payload(rec["data"], entry)
            except Exception as e:
                record_swallowed("program_store.load", e)
                st.store._bump("misses")
                _record_miss("load_failed")
                st.store._quarantine(st.store._path(key))
            else:
                prog = _LoadedProgram(compiled, info, "store_hit")
                with st.lock:
                    st.memo[mkey] = prog
                st.store.record_served()
                return prog, False
        try:
            compiled = fn.lower(*args, **kwargs).compile()
        except Exception as e:
            record_swallowed("program_store.compile", e)
            _record_miss("compile_failed")
            with st.lock:
                st.bad.add(mkey)
            return None, False
        prog = _LoadedProgram(compiled, info, "compiled")
        with st.lock:
            st.memo[mkey] = prog
        try:
            data = _serialize_compiled(compiled)
        except Exception as e:
            # the program still serves this process; it just won't
            # survive a restart — counted so the gap is visible
            record_swallowed("program_store.serialize", e)
            _record_commit("serialize_failed")
        else:
            st.store.put(key, entry, info.get("backend", "-"), sig, data)
        return prog, True


# -- startup loading (prewarm phase A) ----------------------------------------


def load_records(recs, stop=None) -> dict:
    """Deserialize already-scanned records straight into the dispatch
    memo (source ``store_hit``).  A payload the runtime rejects is a
    counted ``load_failed`` miss AND a quarantine, same as the
    foreground path; the serialized bytes are released record by
    record.  Returns {"loaded": n, "failed": n, "entries": {entry: n}}."""
    st = _STATE
    report = {"loaded": 0, "failed": 0, "entries": {}}
    if st is None:
        return report
    for rec in recs:
        if stop is not None and stop.is_set():
            break
        entry = rec.get("entry", "?")
        sig = rec.get("sig", "")
        mkey = (entry, sig)
        path = rec.pop("_path", None)
        data = rec.pop("data", None)
        if data is None:
            continue  # already consumed by an earlier pass
        # the SAME single-flight lock the foreground dispatch takes:
        # without it both sides deserialize the same multi-MB payload
        # concurrently (double memory, double hit count) and a program
        # the foreground evicts to the bad set mid-deserialize could be
        # re-installed (check-then-act)
        with st.lock:
            klock = st.key_locks.setdefault(mkey, threading.Lock())
        with klock:
            with st.lock:
                # honor the memo AND the bad set under the key lock: a
                # rejected program must not be resurrected
                if mkey in st.memo or mkey in st.bad:
                    continue
                info = manifest_info().get(entry, {})
            try:
                compiled = _deserialize_payload(data, entry)
            except Exception as e:
                record_swallowed("program_store.load", e)
                st.store._bump("misses")
                _record_miss("load_failed")
                if path is not None:
                    st.store._quarantine(pathlib.Path(path))
                report["failed"] += 1
                continue
            prog = _LoadedProgram(compiled, info, "store_hit")
            with st.lock:
                st.memo[mkey] = prog
            st.store.record_served()
        report["loaded"] += 1
        report["entries"][entry] = report["entries"].get(entry, 0) + 1
    return report


def load_store_programs(priority=None, stop=None, entries=None,
                        exclude=None) -> dict:
    """Scan + load in one call.  ``priority`` maps an entry id to a
    sort rank; ``entries``/``exclude`` restrict the pass by entry id —
    filtered at the FILENAME level (the entry tag leads each file
    name), so a restricted pass reads only its own group's bytes."""
    st = _STATE
    if st is None:
        return {"loaded": 0, "failed": 0, "entries": {}}
    recs = [r for r in st.store.iter_records(entries=entries,
                                             exclude=exclude)
            if entries is None or r.get("entry") in entries]
    if priority is not None:
        recs.sort(key=lambda r: priority(r.get("entry", "")))
    return load_records(recs, stop=stop)


# -- calibration facade -------------------------------------------------------


def save_calibration(data: dict, record: str = CALIBRATION_RECORD) -> bool:
    st = _STATE
    return (st.store.save_calibration(data, record)
            if st is not None else False)


def load_calibration(record: str = CALIBRATION_RECORD) -> dict | None:
    st = _STATE
    return st.store.load_calibration(record) if st is not None else None
