"""Background AOT prewarmer: load the program store, compile the misses.

On node start the PR 4/PR 6 ladders serve live traffic on the reference
backends while this module makes the device plane hot in the
background:

1. **Load phase** — every serialized executable in the program store
   (for this platform fingerprint) is deserialized straight into the
   dispatch memo, highest-priority backends first, so the first real
   call at a stored shape is a cache hit (source ``store_hit``) instead
   of a trace+compile.
2. **Driver phase** — production-path drivers walk the shape-manifest
   entries in priority order (BLS verify lanes first, then
   sha256/merkle, KZG/DAS, epoch, shuffle — the order a fresh node
   needs them to verify its first block) dispatching each entry at its
   prewarm shape: entries already loaded serve from the memo in
   milliseconds, misses compile through the single-flight
   compile+commit path in :mod:`ops/program_store` so the NEXT start
   loads them.  Each driver is the real production call path (the BLS
   drivers complete real verifications, recording
   ``time_to_first_verify_seconds`` per backend), never a synthetic
   lowering — what goes hot is exactly what serving traffic will run.
3. **Calibration** — the sha256 device-threshold micro-calibration
   (PR 2) is loaded from the store when a measurement for this
   fingerprint exists, else measured once and persisted, so restart
   skips the re-calibration.

Workload scale: ``LHTPU_AOT_PREWARM_SCALE`` picks tiny or production
shape buckets (``auto`` = production on TPU, tiny on the XLA-CPU
fallback where production-width compiles cost minutes each).  Shapes a
node actually serves that the drivers did not cover are committed
lazily by the foreground dispatch path — the store converges on the
node's real working set after one cold pass.

``run()`` is spawned as a TaskExecutor task by the client builder
(gated on ``LHTPU_AOT_PREWARM``); bench's ``--child-coldstart`` calls
it synchronously and reads the report.
"""

from __future__ import annotations

import time

from lighthouse_tpu.common import env as envreg
from lighthouse_tpu.common import flight_recorder as _flight
from lighthouse_tpu.common.logging import Logger
from lighthouse_tpu.common.metrics import REGISTRY, record_swallowed
from lighthouse_tpu.ops import program_store

#: driver priority, the ISSUE 12 order amended by ISSUE 17: the unified
#: MSM plane loads FIRST (the BLS verify driver dispatches its blinded
#: fold internally, so its programs must be resident by then), then the
#: BLS verify lanes (a production client must verify its first block),
#: the merkle hashers, the blob planes, the epoch pass, the shuffle,
#: and the multichip dryrun fold last
DRIVER_ORDER = ("msm", "bls", "pairing", "sharded", "sha256", "kzg",
                "fr", "epoch", "shuffle", "dryrun")


def _import_owners() -> None:
    """Import every module that owns shape-manifest entries: the LH606
    registrations happen at module import, and the walk below needs the
    registry complete before it builds the driver plan."""
    from lighthouse_tpu.crypto import das, kzg  # noqa: F401
    from lighthouse_tpu.ops import (  # noqa: F401
        bls12_381, bls_backend, dispatch_pipeline, epoch_kernels, fr,
        msm, pubkey_kernels, sha256)
    from lighthouse_tpu.parallel import (  # noqa: F401
        bls_sharded, dryrun_worker)


def _resolve_scale() -> str:
    scale = envreg.get_choice("LHTPU_AOT_PREWARM_SCALE",
                              ("tiny", "production", "auto"), "auto")
    if scale != "auto":
        return scale
    import jax

    return "production" if jax.devices()[0].platform == "tpu" else "tiny"


def entry_priority(entry_id: str) -> int:
    """Sort rank for the load phase: the rank of the entry's prewarm
    driver (unregistered entries load last)."""
    driver = program_store.registered_entries().get(entry_id)
    try:
        return DRIVER_ORDER.index(driver)
    except ValueError:
        return len(DRIVER_ORDER)


def _record_outcome(outcome: str, n: int = 1) -> None:
    if n <= 0:
        return
    try:
        REGISTRY.counter(
            "aot_prewarm_entries_total",
            "prewarm-walked manifest entries by outcome: loaded (served "
            "from the program store), compiled (AOT-compiled and "
            "committed this start), missing (driver ran but the entry "
            "reported no program), failed (driver raised), skipped "
            "(prewarm disabled or aborted)",
        ).labels(outcome=outcome).inc(n)
    except Exception as e:
        record_swallowed("prewarm.metric", e)


# -- drivers (each is the production call path at a prewarm shape) ------------


def _fresh_sets(n_sets: int, n_keys: int = 1, tag: bytes = b"prewarm"):
    from lighthouse_tpu.crypto import bls

    sets = []
    for i in range(n_sets):
        msg = tag + bytes([i % 256, i // 256])
        sks = [bls.SecretKey.generate() for _ in range(n_keys)]
        sig = (bls.Signature.aggregate([sk.sign(msg) for sk in sks])
               if n_keys > 1 else sks[0].sign(msg))
        # re-wrap from bytes: fresh (unchecked) signatures force the
        # device psi subgroup batch, exactly like gossip arrivals
        sets.append(bls.SignatureSet(
            bls.Signature(sig.to_bytes()),
            [sk.public_key() for sk in sks], msg))
    return sets


def _drv_bls(scale: str) -> None:
    """The fused verify plane: pipeline, psi subgroup batches, the
    per-set aggregation segment-sum — plus the two cold-start headline
    verifications (reference then tpu)."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.ops import bls_backend

    # plain calls + raise, not assert: python -O must not strip the
    # dispatches that make the highest-priority lanes hot
    if not bls.verify_signature_sets(_fresh_sets(1, tag=b"ref"),
                                     backend="reference"):
        raise RuntimeError("prewarm reference verify rejected")
    # 2 sets x 9 keys routes per-set aggregation through the device
    # segment-sum (n_members - n >= 16); production scale additionally
    # walks a chunk-sized batch so the serving bucket compiles
    if not bls.verify_signature_sets(_fresh_sets(2, n_keys=9),
                                     backend="tpu"):
        raise RuntimeError("prewarm device verify rejected")
    if scale == "production":
        from lighthouse_tpu.ops import dispatch_pipeline as dp

        if not bls.verify_signature_sets(
                _fresh_sets(dp.chunk_size(None), tag=b"bulk"),
                backend="tpu"):
            raise RuntimeError("prewarm chunk-bucket verify rejected")
    if not bool(bls_backend.batch_subgroup_check_g1(
            [cv.g1_generator()])[0]):
        raise RuntimeError("prewarm G1 subgroup check rejected")


def _drv_pairing(scale: str) -> None:
    """The pairing plane outside the fused pipeline: multi-pairing
    Miller+reduce and the chunk-combine Fq12 kernel."""
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.ops import bls12_381 as b381
    from lighthouse_tpu.ops import dispatch_pipeline as dp

    f = b381.multi_pairing_device([(cv.g1_generator(), cv.g2_generator())])
    dev = b381.fq12_to_device(f)
    dp.combine_partials([dev, dev])


def _drv_sharded(scale: str) -> None:
    from lighthouse_tpu.parallel import bls_sharded

    if not bls_sharded.verify_signature_sets_sharded(
            _fresh_sets(1, tag=b"shard")):
        raise RuntimeError("prewarm sharded verify rejected")


def _drv_msm(scale: str) -> None:
    """The unified MSM plane (ops/msm): every track's program at its
    prewarm bucket — the plain g1 fold (kzg lincomb + das cell-proof
    chunk shapes), the fused gather fold, and the blinded merge — each
    gated by host point math (a mis-prewarmed program must never serve
    commitments or committee aggregates)."""
    import numpy as np

    from lighthouse_tpu.crypto import das, kzg
    from lighthouse_tpu.crypto.bls import curve as cv
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls_backend, pubkey_kernels

    # plain g1 track at the lincomb/calibration bucket
    pts = [cv.g1_mul(cv.g1_generator(), 3 + i) for i in range(2)]
    got = kzg.g1_lincomb(pts, [3, 5], device=True)
    want = cv.g1_add(cv.g1_mul(pts[0], 3), cv.g1_mul(pts[1], 5))
    if got != want:
        raise RuntimeError("prewarmed g1 fold mismatches host adds")
    # the das cell-proof chunk shape rides the same program
    das._batched_cell_proof_msms([[1, 2], [3, 4]],
                                 kzg.KzgSettings.dev(width=16))
    # gather track over a tiny resident table
    lanes = 64 if scale == "production" else 2
    table = pubkey_kernels.build_table(pts)
    rows = np.arange(lanes, dtype=np.int64) % 2
    scalars = (np.arange(lanes, dtype=np.uint64) % 7) + 1
    groups = np.zeros(lanes, np.int64)
    xa, ya, inf = pubkey_kernels.gather_fold(table, rows, scalars,
                                             groups, 1)
    want = cv.INF
    for r, s in zip(rows, scalars):
        want = cv.g1_add(want, cv.g1_mul(pts[int(r)], int(s)))
    got = (int(bi.from_mont(xa[0])), int(bi.from_mont(ya[0])))
    if bool(inf[0]) or got != want:
        raise RuntimeError("prewarmed gather fold mismatches host adds")
    # blinded-merge track via the per-set aggregation front end (its
    # lanes gathered from the resident key table by _blinded_lanes)
    sets = _fresh_sets(2, n_keys=2, tag=b"msm")
    bx, by, binf = bls_backend.aggregate_pubkeys_device(sets)
    for i, s in enumerate(sets):
        want = cv.INF
        for pk in s.pubkeys:
            want = cv.g1_add(want, pk.point)
        got = (int(bi.from_mont(bx[i])), int(bi.from_mont(by[i])))
        if bool(binf[i]) or got != want:
            raise RuntimeError(
                "prewarmed blinded fold mismatches host adds")


def _drv_sha256(scale: str) -> None:
    """The merkle hashers at their serving buckets: the pair hash, the
    single-block message sweep, both whole-fold programs and the
    validator element roots."""
    import jax.numpy as jnp
    import numpy as np

    from lighthouse_tpu.ops import sha256 as sha_ops
    from lighthouse_tpu.types.registry import Validators

    if scale == "production":
        pairs = min(max(sha_ops._DEVICE_MIN_PAIRS, 2048), 1 << 15)
        leaves = min(max(sha_ops._DEVICE_FOLD_MIN_LEAVES, 4096), 1 << 16)
    else:
        pairs, leaves = 2, 4
    sha_ops.sha256_block(jnp.zeros((pairs, 8), jnp.uint32),
                         jnp.zeros((pairs, 16), jnp.uint32))
    sha_ops.hash_pairs_device(jnp.zeros((pairs, 16), jnp.uint32))
    sha_ops._fold_levels_device(jnp.zeros((leaves, 8), jnp.uint32))
    sha_ops._fold_to_root_jit(jnp.zeros((leaves, 8), jnp.uint32))
    # the registry's element roots at the row count their routing starts at
    sha_ops.validator_roots(Validators(pairs).columns())
    # host-path sanity so a mis-prewarmed program can never serve: the
    # device fold of a known tree must match hashlib
    probe = np.arange(4 * 8, dtype=np.uint32).reshape(4, 8)
    want = sha_ops.hash_pairs_np(sha_ops.hash_pairs_np(
        probe.reshape(2, 16)).reshape(1, 16))
    got = np.asarray(sha_ops._fold_to_root_jit(jnp.asarray(probe)))
    if not np.array_equal(want, got):
        raise RuntimeError("prewarmed sha256 fold mismatches hashlib")


def _kzg_blob(settings, seed: int) -> bytes:
    import hashlib

    from lighthouse_tpu.crypto import kzg
    from lighthouse_tpu.crypto.bls.fields import R as FR_MOD

    vals = [int.from_bytes(hashlib.sha256(
        bytes([seed, i % 256])).digest(), "big") % FR_MOD
        for i in range(settings.width)]
    return b"".join(kzg.bls_field_to_bytes(v) for v in vals)


def _drv_kzg(scale: str) -> None:
    from lighthouse_tpu.crypto import kzg

    width = 64 if scale == "production" else 16
    settings = kzg.KzgSettings.dev(width=width)
    # the 2-lane device lincomb itself is prewarmed by the msm driver
    n = kzg._DEVICE_EVAL_MIN
    blobs = [_kzg_blob(settings, 40 + i) for i in range(n)]
    cs = [kzg.blob_to_kzg_commitment(b, settings) for b in blobs]
    proofs = [kzg.compute_blob_kzg_proof(b, c, settings)
              for b, c in zip(blobs, cs)]
    if not kzg.verify_blob_kzg_proof_batch(blobs, cs, proofs, settings):
        raise RuntimeError("prewarm KZG batch did not verify")


def _drv_fr(scale: str) -> None:
    from lighthouse_tpu.crypto import kzg
    from lighthouse_tpu.crypto.bls.fields import R as FR_MOD
    from lighthouse_tpu.ops import fr as fr_ops
    import numpy as np

    width = 8
    settings = kzg.KzgSettings.dev(width=width)
    polys = [[(i * 7 + j + 1) % FR_MOD for j in range(width)]
             for i in range(2)]
    raw = np.stack([np.stack([fr_ops._int_to_limbs(v) for v in p])
                    for p in polys])
    fr_ops.evaluate_polynomials_batch(raw, [11, 13], settings.roots_brp)
    # the cell batch's aggregated interpolation: two cells in two columns
    out, _ = fr_ops.interpolate_cells_dispatch(
        raw[:, :2].reshape(1, 2, 1, 2, fr_ops.L),
        fr_ops.to_mont_host([[[3], [5]]]),
        fr_ops.to_mont_host([[1, 1], [1, FR_MOD - 1]]),
        fr_ops.to_mont_host([[[1, 2]], [[1, 4]]]))
    fr_ops.interpolation_scalars(out)


def _drv_epoch(scale: str) -> None:
    # the device seam is called directly (NOT via an LHTPU_EPOCH_BACKEND
    # env flip: the prewarmer runs concurrently with live epoch
    # processing on a serving node, and a process-wide env mutation
    # would force a cold device rung under it)
    from lighthouse_tpu.state_transition import epoch_device
    from lighthouse_tpu.testing import randomized_registry_state

    n = 4096 if scale == "production" else 256
    state, spec = randomized_registry_state(n, "altair", seed=11,
                                            eject_frac=0.0)
    out = epoch_device.prepare_and_run(state.copy(), spec, "altair",
                                       "device")
    if out is None:
        raise RuntimeError("epoch device pass declined the prewarm state")


def _drv_shuffle(scale: str) -> None:
    import numpy as np

    from lighthouse_tpu.state_transition import shuffle as shuffle_mod

    n, rounds = ((1 << 14, 90) if scale == "production" else (512, 10))
    shuffle_mod.shuffle_list(np.arange(n), b"\x07" * 32, rounds,
                             device=True)


def _drv_dryrun(scale: str) -> None:
    from lighthouse_tpu.parallel import dryrun_worker

    dryrun_worker._merkle_dryrun(1)


_DRIVERS = {
    "msm": _drv_msm,
    "bls": _drv_bls,
    "pairing": _drv_pairing,
    "sharded": _drv_sharded,
    "sha256": _drv_sha256,
    "kzg": _drv_kzg,
    "fr": _drv_fr,
    "epoch": _drv_epoch,
    "shuffle": _drv_shuffle,
    "dryrun": _drv_dryrun,
}


# -- calibration persistence --------------------------------------------------


def calibration_step() -> dict:
    """Load the persisted sha256 device-threshold calibration for this
    fingerprint, or measure once and persist it.  An explicit
    LHTPU_SHA_DEVICE_MIN pin bypasses both (operator override)."""
    from lighthouse_tpu.ops import sha256 as sha_ops

    if envreg.get_int("LHTPU_SHA_DEVICE_MIN") is not None:
        return {"source": "env",
                **sha_ops.calibrate_device_thresholds()}
    stored = program_store.load_calibration()
    if stored is not None and sha_ops.apply_calibration(stored):
        return {**stored, "source": "store"}
    measured = sha_ops.calibrate_device_thresholds(force=True)
    program_store.save_calibration(measured)
    return {"source": "measured", **measured}


def _calibrate_into(report: dict) -> None:
    """One calibration attempt recorded into the report (a failure is
    accounted, never fatal to the walk)."""
    try:
        report["calibration"] = calibration_step()
    except Exception as e:
        record_swallowed("prewarm.calibration", e)
        report["calibration"] = {"source": "failed",
                                 "error": f"{type(e).__name__}: {e}"}


def msm_calibration_step() -> dict:
    """Load the persisted MSM device-threshold calibration for this
    fingerprint, or measure once and persist it (its own sidecar record
    next to the sha one).  An explicit LHTPU_MSM_DEVICE_MIN pin
    bypasses both, and LHTPU_MSM_CALIBRATION=0 disables measurement
    entirely (static defaults serve)."""
    from lighthouse_tpu.ops import msm as msm_ops

    if envreg.get_int("LHTPU_MSM_DEVICE_MIN") is not None:
        return {"source": "env",
                **msm_ops.calibrate_device_thresholds()}
    if envreg.get_bool("LHTPU_MSM_CALIBRATION", True) is False:
        return {"source": "disabled"}
    stored = program_store.load_calibration(
        record=program_store.MSM_CALIBRATION_RECORD)
    if stored is not None and msm_ops.apply_calibration(stored):
        return {**stored, "source": "store"}
    measured = msm_ops.calibrate_device_thresholds(force=True)
    program_store.save_calibration(
        measured, record=program_store.MSM_CALIBRATION_RECORD)
    return {"source": "measured", **measured}


def _msm_calibrate_into(report: dict) -> None:
    """One MSM calibration attempt recorded into the report (a failure
    is accounted, never fatal to the walk)."""
    try:
        report["msm_calibration"] = msm_calibration_step()
    except Exception as e:
        record_swallowed("prewarm.msm_calibration", e)
        report["msm_calibration"] = {"source": "failed",
                                     "error": f"{type(e).__name__}: {e}"}


# -- the prewarm walk ---------------------------------------------------------


def should_run() -> bool:
    """LHTPU_AOT_PREWARM gate: 1 always, 0 never, auto = TPU platform
    or an explicitly set LHTPU_AOT_STORE_DIR (so test clients with a
    defaulted datadir store never pay a background compile storm)."""
    mode = (envreg.get("LHTPU_AOT_PREWARM") or "auto").strip().lower()
    if mode in ("0", "false", "no", "off"):
        return False
    if mode in ("1", "true", "yes", "on"):
        return True
    if envreg.get("LHTPU_AOT_STORE_DIR"):
        return True
    import jax

    return jax.devices()[0].platform == "tpu"


def run(stop_event=None, force: bool = False) -> dict:
    """The full prewarm: load phase, calibration, drivers in priority
    order.  Returns a report the coldstart bench (and the builder log)
    reads; every outcome is also counted in
    ``aot_prewarm_entries_total{outcome}``."""
    report: dict = {"ran": False}
    if program_store.active() is None:
        report["skipped"] = "store not configured"
        return report
    if not force and not should_run():
        report["skipped"] = "LHTPU_AOT_PREWARM gate"
        # count from the manifest, not the runtime registry: the LH606
        # registrations only exist once the owner modules import, which
        # the gated-off path deliberately never does
        from lighthouse_tpu.common import device_telemetry as _dtel

        _record_outcome("skipped", len(_dtel.manifest_ids()))
        return report
    t0 = time.perf_counter()
    try:
        _import_owners()
    except Exception as e:
        # a device module that no longer imports (a jax upgrade moved an
        # API) is a fault of the program: every entry would serve from
        # plain jit, cold.  Report it loudly instead of dying silently
        # on the prewarm thread.
        record_swallowed("prewarm.import_owners", e)
        report["import_error"] = f"{type(e).__name__}: {e}"
        Logger("prewarm").error(
            "aot prewarm aborted: a device module failed to import",
            error=report["import_error"])
        return report
    scale = _resolve_scale()
    report.update({"ran": True, "scale": scale})

    by_driver: dict[str, list[str]] = {}
    for entry, driver in program_store.registered_entries().items():
        by_driver.setdefault(driver, []).append(entry)

    load_phase = {"loaded": 0, "failed": 0, "entries": {}}

    def load_group(entries=None, exclude=None):
        # the entry tag leads each store filename, so a group pass
        # reads ONLY its own files — each store byte is read exactly
        # once across the whole walk and the multi-hundred-MB store is
        # never memory-resident at once
        lp = program_store.load_store_programs(
            priority=entry_priority, stop=stop_event, entries=entries,
            exclude=exclude)
        load_phase["loaded"] += lp["loaded"]
        load_phase["failed"] += lp["failed"]
        for e, n in lp["entries"].items():
            load_phase["entries"][e] = load_phase["entries"].get(e, 0) + n

    outcomes: dict[str, str] = {}
    driver_s: dict[str, float] = {}
    calibrated = False
    for driver in DRIVER_ORDER:
        entries = sorted(by_driver.get(driver, ()))
        if not entries:
            continue
        if stop_event is not None and stop_event.is_set():
            for e in entries:
                outcomes[e] = "skipped"
            _record_outcome("skipped", len(entries))
            continue
        td = time.perf_counter()
        failed = None
        # each backend group's stored programs deserialize right before
        # its driver runs: the BLS verify lanes are hot (and the first
        # device verification completes) long before the last epoch
        # program loads — exactly the cold-start budget the warm run is
        # judged on
        load_group(set(entries))
        if driver == "msm" and "msm_calibration" not in report:
            # MSM calibration gates the lincomb/fold routing every
            # consumer (including the BLS driver's blinded merge) uses;
            # its 2-lane measurement dispatch reuses the programs the
            # load_group above just made resident
            _msm_calibrate_into(report)
        if driver == "sha256" and not calibrated:
            # calibration gates the sha routing the merkle driver (and
            # everything after it) uses
            calibrated = True
            _calibrate_into(report)
        try:
            _DRIVERS[driver](scale)
        except Exception as e:  # one broken driver must not sink the walk
            record_swallowed(f"prewarm.{driver}", e)
            failed = f"{type(e).__name__}: {e}"
        driver_s[driver] = round(time.perf_counter() - td, 3)
        stats = program_store.memo_stats()
        for entry in entries:
            sources = stats.get(entry, {})
            if failed is not None and not sources:
                outcomes[entry] = "failed"
            elif sources.get("store_hit"):
                outcomes[entry] = "loaded"
            elif sources.get("compiled"):
                outcomes[entry] = "compiled"
            else:
                outcomes[entry] = "missing"
            _record_outcome(outcomes[entry])
        if failed is not None:
            report.setdefault("driver_errors", {})[driver] = failed

    # a registration whose driver tag is not in DRIVER_ORDER (a typo'd
    # register_entry) must surface, not silently skip its whole group
    unknown = {d: sorted(es) for d, es in by_driver.items()
               if d not in DRIVER_ORDER}
    if unknown:
        record_swallowed(
            "prewarm.unknown_driver",
            RuntimeError(f"unknown prewarm driver tags: {unknown}"))
        report["unknown_drivers"] = unknown
        for es in unknown.values():
            for e in es:
                outcomes[e] = "missing"
            _record_outcome("missing", len(es))

    # anything left in the store (waived/unregistered/unknown-tagged
    # entries, shapes from earlier lives the drivers don't re-dispatch)
    # still loads — entries whose group pass already read their files
    # are excluded, so each store byte is read exactly once
    if stop_event is None or not stop_event.is_set():
        load_group(exclude={
            e for e, d in program_store.registered_entries().items()
            if d in DRIVER_ORDER})
    if "calibration" not in report:
        _calibrate_into(report)
    if "msm_calibration" not in report:
        _msm_calibrate_into(report)
    report["load_phase"] = load_phase

    report.update({
        "outcomes": outcomes,
        "driver_seconds": driver_s,
        "counts": {o: sum(1 for v in outcomes.values() if v == o)
                   for o in ("loaded", "compiled", "missing", "failed",
                             "skipped")},
        "seconds": round(time.perf_counter() - t0, 3),
    })
    _flight.emit("aot_prewarm_complete", **report["counts"],
                 seconds=report["seconds"], scale=scale)
    return report
