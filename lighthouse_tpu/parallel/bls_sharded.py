"""Multi-chip BLS batch verification: signature-set lanes over a device mesh.

The SURVEY §2.9 scaling design: batch signature verification is pure data
parallelism over sets — each device runs Miller loops for its slice of the
(pair) lanes and tree-reduces them to ONE local Fq12 partial product; the
only cross-chip traffic is the tiny all_gather of per-device partials
(12 Fp elements each), multiplied together replicated.  The single final
exponentiation runs on the host once per batch.

Mirrors the single-device path in ops/bls12_381.multi_pairing_device and
the blst batch semantics (/root/reference/crypto/bls/src/impls/blst.rs:37-119).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from lighthouse_tpu.common import device_telemetry as _dtel
from lighthouse_tpu.ops import bls12_381 as dev
from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the mesh Miller program is
# prewarmed by the "sharded" driver in ops/prewarm
_pstore.register_entry(
    "parallel/bls_sharded.py::_sharded_miller_reduce@shard_map",
    driver="sharded")
from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import faults


_SHARDED_JIT_CACHE: dict = {}


def _sharded_miller_reduce(mesh, per_dev: int):
    """Jitted shard_map program: lanes [n_dev*per_dev] -> one Fq12 pytree.

    Memoized per (mesh devices, per_dev) — the Miller program costs
    minutes of XLA compile; rebuilding the jit per call would recompile."""
    key = (tuple(d.id for d in mesh.devices.flat), per_dev)
    cached = _SHARDED_JIT_CACHE.get(key)
    if cached is not None:
        return cached
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.devices.size
    assert n_dev & (n_dev - 1) == 0, "mesh size must be a power of two"

    def local(xp, yp, xqa, xqb, yqa, yqb, mask):
        f = dev.batch_miller_loop(xp, yp, xqa, xqb, yqa, yqb)
        part = dev.reduce_product(f, mask)  # [1]-lane local partial
        parts = jax.tree_util.tree_map(
            lambda x: jax.lax.all_gather(x, "data", axis=0, tiled=True), part)
        # multiply the n_dev partials down to one lane, replicated
        return dev.reduce_product(
            parts, jnp.ones((n_dev,), bool)) if n_dev > 1 else parts

    spec = P("data", None)
    fn = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(spec,) * 6 + (P("data"),),
        out_specs=P(None, None),
        check_vma=False))
    fn = _dtel.instrument(
        "parallel/bls_sharded.py::_sharded_miller_reduce@shard_map", fn)
    _SHARDED_JIT_CACHE[key] = fn
    return fn


def _dispatch_chunk(pairs, mesh, stage):
    """Prep + h2d + dispatch for one lane chunk; returns the (not yet
    synced) replicated Fq12 partial.  ``stage`` accumulates prep_host/h2d
    wall seconds so chunked runs report per-stage totals."""
    import time

    from jax.sharding import NamedSharding, PartitionSpec as P

    n_dev = mesh.devices.size
    t0 = time.perf_counter()
    cols, mask = dev.points_to_device(pairs)
    n = len(pairs)
    # pad so every device holds a power-of-two lane count
    per_dev = 1 << max((n + n_dev - 1) // n_dev - 1, 0).bit_length()
    padded = per_dev * n_dev
    if padded != n:
        cols = [np.concatenate([c, np.tile(c[-1:], (padded - n, 1))])
                for c in cols]
        mask = np.concatenate([mask, np.zeros(padded - n, bool)])
    fn = _sharded_miller_reduce(mesh, per_dev)
    now = time.perf_counter()
    stage["prep_host"] += now - t0
    t0 = now
    sh = NamedSharding(mesh, P("data", None))
    shm = NamedSharding(mesh, P("data"))
    args = [jax.device_put(jnp.asarray(c), sh) for c in cols]
    mask_dev = jax.device_put(jnp.asarray(mask), shm)
    stage["h2d"] += time.perf_counter() - t0
    return fn(*args, mask_dev)


def multi_pairing_sharded(pairs, mesh, chunk_size: int | None = None
                          ) -> "object":
    """Device multi-pairing over a mesh: prod Miller(P_i, Q_i), host final exp.

    Lane sets above the pipeline chunk size (chunk_size arg >
    LHTPU_BLS_CHUNK > default) split into fixed power-of-two chunks
    dispatched back-to-back: the host preps and uploads chunk k+1 while
    chunk k's Miller program runs on the mesh, the per-chunk replicated
    partials multiply down on device, and the batch pays ONE d2h fetch +
    ONE final exponentiation — the single-device overlap model of
    ops/dispatch_pipeline applied across chips.

    Stage wall times land in ``bls_verify_stage_seconds{backend="sharded"}``
    (prep_host / h2d / kernel / d2h / final_exp).  The kernel stage syncs
    the (combined) sharded result before timing — one batch-level sync the
    d2h fetch right after would pay anyway, so the pipeline is not
    serialized."""
    import time

    from lighthouse_tpu.common import tracing
    from lighthouse_tpu.crypto.bls.api import record_stage
    from lighthouse_tpu.crypto.bls.fields import final_exponentiation_fast
    from lighthouse_tpu.ops import dispatch_pipeline as dp

    with tracing.span("bls.multi_pairing_sharded", lanes=len(pairs),
                      devices=int(mesh.devices.size)):
        chunks = dp.plan_chunks(len(pairs), dp.chunk_size(chunk_size))
        stage = {"prep_host": 0.0, "h2d": 0.0}
        partials = []
        overlap_s = 0.0
        t_prev = None
        for ci, (lo, hi) in enumerate(chunks):
            faults.fire("chunk", index=ci)
            tc = time.perf_counter()
            partials.append(_dispatch_chunk(pairs[lo:hi], mesh, stage))
            now = time.perf_counter()
            if t_prev is not None:
                overlap_s += now - tc
            t_prev = now
        record_stage("sharded", "prep_host", stage["prep_host"])
        record_stage("sharded", "h2d", stage["h2d"])
        dp.record_pipeline(len(chunks), overlap_s, len(pairs))
        t0 = time.perf_counter()
        f = dp.combine_partials(partials)
        jax.block_until_ready(f)
        now = time.perf_counter()
        record_stage("sharded", "kernel", now - t0)
        t0 = now
        f_host = dev.fq12_from_device(jax.device_get(f))
        now = time.perf_counter()
        record_stage("sharded", "d2h", now - t0)
        t0 = now
        out = final_exponentiation_fast(f_host)
        record_stage("sharded", "final_exp", time.perf_counter() - t0)
        return out


def verify_signature_sets_sharded(
    sets: Sequence, *, n_devices: int | None = None, mesh=None,
    chunk_size: int | None = None
) -> bool:
    """Batch-verify signature sets with Miller-loop lanes sharded over a mesh.

    Agrees with the single-device "tpu" backend by construction: same host
    prep (ops/bls_backend.prepare_pairs), same Miller formulas, only the
    lane placement differs.
    """
    from jax.sharding import Mesh
    from lighthouse_tpu.crypto.bls.api import record_batch
    from lighthouse_tpu.ops.bls_backend import prepare_pairs

    if not sets:
        return False
    # supervisor-visible dispatch boundary (see bls_backend's twin hook)
    if faults.fire("sharded") == "corrupt":
        return faults.corrupt_verdict()
    record_batch("sharded", len(sets))
    pairs = prepare_pairs(sets)
    if pairs is None:
        return False
    if mesh is None:
        devs = jax.devices()
        n = n_devices or len(devs)
        mesh = Mesh(np.array(devs[:n]), axis_names=("data",))
    return multi_pairing_sharded(pairs, mesh, chunk_size=chunk_size).is_one()
