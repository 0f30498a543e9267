"""Multi-chip dry-run worker: runs in a fresh ``JAX_PLATFORMS=cpu`` process.

Executed as ``python -m lighthouse_tpu.parallel.dryrun_worker N`` by
``__graft_entry__.dryrun_multichip``, which sets ``JAX_PLATFORMS=cpu`` and
the virtual-device flag in the child's environment before jax exists in
it.  The worker itself picks no platform: it runs on whatever devices
jax reports and fails if there are fewer than N.

The step jitted here is the sharded flagship data plane:

- SSZ/SHA-256 merkleization fold sharded over leaf lanes (the reference's
  tree_hash hot path, /root/reference/consensus/types/src/beacon_state.rs:2031):
  local subtree fold per device, all_gather of the 8 subroots, replicated
  top fold — one jit, bounded compile.
- BLS batch-verify lanes sharded over the mesh: per-device Miller loops,
  psum-style tiny combine of the per-device Fq12 partial products (the
  SURVEY §2.9 data-parallel-over-sets design).  On by default; set
  LHTPU_DRYRUN_BLS=0 to skip (the first cold-cache CPU compile of the
  sharded Miller program costs minutes; it lands in .jax_cache after).

Cross-checks run on the host numpy/hashlib path — no extra device
programs, so the compile count is fixed and small.
"""

from __future__ import annotations

import os
import sys
import time

from lighthouse_tpu.ops import program_store as _pstore

# AOT program-store coverage (lhlint LH606): the dryrun's sharded
# merkle fold is prewarmed by the "dryrun" driver in ops/prewarm
_pstore.register_entry("parallel/dryrun_worker.py::_merkle_dryrun@sharded",
                       driver="dryrun")


def _merkle_dryrun(n_devices: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lighthouse_tpu.ops import sha256 as sha_ops

    devices = np.array(jax.devices()[:n_devices])
    mesh = Mesh(devices, axis_names=("data",))

    log_local = 6  # 64 leaves per device — tiny shapes, one compile
    n_leaves = n_devices * (1 << log_local)
    leaves = np.arange(n_leaves * 8, dtype=np.uint32).reshape(n_leaves, 8)

    # pad gathered per-device subroots to a power of two so the top fold
    # works for any n_devices (padding lanes are zero words)
    top_n = 1 << max(n_devices - 1, 0).bit_length()

    def local(leaves_block):
        sub = sha_ops.fold_to_root_device(leaves_block)  # [1, 8] subroot
        roots = jax.lax.all_gather(sub[0], "data")  # [n_devices, 8]
        if top_n != n_devices:
            pad = jnp.zeros((top_n - n_devices, 8), jnp.uint32)
            roots = jnp.concatenate([roots, pad], axis=0)
        return sha_ops.fold_to_root_device(roots)  # replicated top fold

    sharded = shard_map(
        local, mesh=mesh, in_specs=(P("data", None),),
        out_specs=P(None, None), check_vma=False)

    arr = jax.device_put(leaves, NamedSharding(mesh, P("data", None)))
    # one-shot warmup compile by design — the whole point of the dryrun
    from lighthouse_tpu.common import device_telemetry as _dtel

    root = _dtel.instrument(
        "parallel/dryrun_worker.py::_merkle_dryrun@sharded",
        jax.jit(sharded))(arr)  # lhlint: allow(jit-in-function)
    root.block_until_ready()

    # host cross-check (hashlib path, zero extra compiles)
    lvl = leaves
    while lvl.shape[0] > top_n:
        lvl = sha_ops.hash_pairs_np(lvl.reshape(lvl.shape[0] // 2, 16))
    tops = np.zeros((top_n, 8), np.uint32)
    tops[: lvl.shape[0]] = lvl
    while tops.shape[0] > 1:
        tops = sha_ops.hash_pairs_np(tops.reshape(tops.shape[0] // 2, 16))
    if not np.array_equal(tops, np.asarray(root)):
        raise AssertionError("multichip merkle root != host root")
    print(f"dryrun merkle ok: {n_devices} devices, root "
          f"{bytes(np.asarray(root)[0].view(np.uint8))[:8].hex()}…")


def _bls_dryrun(n_devices: int) -> None:
    import jax
    import numpy as np

    from lighthouse_tpu.parallel.bls_sharded import verify_signature_sets_sharded
    from lighthouse_tpu.crypto import bls

    sks = [bls.SecretKey.from_bytes(bytes([0] * 31 + [i + 1]))
           for i in range(n_devices)]
    msg = b"m" * 32
    sets = [bls.SignatureSet(sk.sign(msg), [sk.public_key()], msg)
            for sk in sks]
    ok = verify_signature_sets_sharded(sets, n_devices=n_devices)
    if not ok:
        raise AssertionError("sharded BLS batch verify rejected valid sets")
    bad = list(sets)
    bad[0] = bls.SignatureSet(sks[1].sign(msg), [sks[0].public_key()], msg)
    if verify_signature_sets_sharded(bad, n_devices=n_devices):
        raise AssertionError("sharded BLS batch verify accepted invalid set")
    print(f"dryrun bls ok: {n_devices} devices")


def main() -> int:
    n_devices = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    t0 = time.perf_counter()
    import jax

    from lighthouse_tpu.common import compile_cache

    compile_cache.configure()

    n_have = len(jax.devices())
    if n_have < n_devices:
        raise RuntimeError(
            f"worker has {n_have} devices, need {n_devices}; env "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
            f"XLA_FLAGS={os.environ.get('XLA_FLAGS')!r}")
    plats = {d.platform for d in jax.devices()[:n_devices]}
    print(f"worker devices: {n_have} ({sorted(plats)}), "
          f"init {time.perf_counter() - t0:.1f}s", flush=True)

    _merkle_dryrun(n_devices)
    # sharded BLS is part of the standard dryrun (the first-ever compile
    # costs minutes on CPU but lands in the persistent .jax_cache; set
    # LHTPU_DRYRUN_BLS=0 to skip explicitly)
    if os.environ.get("LHTPU_DRYRUN_BLS", "1") != "0":
        _bls_dryrun(n_devices)
    print(f"dryrun total {time.perf_counter() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
