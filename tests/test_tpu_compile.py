"""Compile-only guard: the main-path device programs at chip_smoke.py's
shapes, lowered and compiled for a DESCRIBED TPU v5e (no chip attached).

What the TPU compiler refuses here costs no chip time (guide
on-chip-measurement section 2.3).  Nothing runs, so these tests say
nothing about results or speed; chip_smoke.py is the run.

Rules this file keeps: the topology is described inside a module-scoped
fixture (never at import, in a skipif, in parametrize or in conftest),
everything compiles in the test's own process, the persistent compile
cache is off around the compiles (a described-device executable cannot
be read back without a chip), and all such tests live in this one file
so one xdist worker owns libtpu.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

LANES = 256            # the bucket a 131-set mainnet block pads to
LEAVES = 1 << 20       # merkle leaves / epoch registry bucket
BLOCK_SEG = 2 * 512    # blinded fold: 512 key lanes + 512 blinding lanes
TABLE_ROWS = 1 << 14   # the smoke's 16,384 interop validators


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices), axis_names=("data",))


@pytest.fixture(scope="module")
def tpu_branches():
    """Steer the code to the branches a TPU node takes, and keep the
    described-device executables out of the persistent cache.

    ``bigint._use_mxu_redc()`` and ``bigint._use_resident_kernel()`` probe
    ``jax.default_backend()``, which is the CPU here, so the two
    switches are set in the test; the tracing caches
    are dropped on both sides so no program traced for the other branch
    is reused."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from lighthouse_tpu.ops import bigint as bi

    was_cache = jax.config.jax_enable_compilation_cache
    was_mxu, was_resident = bi._MXU_REDC, bi._RESIDENT
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    bi._MXU_REDC = bi._RESIDENT = True
    jax.clear_caches()
    yield
    bi._MXU_REDC, bi._RESIDENT = was_mxu, was_resident
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was_cache)
    cc.reset_cache()


def _compile(name, fn, *args, **kwargs):
    """Lower + compile ``fn`` (an instrumented entry's ``._fn`` or a
    plain jit) and print one JSON line of what the compiler reported."""
    t0 = time.perf_counter()
    lowered = fn.lower(*args, **kwargs)
    t1 = time.perf_counter()
    compiled = lowered.compile()
    t2 = time.perf_counter()
    ma = compiled.memory_analysis()
    print("TPU_COMPILE " + json.dumps({
        "program": name, "trace_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "code_bytes": int(ma.generated_code_size_in_bytes),
        "arg_bytes": int(ma.argument_size_in_bytes),
        "out_bytes": int(ma.output_size_in_bytes)}), flush=True)
    # one 16 GB chip: a program whose temporaries alone do not fit is a
    # refusal the compiler does not always raise by itself
    assert ma.temp_size_in_bytes + ma.argument_size_in_bytes < 14 << 30
    return compiled


def _limbs(sh, n=LANES):
    from lighthouse_tpu.ops import bigint as bi

    return jax.ShapeDtypeStruct((n, bi.L), jnp.uint32, sharding=sh)


def _fq12(sh):
    fq2 = (_limbs(sh, 1), _limbs(sh, 1))
    fq6 = (fq2, fq2, fq2)
    return (fq6, fq6)


def test_mont_mul_with_mxu_redc(one_chip, tpu_branches):
    from lighthouse_tpu.ops import bigint as bi

    assert bi._use_mxu_redc()
    c = _compile("bigint.mont_mul[mxu]", jax.jit(bi.mont_mul),
                 _limbs(one_chip), _limbs(one_chip))
    # the MXU branch is a matrix product; the other branch has none
    assert "convolution" in c.as_text() or "dot" in c.as_text()


def _assert_resident(compiled, temp_bytes: int):
    """The program's products run on the multiply whose partial products
    stay in the core: a Pallas kernel is there, and no schoolbook product
    is an array of the program ([.., 27, 54])."""
    assert compiled.memory_analysis().temp_size_in_bytes < temp_bytes
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ",27,54]" not in text[text.index("\nENTRY "):]


def test_g2_subgroup_kernel(one_chip, tpu_branches):
    """A block's membership dispatch: the [|x|]Q scan, ψ, the residues
    and their zero tests on the resident multiply, 2.6 MB of temporaries
    where the materialized scan made every product a [.., 256, 27, 54]
    array of the program."""
    from lighthouse_tpu.ops import bls_backend as bb

    _assert_resident(_compile("_g2_subgroup_kernel@256",
                              bb._g2_subgroup_kernel._fn,
                              *[_limbs(one_chip)] * 4), 8 << 20)


def test_g1_subgroup_kernel(one_chip, tpu_branches):
    from lighthouse_tpu.ops import bls_backend as bb

    _compile("_g1_subgroup_kernel@256", bb._g1_subgroup_kernel._fn,
             *[_limbs(one_chip)] * 2)


def test_fp12_mul_q(one_chip, tpu_branches):
    from lighthouse_tpu.ops import dispatch_pipeline as dp

    _compile("_fp12_mul_q", dp._fq12_mul_pair._fn,
             _fq12(one_chip), _fq12(one_chip))


@pytest.mark.slow  # ~45 s: ten unrolled levels of Jacobian adds, 180 kernels
def test_blinded_fold_block_layout(one_chip, tpu_branches):
    """131 sets x 512 keys fold in slices of bls_backend._AGG_MAX_LANES
    lanes: 32 segments x (512 key + 512 blinding lanes) per dispatch, and
    so do an electra block's 8 aggregates of 32,768 keys, 64 segments
    each.  The segment sum runs on the multiply whose partial products
    stay in the core (PR 36): 7.9 MB of temporaries and 1.89 GB accessed
    a slice, where every product of the sum was a [.., 27, 54] array of
    the program (1.94 GB and 46.7 GB; 15.5 GB of temporaries for the
    whole block in ONE dispatch of 262,144 lanes, now 284 MB)."""
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls_backend as bb
    from lighthouse_tpu.ops import msm

    assert bi._use_resident_kernel()
    max_k, n_pad = bb._fold_shape([512] * 131)
    assert (max_k, n_pad) == bb._fold_shape([32768] * 8 + [512, 1, 1])
    assert 2 * max_k == BLOCK_SEG and n_pad * BLOCK_SEG == 1 << 15
    rows = _limbs(one_chip, BLOCK_SEG * n_pad)
    c = _compile("_blinded_fold@32x(512+512)", msm._blinded_fold._fn,
                 rows, rows, rows, _limbs(one_chip, 1), _limbs(one_chip, 1),
                 n_pad)
    read = c.cost_analysis()["bytes accessed"]
    print("TPU_COMPILE " + json.dumps(
        {"program": "_blinded_fold", "bytes_accessed": int(read)}),
        flush=True)
    assert read < 4e9
    assert c.memory_analysis().temp_size_in_bytes < 64 << 20
    text = c.as_text()
    assert "tpu_custom_call" in text
    # behind the sum only the 32 rows of the segments carry a
    # schoolbook product as an array
    entry = text[text.index("\nENTRY "):]
    assert "[32768,27,5" not in entry and "[16384,27,5" not in entry


def test_blinded_lanes_from_the_key_table(one_chip, tpu_branches):
    """The gather in front of the fold (PR 38): a slice's 16,384 key lanes
    from the resident key table at `block-8x32k`'s 2^18 keys (x and y, 54
    words a row padded to a tile's 128), and the blinding half: the
    table is read in place, not laid out again on every call."""
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls_backend as bb

    max_k, n_pad = bb._fold_shape([32768] * 8 + [512, 1, 1])
    keys = max_k * n_pad
    c = _compile(
        "_blinded_lanes@2^18x16384", bb._blinded_lanes._fn,
        jax.ShapeDtypeStruct((1 << 18, bb._KEY_ROW_WORDS), jnp.uint32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((keys,), jnp.int32, sharding=one_chip),
        *[_limbs(one_chip, keys)] * 3)
    ma = c.memory_analysis()
    assert ma.temp_size_in_bytes < 1 << 20
    assert ma.argument_size_in_bytes < 160 << 20
    assert ma.output_size_in_bytes < 16 << 20


@pytest.mark.slow  # ~1 min (46 s of it the trace), 63 MB of code; not on chip_smoke's path
def test_gather_fold_16_committees(one_chip, tpu_branches):
    """The pubkey plane's fold over a 16,384-row table at 16 groups x
    512 keys.  At the 131-set x 512-key layout (131,072 lanes) the TPU
    compiler refuses it outright: 47.6 GB of HBM wanted, 15.75 GB there
    (ROADMAP Queue 1 item 7 — the plane needs a lane cap of its own)."""
    from lighthouse_tpu.ops import msm

    groups, lanes = 16, 16 * 512
    _compile(
        "_gather_fold@16x512", msm._gather_fold._fn,
        _limbs(one_chip, TABLE_ROWS), _limbs(one_chip, TABLE_ROWS),
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((16, lanes), jnp.uint32, sharding=one_chip),
        groups)


# -- the blob plane at a full blob_sidecars_by_range response (768 blobs) ----

BLOB_WIDTH = 4096      # FIELD_ELEMENTS_PER_BLOB
BLOB_POINTS = 2048     # 2 x 768 commitments and proofs, padded
FUSED_LANES = 4096     # two MSMs of bucket(2 * 768 + 1) = 2,048 lanes


def _fr_rows(sh, *lead):
    from lighthouse_tpu.ops import fr

    return jax.ShapeDtypeStruct((*lead, fr.L), jnp.uint32, sharding=sh)


def test_kzg_eval_slice(one_chip, tpu_branches):
    """One evaluation slice: fr._EVAL_MAX_BLOBS blobs of 4,096 field
    elements through the to-Montgomery program and _eval_kernel, on the
    multiply whose partial products stay in the core (PR 32).  What one
    slice moves through HBM is held here by the compiler's own count.
    Before that multiply (the parent of PR 32: every product a
    [64, 4096, 18, 36] array of the program) the to-Montgomery program
    read 7.04 GB, _eval_kernel 50.9 GB with 2.74 GB of temporaries, 128
    blobs wanted 5.46 GB and the 768 of a full response were refused
    (22.79 GB of 15.75 GB), which is where the cap of 64 came from."""
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import fr

    assert bi._use_resident_kernel()
    n = fr._EVAL_MAX_BLOBS
    assert 768 % n == 0  # a full response is whole slices: no fill
    programs = {
        "_to_mont_kernel": _compile(
            f"_to_mont_kernel@{n}x4096", fr._to_mont_kernel._fn,
            _fr_rows(one_chip, n, BLOB_WIDTH)),
        "_eval_kernel": _compile(
            f"_eval_kernel@{n}x4096", fr._eval_kernel._fn,
            _fr_rows(one_chip, n, BLOB_WIDTH), _fr_rows(one_chip, n),
            _fr_rows(one_chip, BLOB_WIDTH), _fr_rows(one_chip))}
    limits = {"_to_mont_kernel": 0.5e9, "_eval_kernel": 6e9}
    for name, c in programs.items():
        read = c.cost_analysis()["bytes accessed"]
        print("TPU_COMPILE " + json.dumps(
            {"program": name, "bytes_accessed": int(read)}), flush=True)
        assert read < limits[name]
        assert c.memory_analysis().temp_size_in_bytes < 1 << 30
        text = c.as_text()
        assert "tpu_custom_call" in text
        # no schoolbook product is an array of the program
        entry = text[text.index("\nENTRY "):]
        assert "[64,4096,18,3" not in entry and ",18,36]" not in entry


def test_cell_interp_full_block(one_chip, tpu_branches):
    """The aggregated coset interpolation of one full block's 128 data
    column sidecars (21 blobs: rows 32, two groups of 64 columns, cells of
    64 field elements), the one shape `columns-21x128` dispatches, on the
    multiply whose partial products stay in the core."""
    from lighthouse_tpu.ops import fr

    rows, slots, groups, size = 32, 64, 2, 64
    c = _compile(
        "_cell_interp_kernel@32x64x2x64", fr._cell_interp_kernel._fn,
        _fr_rows(one_chip, rows, slots, groups, size),
        _fr_rows(one_chip, rows, slots, groups),
        _fr_rows(one_chip, size, size),
        _fr_rows(one_chip, slots, groups, size))
    read = c.cost_analysis()["bytes accessed"]
    print("TPU_COMPILE " + json.dumps(
        {"program": "_cell_interp_kernel", "bytes_accessed": int(read)}),
        flush=True)
    # 786,432 + 8,192 lane-products of 18 limbs: operands and results are
    # ~230 MB; no [.., 18, 36] schoolbook product is an array
    assert read < 1.5e9
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert ",18,36]" not in text[text.index("\nENTRY "):]


def test_g1_subgroup_kernel_blob_batch(one_chip, tpu_branches):
    """The membership dispatch of a 768-sidecar batch, 1,536 points, and
    of each group of a full block's column sidecars.  The [r-1]P scan, the
    residues and their zero tests run on the multiply whose partial
    products stay in the core: 1.0 MB of temporaries and 2.7 MB of code,
    where the scan on the materialized multiply made every product a
    [.., 2048, 27, 54] array of the program (55.8 MB and 24.1 MB)."""
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls_backend as bb

    assert bi._use_resident_kernel()
    _assert_resident(_compile("_g1_subgroup_kernel@2048",
                              bb._g1_subgroup_kernel._fn,
                              *[_limbs(one_chip, BLOB_POINTS)] * 2), 8 << 20)


def test_recover_kernel_full_block(one_chip, tpu_branches):
    """The recovery of one full block of 21 blobs at the preset's 8,192
    extended evaluations (recover-21x64's one shape): four radix-2
    transforms of 13 stages, one `lax.scan` body each, on the multiply
    whose partial products stay in the core (~25 s: 87 MB of temporaries,
    53 MB of code)."""
    from lighthouse_tpu.ops import fr

    N = 8192
    c = _compile("_recover_kernel@21x8192", fr._recover_kernel._fn,
                 _fr_rows(one_chip, 21, N), _fr_rows(one_chip, 128),
                 _fr_rows(one_chip, 128), _fr_rows(one_chip, N),
                 _fr_rows(one_chip, N), _fr_rows(one_chip, 13, N // 2),
                 _fr_rows(one_chip, 13, N // 2))
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert ",18,36]" not in text[text.index("\nENTRY "):]


@pytest.mark.slow  # ~25 s: the setup's 64 G1 transforms of 128
def test_fk20_tables(one_chip, tpu_branches):
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import msm

    cols = jax.ShapeDtypeStruct((bi.L, 128, 64), jnp.uint32,
                                sharding=one_chip)
    c = _compile("_fk20_tables@64x128", msm._fk20_tables._fn, cols, cols,
                 cols)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.slow  # ~1.5 min of trace and compile
def test_fk20_full_block(one_chip, tpu_branches):
    """Every cell proof of 21 blobs: the sixteen multiples of the 8,192
    setup points, 64 windows of 2,688 (cell, blob) sums over the 64
    columns and 42 G1 transforms of 128 in one program, all on the
    multiply whose partial products stay in the core: 300 MB of
    temporaries (the shared tables are 42 MB of it, where a table a lane
    took 892 MB of 3.3 GB), 124 MB of code."""
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import fr, msm

    table = jax.ShapeDtypeStruct((bi.L, 64, 128), jnp.uint32,
                                 sharding=one_chip)
    coeffs = jax.ShapeDtypeStruct((fr.L, 4096 * 21), jnp.uint32,
                                  sharding=one_chip)
    c = _compile("_fk20_kernel@21", msm._fk20_kernel._fn, coeffs, table,
                 table, table)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert ",27,54]" not in text[text.index("\nENTRY "):]


@pytest.mark.slow  # ~3 min: the trace of ~420 kernel bodies is half of it
def test_kzg_fused_768_blobs(one_chip, tpu_branches):
    """Both RLC MSMs and the two-lane Jacobian Miller loop in one dispatch
    at 768 blobs, the G1 fold on the multiply whose partial products stay
    in the core (PR 34): 62 MB of temporaries (2.16 GB while every product
    of the fold was a [.., 27, 54] array of the program), so
    _kzg_fused_check needs no lane cap."""
    from lighthouse_tpu.crypto import kzg

    c = _compile(
        "_kzg_fused@4096", kzg._kzg_fused_program()._fn,
        _limbs(one_chip, FUSED_LANES), _limbs(one_chip, FUSED_LANES),
        jax.ShapeDtypeStruct((64, FUSED_LANES), jnp.uint32,
                             sharding=one_chip),
        *[_limbs(one_chip, 2)] * 4)
    print("TPU_COMPILE " + json.dumps(
        {"program": "_kzg_fused", "bytes_accessed": int(
            c.cost_analysis()["bytes accessed"])}), flush=True)
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30
    text = c.as_text()
    assert "tpu_custom_call" in text
    # the fold's lanes carry no schoolbook product as an array
    assert "[4096,27,5" not in text[text.index("\nENTRY "):]


def test_hash_pairs_device(one_chip, tpu_branches):
    from lighthouse_tpu.ops import sha256

    _compile("hash_pairs_device@2^19", sha256.hash_pairs_device._fn,
             jax.ShapeDtypeStruct((LEAVES // 2, 16), jnp.uint32,
                                  sharding=one_chip))


def test_fold_levels_device(one_chip, tpu_branches):
    from lighthouse_tpu.ops import sha256

    _compile("_fold_levels_device@2^20", sha256._fold_levels_device._fn,
             jax.ShapeDtypeStruct((LEAVES, 8), jnp.uint32,
                                  sharding=one_chip))


def test_validator_roots_device(one_chip, tpu_branches):
    """The registry's element roots at the 2^20 bucket: a 644k-row update
    and the first full build both dispatch this shape."""
    from lighthouse_tpu.ops import sha256
    from lighthouse_tpu.types.registry import Validators

    views = [sha256._stage_column(col, LEAVES)
             for col in Validators(LEAVES).columns()]
    _compile("validator_roots_device@2^20",
             sha256.validator_roots_device._fn,
             *(jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
               for v in views))


def test_fold_to_root(one_chip, tpu_branches):
    """What merkleize_words(device=True) dispatches at 2^20 leaves."""
    from lighthouse_tpu.ops import sha256

    _compile("_fold_to_root_jit@2^20", sha256._fold_to_root_jit._fn,
             jax.ShapeDtypeStruct((LEAVES, 8), jnp.uint32,
                                  sharding=one_chip))


def test_fused_epoch_pass(one_chip, tpu_branches):
    """int64 lanes are emulated on a TPU: the pass must still compile,
    under the scoped x64 context it is dispatched in."""
    from lighthouse_tpu.ops import epoch_kernels as ek

    def col(dt):
        return jax.ShapeDtypeStruct((LEAVES,), dt, sharding=one_chip)

    with jax.enable_x64():
        i64 = jnp.int64
        table = jax.ShapeDtypeStruct((3, 33), i64, sharding=one_chip)
        _compile(
            "_fused_epoch_pass@2^20", ek._epoch_pass_jit()._fn,
            col(jnp.int32), col(i64), col(i64), col(jnp.uint8),
            col(jnp.bool_), col(i64), col(i64), col(i64),
            table, table,
            jax.ShapeDtypeStruct((33,), i64, sharding=one_chip),
            jax.ShapeDtypeStruct((ek.N_PARAMS,), i64, sharding=one_chip),
            apply_eb=True)


def test_fused_epoch_pass_over_four_chips(mesh4, tpu_branches):
    """parallel/epoch_sharded's placement: columns over the mesh, tables
    replicated — pure lane parallelism, so no collective may appear."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lighthouse_tpu.ops import epoch_kernels as ek

    def arr(shape, dt, spec):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh4, spec))

    with jax.enable_x64():
        i64 = jnp.int64
        col = lambda dt: arr((LEAVES,), dt, P("data"))  # noqa: E731
        table = arr((3, 33), i64, P())
        c = _compile(
            "_fused_epoch_pass@2^20/4chips", ek._epoch_pass_jit()._fn,
            col(jnp.int32), col(i64), col(i64), col(jnp.uint8),
            col(jnp.bool_), col(i64), col(i64), col(i64), table, table,
            arr((33,), i64, P()), arr((ek.N_PARAMS,), i64, P()),
            apply_eb=True)
    text = c.as_text()
    assert not any(op in text for op in (
        "all-gather", "all-reduce", "collective-permute", "all-to-all"))


def test_shuffle_rounds(one_chip, tpu_branches):
    from lighthouse_tpu.ops import epoch_kernels as ek

    rounds = 90
    _compile(
        "_shuffle_rounds@2^20", ek._shuffle_jit(rounds)._fn,
        jax.ShapeDtypeStruct((LEAVES,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((rounds,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((rounds, LEAVES // 8), jnp.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))


def _pipeline_args(sh, n):
    rows = [_limbs(sh, n)] * 10
    return (*rows,
            jax.ShapeDtypeStruct((16, n), jnp.uint32, sharding=sh),
            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=sh),
            _limbs(sh, 1), _limbs(sh, 1), 0)


@pytest.mark.slow  # ~8 min of XLA:TPU compile on 8 cores, one thread
def test_pipeline_fused_flat_256(one_chip, tpu_branches):
    """THE program: the whole batch-verify data plane, flat layout, at
    the 256-lane bucket of a 131-set mainnet block."""
    from lighthouse_tpu.ops import bls_backend as bb

    _compile("_pipeline_fused@256", bb._pipeline_fused._fn,
             *_pipeline_args(one_chip, LANES))


@pytest.mark.slow  # the node's 1-set proposer check: a second program
def test_pipeline_fused_flat_4(one_chip, tpu_branches):
    from lighthouse_tpu.ops import bls_backend as bb

    _compile("_pipeline_fused@4", bb._pipeline_fused._fn,
             *_pipeline_args(one_chip, 4))


@pytest.mark.slow  # ~5 min: an electra block's 11 sets, a third bucket
def test_pipeline_fused_flat_16(one_chip, tpu_branches):
    """`block-8x32k`'s fused program: 8 aggregates, the sync set and two
    single-key sets pad to 16 lanes (the 32,768 keys of an aggregate are
    the fold's: `test_blinded_fold_block_layout`'s 32 x 1,024 shape)."""
    from lighthouse_tpu.ops import bls_backend as bb

    _compile("_pipeline_fused@16", bb._pipeline_fused._fn,
             *_pipeline_args(one_chip, 16))
    _assert_resident(_compile("_g2_subgroup_kernel@16",
                              bb._g2_subgroup_kernel._fn,
                              *[_limbs(one_chip, 16)] * 4), 8 << 20)


@pytest.mark.slow  # ~5 min: the Miller loop once more, as a mesh program
def test_sharded_miller_reduce_over_four_chips(mesh4, tpu_branches):
    """parallel/bls_sharded at chip_smoke --chips 4's shape: 257 pairs
    pad to 128 lanes a device; one all-gather of the partial products."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.parallel import bls_sharded

    per_dev = 128
    n = per_dev * mesh4.devices.size
    cols = [jax.ShapeDtypeStruct(
        (n, bi.L), jnp.uint32,
        sharding=NamedSharding(mesh4, P("data", None)))] * 6
    mask = jax.ShapeDtypeStruct(
        (n,), jnp.bool_, sharding=NamedSharding(mesh4, P("data")))
    c = _compile("_sharded_miller_reduce@128x4chips",
                 bls_sharded._sharded_miller_reduce(mesh4, per_dev)._fn,
                 *cols, mask)
    assert "all-gather" in c.as_text()


@pytest.mark.slow  # ~3 min: the merge twice, the membership program once
def test_blinded_merge_flood_buckets(one_chip, tpu_branches):
    """A gossip flood's same-message merge at both lane buckets of
    ops/bls_backend.merge_shapes, 16 groups each whatever the count of
    messages, and the membership program at a beacon processor batch of
    2,048 signatures: the shapes flood-2048 names in its hints beside
    `_pipeline_fused` @4."""
    from lighthouse_tpu.ops import bls_backend as bb
    from lighthouse_tpu.ops import msm

    for lanes in bb._MERGE_BUCKETS:
        digits = jax.ShapeDtypeStruct((16, lanes), jnp.uint32,
                                      sharding=one_chip)
        group = jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip)
        _compile(f"_blinded_merge@{lanes}x{bb._MERGE_GROUPS}",
                 msm._blinded_merge._fn, *[_limbs(one_chip, lanes)] * 6,
                 digits, group, bb._MERGE_GROUPS)
    # 3.5 MB of temporaries where the materialized scan took 619 MB
    _assert_resident(_compile("_g2_subgroup_kernel@2048",
                              bb._g2_subgroup_kernel._fn,
                              *[_limbs(one_chip, 2048)] * 4), 16 << 20)
