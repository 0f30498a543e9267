"""Device kernels for the pubkey registry plane (chain/pubkey_plane).

One fused program: gather validator pubkey rows out of the
device-RESIDENT registry table, scalar-multiply each gathered lane by
its 64-bit blinder, and segment-sum per (slot, committee index,
beacon_block_root) group — the committee-aggregate-pubkey step of the
attestation firehose as one dispatch instead of per-set host point
adds ("Performance of EdDSA and BLS Signatures in Committee-Based
Consensus", PAPERS.md: the host adds were the per-set cost the batch
cannot amortize).

Soundness of the Jacobian tree under duplicate validators: every lane
is r_i·P_i with an independent random 64-bit r_i, so an exact-collision
(H == 0) chord between tree nodes needs a relation over the r_i
(~2^-64) — the same honest-random-blinding contract as
ec.gj_scalar_mul_windowed.  Zero-scalar padding lanes enter as exact
infinity (group identity).  An identity GROUP output (cancelling keys)
is reported in the bool row, never silently returned as garbage.

Shape discipline (lhlint LH301/302): ONE jitted program keyed by
(table rows, lane count, group count) — the plane pads lanes and
groups to powers of two so batch composition cannot churn compiles.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from lighthouse_tpu.ops import bigint as bi
from lighthouse_tpu.ops import ec
from lighthouse_tpu.ops import msm as _msm

# the fused gather+fold program itself lives on the unified MSM plane
# (ops/msm._gather_fold, "msm" prewarm driver); this module keeps the
# registry-table residency and the host lane layout


def _next_pow2(x: int, floor: int = 1) -> int:
    return _msm.bucket(x, floor=floor)


def mont_rows(points) -> tuple:
    """Decompressed affine G1 points -> HOST Montgomery limb rows
    (x, y) uint32[n, L] — the per-row half of build_table, split out so
    the pubkey plane can convert only newly appended registry rows and
    cache the rest instead of re-running the bigint conversion over the
    full table on every refresh."""
    xs = [p[0] for p in points]
    ys = [p[1] for p in points]
    return ec.ints_to_mont_limbs(xs), ec.ints_to_mont_limbs(ys)


def table_from_rows(rows_x: np.ndarray, rows_y: np.ndarray) -> tuple:
    """Host limb rows -> device-resident (tx, ty) with the row count
    padded to a power of two (the padding rows replicate row 0: never
    referenced — lane_idx only names real rows — but keep the gather
    in-bounds)."""
    n = len(rows_x)
    if n == 0:
        rows_x, rows_y = mont_rows([(1, 2)])
        n = 1
    t_pad = _next_pow2(n)
    if t_pad > n:
        rows_x = np.concatenate(
            [rows_x, np.repeat(rows_x[:1], t_pad - n, 0)])
        rows_y = np.concatenate(
            [rows_y, np.repeat(rows_y[:1], t_pad - n, 0)])
    return jnp.asarray(rows_x), jnp.asarray(rows_y)


def build_table(points) -> tuple:
    """Decompressed affine G1 points -> device-resident Montgomery limb
    table (tx, ty) uint32[T, L] with T padded to a power of two (the
    one-shot convenience over mont_rows + table_from_rows)."""
    rx, ry = mont_rows(points)
    return table_from_rows(rx, ry)


def gather_fold(table, row_of_lane: np.ndarray, scalars: np.ndarray,
                group_of_lane: np.ndarray, n_groups: int, shardings=None):
    """Σ r_i·pk[row_i] per group -> (x_limbs[G, L], y_limbs[G, L],
    inf bool[G]) — affine Montgomery rows for the merged-set pubkeys.

    Lanes are laid out s-major over padded (segment, group) geometry so
    the jit shape is a pure function of (lanes_pow2, groups_pow2).
    ``shardings=(lane_sh, table_sh)`` places lanes over a mesh and
    replicates the table (the parallel/msm_sharded rung)."""
    n = len(row_of_lane)
    if n == 0 or n_groups == 0:
        L = bi.L
        return (np.zeros((0, L), np.uint32), np.zeros((0, L), np.uint32),
                np.zeros(0, bool))
    counts = np.bincount(group_of_lane, minlength=n_groups)
    seg = _next_pow2(int(counts.max()))
    g_pad = _next_pow2(n_groups, floor=2)
    lane_idx = np.zeros(seg * g_pad, np.int32)
    lane_scalars = np.zeros(seg * g_pad, np.uint64)
    # s_i per lane = rank within its group in arrival order, computed
    # as a group-wise cumcount (stable argsort + offset subtraction) —
    # no per-lane Python in the hot fold path
    order = np.argsort(group_of_lane, kind="stable")
    offsets = np.zeros(n_groups, np.int64)
    np.cumsum(counts[:-1], out=offsets[1:])
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n, dtype=np.int64) - np.repeat(
        offsets, counts)
    lanes = rank * g_pad + group_of_lane
    lane_idx[lanes] = row_of_lane
    lane_scalars[lanes] = scalars
    digits = ec.scalars_to_digits(lane_scalars)
    tx, ty = table
    lane_idx_j = jnp.asarray(lane_idx)
    digits_j = jnp.asarray(digits)
    if shardings is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        lane_sh, tbl_sh = shardings
        mesh = lane_sh.mesh
        lane_idx_j = jax.device_put(lane_idx_j, lane_sh)
        digits_j = jax.device_put(
            digits_j, NamedSharding(mesh, P(None, *lane_sh.spec)))
        tx = jax.device_put(tx, tbl_sh)
        ty = jax.device_put(ty, tbl_sh)
    xa, ya, inf = jax.device_get(_msm.gather_fold_device(
        tx, ty, lane_idx_j, digits_j, g_pad))
    return np.asarray(xa)[:n_groups], np.asarray(ya)[:n_groups], \
        np.asarray(inf)[:n_groups]


__all__ = ["build_table", "gather_fold"]
