"""Least time for the fused check of a cell batch, from its live points
alone, at the prices of rooflines/kzg_fused.py.

The algorithm (the spec's universal verification equation) is ONE
two-pairing check a batch, however the program groups it: two multi-scalar
multiplications, over the D distinct commitments, the n proofs times
r^k h_k^S and the S monomial points, and over the n proofs times r^k; two
Miller lanes sharing one accumulator.

Bytes: as kzg_fused.py (a point's coordinates and its scalar in, two G2
points in, one Fq12 out).
"""

from benchmarks.rooflines import kzg_fused
from benchmarks.rooflines.kzg_cell_interp import cell_size
from benchmarks.rooflines.kzg_eval import least_of


def work(cells: int, commitments: int, size: int, batches: int) -> dict:
    points = batches * (commitments + size) + 2 * cells
    return {
        "ops": (points * kzg_fused.FP_MUL_PER_POINT
                + batches * (2 * kzg_fused.FP_MUL_PER_MILLER_LANE
                             + kzg_fused.FP_MUL_PER_BATCH))
        * kzg_fused.INT8_OPS_PER_FP_MUL,
        "bytes": (points * kzg_fused.BYTES_PER_POINT
                  + batches * kzg_fused.BYTES_PER_BATCH),
    }


def request_work(ctx) -> dict:
    """One batch a request: every live cell of the traced window, and the
    commitments of a request's blocks."""
    params = ctx["params"]
    return work(ctx["requests"] * ctx["units_per_request"],
                params["blocks"] * params["blobs_per_block"],
                cell_size(params), ctx["requests"])


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    return least_of(request_work(ctx), peaks)
