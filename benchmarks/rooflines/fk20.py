"""Least time for every cell proof of a request's blobs, from the blobs
alone.

The count is of the arithmetic the ALGORITHM needs (FK20: Feist and
Khovratovich, ePrint 2023/033), whatever implements it, in multiplications
in Fp (381 bits), at the prices of rooflines/kzg_fused.py.  With n the
width, l the cell size and m = 2n/l cells a blob:

  the fixed-base stage: m * l scalar multiplications by 255-bit scalars,
  each into its sum (kzg_fused's price of a point: 256 doublings of 7,
  14 additions for the window table and 64 for the windows, 16 each, and
  one addition into its sum)                          m l * 3,056
  two transforms of size m over G1: log2(m) stages of m/2 butterflies,
  each a scalar multiplication by a root of unity (3,040: the same
  without the sum) and two additions (16 each)        2 * log2(m) * m/2 * 3,072

For the preset: 8,192 scalar multiplications and 2 x 448 butterflies a
blob.  The Fr transforms of the coefficient columns (l transforms of size
m, 28,672 Fr products a blob) are below a thousandth of it and not
counted, nor is the setup's.  One Fp multiplication = 13,824 int8
operations.  Bytes: a blob's n coefficients in (32 bytes each), the setup
transforms once (m l points of 96 bytes), m proofs out (48 bytes each).
"""

from benchmarks.rooflines.kzg_fused import (
    FP_MUL_PER_POINT,
    INT8_OPS_PER_FP_MUL,
)

FP_MUL_PER_BUTTERFLY = (FP_MUL_PER_POINT - 16) + 2 * 16


def cell_size(width: int) -> int:
    ext = 2 * width
    return ext // min(128, ext)


def work(blobs: int, width: int) -> dict:
    ell = cell_size(width)
    m = 2 * width // ell
    per_blob = (m * ell * FP_MUL_PER_POINT
                + 2 * (m.bit_length() - 1) * (m // 2) * FP_MUL_PER_BUTTERFLY)
    return {"ops": blobs * per_blob * INT8_OPS_PER_FP_MUL,
            "bytes": blobs * (width * 32 + m * 48) + m * ell * 96}


def request_work(ctx) -> dict:
    params = ctx["params"]
    per_request = work(params["blobs_per_block"],
                       params["field_elements_per_blob"])
    return {k: ctx["requests"] * v for k, v in per_request.items()}


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    from benchmarks.rooflines.kzg_eval import least_of

    return least_of(request_work(ctx), peaks)
