"""Least time for the fused KZG check's work, from the live points alone.

The count is of the arithmetic the ALGORITHM needs to fold n blob proofs
into one two-pairing check, whatever implements it and however its lanes
are padded, in multiplications in Fp (381 bits), with the prices of
rooflines/pipeline_fused.py (Fq2 mul = 3, Fq2 square = 2):

  two multi-scalar multiplications over 255-bit scalars: 2n + 1 points
  (the commitments, the proofs times r^i z_i, the generator) and n points
  (the proofs times r^i).  A point by 4-bit windows: 256 doublings (7
  each), 14 additions for the window table and 64 for the windows (16
  each), and one addition into its sum:
    per point       256 * 7 + 78 * 16 + 16                              = 3056
  two Miller lanes, |x| = 0xd201000000010000, sharing one Fq12
  accumulator (63 doubling steps of 77, 5 addition steps of 92, as
  pipeline_fused counts them):
    per lane        63 * 77 + 5 * 92                                    = 5311
    per batch       63 Fq12 squarings (36 each)                         = 2268

One Fp multiplication = 13,824 int8 operations (pipeline_fused's price).

Bytes: a point's two coordinates (48 bytes each) and its scalar (32) in;
the two G2 points (4 x 48 each) in; one Fq12 (12 x 48) out.
"""

FP_MUL_PER_POINT = 256 * 7 + (14 + 64) * 16 + 16
FP_MUL_PER_MILLER_LANE = 63 * 77 + 5 * 92
FP_MUL_PER_BATCH = 63 * 36
INT8_OPS_PER_FP_MUL = 2 * 3 * 48 * 48
BYTES_PER_POINT = 2 * 48 + 32
BYTES_PER_BATCH = 2 * 4 * 48 + 12 * 48


def work(blobs: int, batches: int) -> dict:
    """``blobs`` live blobs verified in ``batches`` batches."""
    points = 3 * blobs + batches
    return {
        "ops": (points * FP_MUL_PER_POINT
                + batches * (2 * FP_MUL_PER_MILLER_LANE + FP_MUL_PER_BATCH))
        * INT8_OPS_PER_FP_MUL,
        "bytes": points * BYTES_PER_POINT + batches * BYTES_PER_BATCH,
    }


def request_work(ctx) -> dict:
    """One batch a request, every live blob of the traced window."""
    return work(ctx["requests"] * ctx["units_per_request"], ctx["requests"])


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    from benchmarks.rooflines.kzg_eval import least_of

    return least_of(request_work(ctx), peaks)
