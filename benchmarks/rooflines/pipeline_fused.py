"""Least time for the fused BLS verify program's work, from lanes alone.

The count is of the arithmetic the ALGORITHM needs for one live lane (one
(aggregate key, message, signature) triple of a blinded batch check),
whatever implements it, in multiplications in Fp (381 bits), with
Fq2 mul = 3, Fq2 square = 2 (Karatsuba / complex squaring):

  Miller loop, |x| = 0xd201000000010000: 63 doubling steps, 5 addition
  steps, with the Fq12 accumulator SHARED by the batch (one squaring a
  step for all lanes, counted per batch below) and a sparse (014) product
  per lane per step.
    doubling step  tangent line 3S+4M (18) + 2 scalings by xp, yp (4)
                   + point doubling 2M+5S (16) + sparse product 13M (39) = 77
    addition step  chord 6M+1S (20) + 2 scalings by xp, yp (4)
                   + mixed addition 7M+4S (29) + sparse product (39)     = 92
    per lane       63 * 77 + 5 * 92                                     = 5311
  Blinding, 64-bit scalar r by 4-bit windows (64 doublings, 16 + 14
  additions):
    r * apk in G1  64 * 7 + 30 * 16                                      = 928
    r * sig in G2  64 * 16 + 30 * 43                                     = 2314
    sum of r * sig one G2 addition a lane                                = 43
  per lane                                                               = 8596
  per batch        63 Fq12 squarings (36 each) for the shared accumulator = 2268

One Fp multiplication is priced at its schoolbook cost in the MXU's int8
multiply-accumulates: 48 x 48 bytes for the product and twice that again
for the Montgomery reduction (m = t * p' mod R, then m * p): 3 * 2304 =
6912 multiply-accumulates = 13,824 int8 operations.

Bytes: operands in (key x, y; signature and message point in Fq2
coordinates: 10 field elements of 27 uint32 limbs; 16 scalar digits of 4
bytes; 1 mask byte) plus, per batch, one Fq12 out.
"""

FP_MUL_PER_LANE = 63 * 77 + 5 * 92 + 928 + 2314 + 43
FP_MUL_PER_BATCH = 63 * 36
INT8_OPS_PER_FP_MUL = 2 * 3 * 48 * 48
BYTES_PER_LANE = 10 * 27 * 4 + 16 * 4 + 1
BYTES_PER_BATCH = 12 * 27 * 4


def work(lanes: int, batches: int) -> dict:
    return {
        "ops": (lanes * FP_MUL_PER_LANE + batches * FP_MUL_PER_BATCH)
        * INT8_OPS_PER_FP_MUL,
        "bytes": lanes * BYTES_PER_LANE + batches * BYTES_PER_BATCH,
    }


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    """(seconds, which bound binds) for the traced window: every request's
    live lanes, in as many dispatches as the trace shows."""
    w = work(ctx["requests"] * ctx["units_per_request"], events)
    by_ops = w["ops"] / peaks["int8_ops_per_s"]
    by_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), "compute" if by_ops >= by_bytes else "memory"
