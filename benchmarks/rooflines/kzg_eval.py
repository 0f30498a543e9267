"""Least time for the barycentric evaluations of a blob batch, from the
live blobs alone.

The count is of the arithmetic the ALGORITHM needs to evaluate one blob
polynomial in evaluation form at its challenge z, whatever implements it
and however it is sliced or padded, in multiplications in Fr (255 bits):

  p(z) = (z^W - 1) / W * sum_i f_i * w_i / (z - w_i)

  per field element  the W denominators inverted together (Montgomery's
                     trick or a product tree: 3 products an element),
                     f_i * w_i and the product with the inverse         = 5
  per blob           the one inversion left, z^(r-2) by square and
                     multiply: 254 squarings + (popcount(r-2) - 1)
                     products; z^W by log2(W) squarings; the factor
                     (z^W - 1) / W and the last product                 = 2

Converting to and from Montgomery form, the limb layout and the lanes a
slice pads with are the implementation's, not the algorithm's, and are not
counted.

One Fr multiplication is priced as rooflines/pipeline_fused.py prices an
Fp one, at its schoolbook cost in the MXU's int8 multiply-accumulates:
32 x 32 bytes for the product and twice that again for the Montgomery
reduction: 3 * 1024 = 3072 multiply-accumulates = 6,144 int8 operations.

Bytes: the blob's field elements in (32 bytes each), z in and y out.
"""

FR_MODULUS = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
FR_MUL_PER_ELEMENT = 5
FR_MUL_INVERSION = 254 + bin(FR_MODULUS - 2).count("1") - 1
INT8_OPS_PER_FR_MUL = 2 * 3 * 32 * 32
BYTES_PER_ELEMENT = 32


def work(blobs: int, width: int) -> dict:
    per_blob = (width * FR_MUL_PER_ELEMENT + FR_MUL_INVERSION
                + (width.bit_length() - 1) + 2)
    return {
        "ops": blobs * per_blob * INT8_OPS_PER_FR_MUL,
        "bytes": blobs * (width + 2) * BYTES_PER_ELEMENT,
    }


def request_work(ctx) -> dict:
    """Every live blob of the traced window's requests."""
    return work(ctx["requests"] * ctx["units_per_request"],
                ctx["params"]["field_elements_per_blob"])


def least_of(w: dict, peaks: dict) -> tuple:
    by_ops = w["ops"] / peaks["int8_ops_per_s"]
    by_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), "compute" if by_ops >= by_bytes else "memory"


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    """(seconds, which bound binds); the number of dispatches the trace
    shows changes nothing: the work is the live blobs'."""
    return least_of(request_work(ctx), peaks)
