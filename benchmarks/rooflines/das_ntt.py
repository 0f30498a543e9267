"""Least time for the recovery of a request's blobs, from the blobs alone.

The count is of the arithmetic the ALGORITHM needs (consensus-specs
polynomial-commitments-sampling.md recover_polynomialcoeff, then the
extension that gives the cells), whatever implements it, in
multiplications in Fr, with N = 2n extended evaluations a blob:

  a radix-2 transform of size N        N/2 * log2(N)                = T
  per blob   E·Z, the inverse transform, the coset shift, the coset
             transform, the division by Z there, the inverse coset
             transform and its unshift, the transform of the
             coefficients (the cells)                         4 T + 4 N
  once a request (the blobs miss the same cells, so Z is shared): Z's
             transform, its coset shift and coset transform, and the
             inverses of Z on the coset (three products an element
             by Montgomery's trick)                           2 T + 4 N

Montgomery form, the limb layout and how Z is really made are not
counted.  One Fr multiplication = 6,144 int8 operations
(rooflines/kzg_eval.py's price).  Bytes: the received half of a blob's
extended evaluations in, the other half (the recovered cells) and its n
coefficients out, 32 bytes each.
"""

from benchmarks.rooflines.kzg_eval import (
    BYTES_PER_ELEMENT,
    INT8_OPS_PER_FR_MUL,
    least_of,
)


def transform(size: int) -> int:
    return size // 2 * (size.bit_length() - 1)


def work(blobs: int, width: int) -> dict:
    """One request: ``blobs`` blobs of ``width`` missing the same cells."""
    N = 2 * width
    per_blob = 4 * transform(N) + 4 * N
    shared = 2 * transform(N) + 4 * N
    return {"ops": (blobs * per_blob + shared) * INT8_OPS_PER_FR_MUL,
            "bytes": blobs * (N + width) * BYTES_PER_ELEMENT}


def request_work(ctx) -> dict:
    params = ctx["params"]
    per_request = work(params["blobs_per_block"],
                       params["field_elements_per_blob"])
    return {k: ctx["requests"] * v for k, v in per_request.items()}


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    return least_of(request_work(ctx), peaks)
