"""Least time for the key aggregation of a batch of signature sets, from
the workload's ``params`` alone.

The count is of the arithmetic the ALGORITHM needs to turn the member keys
of every set (affine G1 points, as a node's pubkey cache holds them) into
one aggregate key a set, whatever implements it: never from the program's
lanes, slices, segments or blinding points, so that it reads the same work
however the fold is cut.

  per set of k keys  k - 1 mixed additions (a Jacobian accumulator plus an
                     affine key: 7M + 4S = 11 multiplications in Fp)
  per request        (keys - sets) * 11

The one conversion back to affine coordinates a set (an inversion) and the
identity test are the implementation's choice of output form and are not
counted; nor are the blinding lanes, which exist because the device's
addition is incomplete.

One Fp multiplication is priced as ``rooflines/pipeline_fused.py`` prices
it: 13,824 int8 operations.  Bytes: a key's two coordinates in (2 x 27
uint32 limbs), a set's aggregate and its identity flag out.
"""

from benchmarks.rooflines.kzg_eval import least_of
from benchmarks.rooflines.pipeline_fused import INT8_OPS_PER_FP_MUL

FP_MUL_PER_MIXED_ADD = 7 + 4
BYTES_PER_POINT = 2 * 27 * 4


def work(keys: int, sets: int) -> dict:
    return {
        "ops": (keys - sets) * FP_MUL_PER_MIXED_ADD * INT8_OPS_PER_FP_MUL,
        "bytes": keys * BYTES_PER_POINT + sets * (BYTES_PER_POINT + 1),
    }


def request_shape(params: dict) -> tuple:
    """(keys, sets) of one request of a ``bls_sets`` workload."""
    keys = sum(s["count"] * s["keys"] for s in params["sets"])
    return keys, sum(s["count"] for s in params["sets"])


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    """(seconds, which bound binds) for the traced window's requests; the
    number of dispatches the trace shows changes nothing."""
    keys, sets = request_shape(ctx["params"])
    return least_of(work(ctx["requests"] * keys, ctx["requests"] * sets),
                    peaks)
