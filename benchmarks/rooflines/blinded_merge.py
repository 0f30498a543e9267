"""Least time for the blinded same-message merges a traced window ran,
counted from the merges themselves: each ``bls.merge`` span (attrs
``sets`` and ``messages``) is one merge of that many single-key sets into
one blinded key sum and one blinded signature sum a message, a full batch
or one of its bisection halves alike, so the count follows every
`_blinded_merge` dispatch the trace times.

The count is of the arithmetic the ALGORITHM needs for a merge, whatever
implements it: never from the program's lanes, slices or group padding.
Prices as ``rooflines/pipeline_fused.py`` gives them, in multiplications
in Fp:

  per attestation   r * pk in G1 by 4-bit windows                   928
                    r * sig in G2 by 4-bit windows                 2314
  per addition      into a message's key sum (G1, Jacobian)          16
                    into a message's signature sum (G2)              43
  per merge         sets * (928 + 2314) + (sets - messages) * (16 + 43)

One Fp multiplication is 13,824 int8 operations.  Bytes: an attestation's
key (2 x 27 uint32 limbs), signature (4 x 27), 16 window digits and its
group in; a message's two Jacobian sums (3 x 27 and 6 x 27 limbs) out.
"""

from benchmarks.rooflines.kzg_eval import least_of
from benchmarks.rooflines.pipeline_fused import INT8_OPS_PER_FP_MUL

FP_MUL_PER_ATTESTATION = 928 + 2314
FP_MUL_PER_ADDITION = 16 + 43
BYTES_PER_ATTESTATION = (2 + 4) * 27 * 4 + 16 * 4 + 4
BYTES_PER_MESSAGE = (3 + 6) * 27 * 4


def work(attestations: int, messages: int) -> dict:
    return {
        "ops": (attestations * FP_MUL_PER_ATTESTATION
                + (attestations - messages) * FP_MUL_PER_ADDITION)
        * INT8_OPS_PER_FP_MUL,
        "bytes": attestations * BYTES_PER_ATTESTATION
        + messages * BYTES_PER_MESSAGE,
    }


def merges(spans) -> tuple:
    """(attestations, messages) summed over the ``bls.merge`` spans."""
    attrs = [s["attrs"] for s in spans if s["name"] == "bls.merge"]
    return (sum(a.get("sets", 0) for a in attrs),
            sum(a.get("messages", 0) for a in attrs))


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    """(seconds, which bound binds) for every merge the traced window
    ran, its requests' bisection halves among them."""
    return least_of(work(*merges(ctx["spans"])), peaks)
