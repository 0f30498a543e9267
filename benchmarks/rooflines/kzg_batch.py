"""Least time for a whole blob-batch request: the evaluations
(rooflines/kzg_eval.py) and the fused check (rooflines/kzg_fused.py)
together.  The membership test of the 2n decoded points is input
validation, not part of the verification equation, and is not counted."""

from benchmarks.rooflines import kzg_eval, kzg_fused


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    parts = (kzg_eval.request_work(ctx), kzg_fused.request_work(ctx))
    return kzg_eval.least_of(
        {key: sum(p[key] for p in parts) for key in ("ops", "bytes")}, peaks)
