"""Least time for the fused BLS verify program's work, its live lanes
counted from the dispatches themselves: each ``bls.pipeline.dispatch``
span carries the chunk's live Miller lanes (attr ``lanes``: one a
message after the same-message merge, one a set in a batch that does not
merge, as a bisection's small halves), so the count follows every
`_pipeline_fused` dispatch the trace times.  Prices and bytes are
``rooflines/pipeline_fused.py``'s; a batch is one dispatch.
"""

from benchmarks.rooflines.pipeline_fused import work


def lanes(spans) -> int:
    """Live lanes summed over the ``bls.pipeline.dispatch`` spans."""
    return sum(s["attrs"].get("lanes", 0) for s in spans
               if s["name"] == "bls.pipeline.dispatch")


def least_seconds(ctx, peaks: dict, events: int) -> tuple:
    """(seconds, which bound binds) for the traced window's dispatches."""
    w = work(lanes(ctx["spans"]), events)
    by_ops = w["ops"] / peaks["int8_ops_per_s"]
    by_bytes = w["bytes"] / peaks["hbm_bytes_per_s"]
    return max(by_ops, by_bytes), "compute" if by_ops >= by_bytes else "memory"
