"""Device time (ms a request) of the programs named in ``modules``, summed
from the trace; nothing when none of them ran."""

from benchmarks import trace_reduce


def read(ctx, args):
    seconds, events = trace_reduce.module_seconds(ctx["trace"], args["modules"])
    if not events:
        return None
    return seconds / ctx["trace"]["requests"] * 1000.0
