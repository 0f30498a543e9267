"""Share (%) of a counter family's growth that falls on the label values
``numerator`` (label -> admitted values); nothing when it did not move."""

from benchmarks import counters


def read(ctx, args):
    total = counters.delta(ctx["before"], ctx["after"], args["family"])
    if not total:
        return None
    part = counters.delta(
        ctx["before"], ctx["after"], args["family"],
        lambda labels: all(labels.get(k) in vs
                           for k, vs in args["numerator"].items()))
    return 100.0 * part / total
