"""Growth of ``<family>_sum`` over the window, summed over the label sets
that match ``labels`` (exact) and ``any_of`` (label -> admitted values),
per request, times ``scale``; nothing when the family did not move."""

from benchmarks import counters


def read(ctx, args):
    def where(labels):
        return (all(labels.get(k) == v for k, v in args.get("labels", {}).items())
                and all(labels.get(k) in vs
                        for k, vs in args.get("any_of", {}).items()))

    name = args["family"] + "_sum"
    moved = counters.delta(ctx["before"], ctx["after"],
                           args["family"] + "_count", where)
    if not moved or not ctx["requests"]:
        return None
    total = counters.delta(ctx["before"], ctx["after"], name, where)
    return total / ctx["requests"] * args.get("scale", 1.0)
