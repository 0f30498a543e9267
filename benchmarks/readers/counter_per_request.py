"""Growth of a counter family over the window, summed over its label sets,
per request, times ``scale``: 0.0 when the family is there and did not
move, nothing only when the program has no such family (the program
creates the label children before anything happens, so a calm window
reads a number)."""

from benchmarks import counters


def read(ctx, args):
    family = args["family"]
    if not ctx["requests"] or not any(name == family
                                      for name, _ in ctx["after"]):
        return None
    grown = counters.delta(ctx["before"], ctx["after"], family)
    return grown / ctx["requests"] * args.get("scale", 1.0)
