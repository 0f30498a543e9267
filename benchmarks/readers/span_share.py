"""Share (%) of the spans named ``span`` whose attribute ``attr`` equals
``equals``; nothing when no such span finished."""


def read(ctx, args):
    spans = [s for s in ctx["spans"] if s["name"] == args["span"]]
    if not spans:
        return None
    hit = sum(s["attrs"].get(args["attr"]) == args["equals"] for s in spans)
    return 100.0 * hit / len(spans)
