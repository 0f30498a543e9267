"""``<family>_sum`` as it stands when the window closes (process start to
window close, not the window's growth: what a histogram gathered during
set-up), summed over the label sets that match ``labels`` (exact) and
``any_of`` (label -> admitted values), times ``scale``; nothing when the
program has no such family or it never observed."""


def read(ctx, args):
    def where(labels):
        return (all(labels.get(k) == v for k, v in args.get("labels", {}).items())
                and all(labels.get(k) in vs
                        for k, vs in args.get("any_of", {}).items()))

    def total(suffix):
        return sum(value for (sample, labels), value in ctx["after"].items()
                   if sample == args["family"] + suffix and where(dict(labels)))

    if not total("_count"):
        return None
    return total("_sum") * args.get("scale", 1.0)
