"""Device idle share (%) inside the requests of the traced window."""


def read(ctx, args):
    t = ctx["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
