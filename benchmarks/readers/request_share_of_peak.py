"""The whole request's share (%) of the chip's peak: the least time the
chip could take for the work the requests of the traced window need
(``rooflines/<roofline>.py``) over all of their time.  It bounds what a
kernel's own roofline can hide: a program taken off the path leaves its
roofline silent, this number stays."""

import importlib

from benchmarks import trace_reduce


def read(ctx, args):
    t = ctx["trace"]
    if not t["window_s"] or not t["requests"]:
        return None
    _, events = trace_reduce.module_seconds(t, args["modules"])
    roofline = importlib.import_module(f"benchmarks.rooflines.{args['roofline']}")
    least, _ = roofline.least_seconds(
        dict(ctx, requests=t["requests"]), ctx["peaks"], events or t["requests"])
    return 100.0 * least / t["window_s"]
