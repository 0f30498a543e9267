"""A program's share (%) of its roofline: the least time the chip could
take for the work (``rooflines/<roofline>.py``, peaks by device kind) over
the traced device time of the programs in ``modules``.  Nothing when the
program did not run: never 0."""

import importlib

from benchmarks import trace_reduce


def read(ctx, args):
    seconds, events = trace_reduce.module_seconds(ctx["trace"], args["modules"])
    if not events or seconds <= 0:
        return None
    roofline = importlib.import_module(f"benchmarks.rooflines.{args['roofline']}")
    least, binds = roofline.least_seconds(
        dict(ctx, requests=ctx["trace"]["requests"]), ctx["peaks"], events)
    ctx["log"](f"roofline {args['roofline']}: least {least:.3e} s "
               f"({binds}-bound) over traced {seconds:.3e} s, {events} events")
    return 100.0 * least / seconds
