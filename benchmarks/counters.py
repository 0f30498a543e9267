"""Reading the program's own counters and spans, from outside.

``samples()`` goes through the program's Prometheus exposition, so a
family is read the way an operator's scrape reads it; ``SpanSink`` collects
finished spans from the program's tracer.
"""

from __future__ import annotations


def samples() -> dict:
    """{(sample name, frozenset(labels)): value} of every metric."""
    from lighthouse_tpu.common import promtext
    from lighthouse_tpu.common.metrics import REGISTRY

    return {(s.name, frozenset(s.labels)): s.value
            for fam in promtext.parse(REGISTRY.render()).values()
            for s in fam.samples}


def delta(before: dict, after: dict, name: str, where=None) -> float:
    """Sum over the label sets of ``name`` that ``where(labels)`` admits of
    the growth between two ``samples()`` readings."""
    total = 0.0
    for (sample, labels), value in after.items():
        if sample == name and (where is None or where(dict(labels))):
            total += value - before.get((sample, labels), 0.0)
    return total


class SpanSink:
    """Flat list of {"name", "attrs"} of every span finished while armed."""

    def __init__(self):
        self.spans = []

    def _walk(self, d):
        self.spans.append({"name": d.get("name"), "attrs": d.get("attrs", {})})
        for child in d.get("children", ()):
            self._walk(child)

    def _sink(self, root, _slot):
        self._walk(root.to_dict())

    def __enter__(self):
        from lighthouse_tpu.common import tracing

        tracing.TRACER.add_sink(self._sink)
        return self

    def __exit__(self, *exc):
        from lighthouse_tpu.common import tracing

        tracing.TRACER.remove_sink(self._sink)
