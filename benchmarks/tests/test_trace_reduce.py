"""The trace reduction against one small recorded trace: three dispatches
of a jitted 512x512 ``tanh(x @ x).sum()`` on a TPU v5e, each inside a
``bench.request`` annotation (recorded in PR 25 on the chip).  The wanted
numbers were read off the events by hand."""

import os

import pytest

from benchmarks import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "tiny_tpu_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce(TRACE)


def test_requests_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["requests"] == 3
    # the three annotations: 1,181,831 + 858,850 + 862,520 ns
    assert reduced["window_s"] == pytest.approx(2.903201e-3, rel=1e-6)


def test_module_sums(reduced):
    m = reduced["modules"]["tiny_named_kernel"]
    assert m["count"] == 3
    assert m["seconds"] == pytest.approx((1846 + 1847 + 1847) * 1e-9, rel=1e-6)
    assert trace_reduce.module_seconds(reduced, ["tiny_named_kernel"]) == (
        pytest.approx(5.54e-6, rel=1e-6), 3)
    assert trace_reduce.module_seconds(reduced, ["no_such_program"]) == (0.0, 0)


def test_busy_is_clipped_to_the_requests(reduced):
    # the device clock runs ~1.1 ms ahead of the host's in this trace: the
    # first dispatch's ops end before its annotation begins, the other two
    # fall inside the PREVIOUS request's annotation or a gap; what is
    # counted is only what lies inside a request
    ops = 3 * (13 + 3 + 1825) * 1e-9
    assert 0.0 <= reduced["busy_s"] <= ops * 1.01


def test_gaps_are_labelled(reduced):
    labels = {g[0] for g in reduced["gaps"]}
    assert labels <= {"inside a request", "between requests"}
    assert reduced["gaps"] == sorted(reduced["gaps"], key=lambda g: -g[1])
    total = sum(g[1] for g in reduced["gaps"])
    assert total > 0


def test_module_name():
    assert trace_reduce.module_name("jit__pipeline_fused(123456)") == \
        "_pipeline_fused"
    assert trace_reduce.module_name("jit_tiny(1)") == "tiny"
    assert trace_reduce.module_name("plain") == "plain"


def test_union_and_clip():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == [[0, 3], [5, 6]]
    assert trace_reduce._clip([[0, 3], [5, 6]], [[2, 5.5]]) == 1.5


def test_only_requests_the_device_buffer_covers_are_kept():
    """Five 100 ms requests; the device's events stop inside the fourth.
    The fifth shows no op, so the fourth may be cut and goes with it:
    three are kept and every number is of those."""
    ms = 1e6
    requests = [(i * 200 * ms, (i * 200 + 100) * ms) for i in range(5)]
    ops = [(r[0] + 10 * ms, r[0] + 60 * ms, "op") for r in requests[:3]]
    ops.append((requests[3][0] + 10 * ms, requests[3][0] + 20 * ms, "op"))
    modules = [("prog", lo, hi - lo) for lo, hi, _ in ops]
    got = trace_reduce.reduce_events(requests, [("/device:TPU:0", ops, modules)])
    assert got["requests_annotated"] == 5 and got["requests"] == 3
    assert got["window_s"] == pytest.approx(0.3)
    assert got["busy_s"] == pytest.approx(0.15)
    assert got["modules"]["prog"] == {"count": 3, "seconds": pytest.approx(0.15)}
    assert all(label == "inside a request" or seconds > 0
               for label, seconds in got["gaps"])


def test_a_trace_without_device_events_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events([(0, 1e6)], [])
