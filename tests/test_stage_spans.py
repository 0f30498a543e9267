"""Stage spans inside the BLS seam, the tree cache and the epoch step.

One small batch through ``verify_sets_pipeline`` and one ``state_advance``
across an epoch boundary at the minimal preset: every stage span sits under
the right parent, feeds its ``stage`` value, and the children of a request's
pipeline/slot span cover their parent.  The counters that sit where the
merkle work happens move by exactly the work done.  And every per-layer
metric file this PR added to the benchmark names a reader that imports and
reads a synthetic context.
"""

import os

import numpy as np
import pytest

from lighthouse_tpu.common import tracing
from lighthouse_tpu.common.metrics import REGISTRY
from lighthouse_tpu.ops import sha256 as sha_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Roots:
    """Sink: every finished root span, as a dict tree."""

    def __init__(self):
        self.roots = []

    def _sink(self, root, _slot):
        self.roots.append(root.to_dict())

    def __enter__(self):
        tracing.TRACER.add_sink(self._sink)
        return self

    def __exit__(self, *exc):
        tracing.TRACER.remove_sink(self._sink)

    def parents(self):
        """{span name: set of the names it appeared under (None = root)}."""
        out = {}

        def walk(d, parent):
            out.setdefault(d["name"], set()).add(parent)
            for child in d.get("children", ()):
                walk(child, d["name"])

        for root in self.roots:
            walk(root, None)
        return out

    def find(self, name):
        def walk(d):
            if d["name"] == name:
                yield d
            for child in d.get("children", ()):
                yield from walk(child)

        return [d for root in self.roots for d in walk(root)]


def _closure(span_dict):
    """Share of a span's duration that its direct children cover."""
    return (sum(c["duration_ms"] for c in span_dict.get("children", ()))
            / span_dict["duration_ms"])


def _stage_values(family):
    return {line.split('stage="')[1].split('"')[0]
            for line in REGISTRY.render().splitlines()
            if line.startswith(family + "_count{")}


# -- the BLS seam --------------------------------------------------------------

BLS_PARENTS = {
    "bls.subgroup": "bls.verify_pipeline",
    "bls.aggregate": "bls.verify_pipeline",
    "bls.prep_host": "bls.verify_pipeline",
    "bls.limbs": "bls.verify_pipeline",
    "bls.pipeline.dispatch": "bls.verify_pipeline",
    "bls.final_exp": "bls.verify_pipeline",
    "bls.aggregate.layout": "bls.aggregate",
    "bls.aggregate.dispatch": "bls.aggregate",
    "bls.aggregate.fetch": "bls.aggregate",
    "bls.subgroup.wait": "bls.final_exp",
    "bls.pipeline.wait": "bls.final_exp",
    "bls.final_exp.host": "bls.final_exp",
}
BLS_STAGES_KEPT = {"subgroup", "aggregate", "prep_host", "limbs", "pipeline",
                   "final_exp"}
BLS_STAGES_NEW = {"aggregate_layout", "aggregate_dispatch", "aggregate_fetch",
                  "subgroup_wait", "pipeline_wait", "final_exp_host"}


def _aggregation_sets():
    from lighthouse_tpu.crypto import bls

    sks = [bls.SecretKey.from_bytes(int(500 + i).to_bytes(32, "big"))
           for i in range(12)]
    pks = [sk.public_key() for sk in sks]
    msg = b"\x33" * 32
    sets = []
    for lo, hi in ((0, 8), (1, 12), (2, 9)):
        sig = bls.Signature.aggregate([sks[k].sign(msg) for k in range(lo, hi)])
        sets.append(bls.SignatureSet(bls.Signature(sig.to_bytes()),
                                     pks[lo:hi], msg))
    return sets


def test_bls_pipeline_stage_spans():
    """The shapes of test_device_pairing's aggregation batch (3 sets of
    8, 11 and 7 keys, one message): the fold and the fused program are in
    the compile cache of any tree that ran that test."""
    from lighthouse_tpu.ops.bls_backend import verify_sets_pipeline

    sets = _aggregation_sets()
    with _Roots() as sink:
        assert verify_sets_pipeline(sets)
    parents = sink.parents()
    assert parents["bls.verify_pipeline"] == {None}
    for name, parent in BLS_PARENTS.items():
        assert parents.get(name) == {parent}, name
    (pipeline,) = sink.find("bls.verify_pipeline")
    assert "profiled" not in pipeline["attrs"]
    (aggregate,) = sink.find("bls.aggregate")
    assert aggregate["attrs"]["slices"] == 1
    assert aggregate["attrs"]["lanes"] == 2 * 16 * 4   # seg x n_pad
    stages = _stage_values("bls_verify_stage_seconds")
    assert BLS_STAGES_KEPT <= stages and BLS_STAGES_NEW <= stages
    # closure: the stages are the request, and the parts are their stage
    assert _closure(pipeline) >= 0.90
    assert _closure(aggregate) >= 0.90
    assert _closure(sink.find("bls.final_exp")[0]) >= 0.90


@pytest.mark.parametrize("slot", [None, 4242])
def test_under_the_supervisor_a_request_is_one_tree(slot):
    """The watchdog's thread runs in a copy of the caller's context: every
    `bls.*` stage span's root is `bls.verify` (or the slotted caller's own
    span above it), none is filed beside it, and a slotted caller finds
    its signature stages in its slot's timeline."""
    from contextlib import nullcontext

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.testing import supervised_bls

    sets = _aggregation_sets()
    tracing.TRACER.clear()
    with supervised_bls(LHTPU_SUPERVISOR_LADDER="tpu,reference"), \
            _Roots() as sink:
        with (tracing.span("block_import", slot=slot) if slot is not None
              else nullcontext()):
            assert bls.verify_signature_sets(sets, backend="tpu")
    top = "bls.verify" if slot is None else "block_import"
    assert [r["name"] for r in sink.roots] == [top]
    parents = sink.parents()
    assert parents["bls.verify"] == {None if slot is None else top}
    assert parents["bls.verify_pipeline"] == {"bls.verify"}
    for name, parent in BLS_PARENTS.items():
        assert parents.get(name) == {parent}, name
    (verify,) = sink.find("bls.verify")
    assert verify["attrs"]["supervised"] is True
    # the hand-off is the caller span's self time: the pipeline is the rest
    assert _closure(verify) >= 0.90
    if slot is not None:
        (root,) = tracing.TRACER.timeline(slot)["spans"]
        names = set()

        def walk(d):
            names.add(d["name"])
            for child in d.get("children", ()):
                walk(child)

        walk(root)
        assert {"bls.verify", "bls.verify_pipeline", *BLS_PARENTS} <= names


def test_fold_counts_its_products_under_bls_aggregate():
    """`bls_fold_products_total` is registered and grows, once a slice
    dispatched under `bls.aggregate`, by `msm.blinded_fold_products` of
    the slice's shape (the fold of the test above: one slice of 4
    segments x 32 lanes, no new program)."""
    from benchmarks import counters
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.ops import msm
    from lighthouse_tpu.ops.bls_backend import aggregate_pubkeys_device

    def grown(before, after, multiply):
        return counters.delta(before, after, "bls_fold_products_total",
                              lambda labels: labels["multiply"] == multiply)

    pks = [bls.SecretKey.from_bytes(int(500 + i).to_bytes(32, "big"))
           .public_key() for i in range(12)]
    sig = bls.Signature(b"\xc0" + b"\x00" * 95)    # never read by the fold
    sets = [bls.SignatureSet(sig, pks[lo:hi], b"\x33" * 32)
            for lo, hi in ((0, 8), (1, 12), (2, 9))]
    before = counters.samples()
    with _Roots() as sink, tracing.span("bls.aggregate"):
        aggregate_pubkeys_device(sets)
    after = counters.samples()
    (aggregate,) = sink.find("bls.aggregate")
    assert len(sink.find("bls.aggregate.dispatch")) == 1
    assert aggregate["attrs"]["lanes"] == 128 and aggregate["attrs"]["slices"] == 1
    res, mat = msm.blinded_fold_products(128, 4)
    assert grown(before, after, "resident") == res == 16 * 124
    assert grown(before, after, "materialized") == mat == 783 * 4
    assert "# TYPE bls_fold_products_total counter" in REGISTRY.render()


def test_verify_sets_pipeline_has_no_ledger_mode():
    import inspect

    from lighthouse_tpu.ops import bls_backend

    for fn in (bls_backend.verify_sets_pipeline,
               bls_backend._verify_sets_pipeline):
        assert "ledger" not in inspect.signature(fn).parameters
    assert not os.path.exists(os.path.join(ROOT, "tools", "bls_ledger.py"))


# -- the state plane -----------------------------------------------------------

STATE_PARENTS = {
    "state.root": {"state.slot"},
    "epoch.transition": {"state.slot"},
    "tree.field": {"state.root"},
    "tree.leaves": {"tree.field", "tree.validators.element_roots"},
    "tree.diff": {"tree.field"},
    "tree.update": {"tree.field"},
    "tree.validators.diff": {"tree.field"},
    "tree.validators.slice": {"tree.field"},
    "tree.validators.element_roots": {"tree.field"},
    "tree.validators.snapshot": {"tree.field"},
    "tree.level.gather": {"tree.update"},
    "tree.level.scatter": {"tree.update"},
    # (a field with no cache of its own merkleizes whole, under tree.field;
    # the container's own few chunks fold under state.root)
    **{name: {"tree.update", "tree.validators.element_roots", "tree.field",
              "state.root"}
       for name in ("sha.pad", "sha.h2d", "sha.execute", "sha.d2h",
                    "sha.host")},
    "epoch.justification": {"epoch.transition"},
    "epoch.registry_updates": {"epoch.transition"},
    "epoch.effective_balance": {"epoch.transition"},
    "epoch.resets": {"epoch.transition"},
    "epoch.sync_committee": {"epoch.transition"},
}
RUNG_PARENTS = {
    "reference": {
        "epoch.inactivity": {"epoch.transition"},
        "epoch.rewards": {"epoch.transition"},
        "epoch.slashings": {"epoch.transition"},
    },
    "device": {
        "epoch.device_pass": {"epoch.transition"},
        "epoch.prep_host": {"epoch.device_pass"},
        "epoch.dispatch": {"epoch.device_pass"},
        "epoch.apply": {"epoch.device_pass"},
    },
}
MERKLE_STAGES = {"leaves", "diff", "slice", "snapshot", "gather", "scatter",
                 "pad", "h2d", "execute", "d2h", "hash_host"}
EPOCH_STAGES = {"justification", "registry_updates", "effective_balance",
                "resets", "sync_committee"}


def _fake_epoch_pass(columns, tables, params, apply_eb):
    """The fused epoch program's interface without its compile: scores and
    balances pass through, effective balances from their increments."""
    return (columns["scores"], columns["balances"],
            columns["eff_incr"].astype(np.int64) * 10**9)


@pytest.mark.parametrize("rung", ["reference", "device"])
def test_state_advance_stage_spans(monkeypatch, rung):
    from lighthouse_tpu.ops import epoch_kernels
    from lighthouse_tpu.state_transition import epoch_processing as ep
    from lighthouse_tpu.state_transition import state_advance
    from lighthouse_tpu.testing import Harness

    monkeypatch.setenv("LHTPU_EPOCH_BACKEND", rung)
    monkeypatch.setattr(epoch_kernels, "epoch_pass_device", _fake_epoch_pass)
    # levels of 16 pairs and more take the device path (a handful of small
    # shapes to compile), the ones under it the host's
    monkeypatch.setattr(sha_ops, "_DEVICE_MIN_PAIRS", 16)
    h = Harness(n_validators=64, fork="altair", real_crypto=False)
    spe = h.spec.preset.slots_per_epoch
    state_advance(h.state, h.spec, 2 * spe - 1)   # cache warm, epoch 1
    # what an epoch of blocks leaves behind: every record and balance dirty
    h.state.validators.effective_balance[:] -= np.uint64(10**9)
    h.state.balances[:] += np.uint64(12345)
    try:
        with _Roots() as sink:
            state_advance(h.state, h.spec, 2 * spe + 1)
    finally:
        ep.reset_epoch_supervisor()
    parents = sink.parents()
    assert parents["state.slot"] == {None}
    for name, under in {**STATE_PARENTS, **RUNG_PARENTS[rung]}.items():
        assert parents.get(name) and parents[name] <= under, (name, parents.get(name))
    other = "device" if rung == "reference" else "reference"
    assert not set(RUNG_PARENTS[other]) & set(parents)
    first, second = sink.find("state.slot")
    assert first["attrs"]["slot"] == 2 * spe - 1
    assert [c["name"] for c in first["children"]] == ["state.root",
                                                      "epoch.transition"]
    assert [c["name"] for c in second["children"]] == ["state.root"]
    (update,) = [u for u in sink.find("tree.update")
                 if u["attrs"]["dirty"] == 64]
    assert update["attrs"]["levels"] == 6
    assert {f["attrs"]["field"] for f in sink.find("tree.field")} >= {
        "validators", "balances", "slot"}
    assert MERKLE_STAGES <= _stage_values("merkle_stage_seconds")
    assert EPOCH_STAGES | {s.split(".")[1] for s in RUNG_PARENTS[rung]
                           if s != "epoch.device_pass"} <= _stage_values(
        "epoch_stage_seconds")
    assert "state_root_seconds_count" in REGISTRY.render()
    # closure: the two children are the slot
    assert _closure(first) >= 0.90 and _closure(second) >= 0.90


# -- counters where the merkle work happens ------------------------------------

def _counter(name, **labels):
    want = ",".join(f'{k}="{v}"' for k, v in labels.items())
    for line in REGISTRY.render().splitlines():
        if line.startswith(f"{name}{{{want}}}"):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


@pytest.mark.parametrize("min_pairs, path", [(1, "levels_device"),
                                             (1 << 30, "levels_host")])
def test_tree_update_counts_its_chunks_and_lanes(monkeypatch, min_pairs, path):
    from lighthouse_tpu.ssz.tree_cache import IncrementalTree

    rng = np.random.default_rng(5)
    leaves = rng.integers(0, 2**32, (64, 8), dtype=np.uint32)
    tree = IncrementalTree(leaves, 64)
    monkeypatch.setattr(sha_ops, "_DEVICE_MIN_PAIRS", min_pairs)
    new = leaves.copy()
    dirty = np.array([0, 1, 2, 9, 40, 41, 42, 43, 44, 63])
    new[dirty] ^= np.uint32(1)
    # pairs per level: the distinct parents of the dirty leaves, level by level
    pairs, idx = [], dirty
    for _ in range(6):
        idx = np.unique(idx >> 1)
        pairs.append(len(idx))
    assert pairs == [7, 5, 4, 3, 2, 1]
    padded = [1 << max(p - 1, 0).bit_length() for p in pairs]
    before = {p: _counter("sha256_merkle_chunks_total", path=p)
              for p in ("levels_device", "levels_host")}
    lanes = {k: _counter("sha256_device_lanes_total", kind=k)
             for k in ("live", "padding")}
    tree.update(new)
    other = "levels_host" if path == "levels_device" else "levels_device"
    assert (_counter("sha256_merkle_chunks_total", path=path) - before[path]
            == 2 * sum(pairs))
    assert _counter("sha256_merkle_chunks_total", path=other) == before[other]
    on_device = path == "levels_device"
    assert (_counter("sha256_device_lanes_total", kind="live") - lanes["live"]
            == (sum(pairs) if on_device else 0))
    assert (_counter("sha256_device_lanes_total", kind="padding")
            - lanes["padding"]
            == (sum(padded) - sum(pairs) if on_device else 0))
    # and the root is the plain one
    fresh = IncrementalTree(new, 64)
    assert tree.root() == fresh.root()


# -- the benchmark's new per-layer metrics and host_gaps.py's attribution ------
# (the benchmark's own tests, mirrored here so that tier-1 sees them)

from benchmarks.tests.test_stage_metrics import (  # noqa: E402,F401
    test_every_per_layer_metric_of_benchmark_json_resolves,
    test_idle_gaps_go_to_the_innermost_span_open,
    test_merkle_pad_waste_reads_a_synthetic_window,
    test_new_histogram_metric_reads_a_synthetic_window,
)


from benchmarks.tests.test_host_stall_metrics import (  # noqa: E402,F401
    test_a_still_family_reads_zero_not_nothing,
    test_counter_per_request_sums_scales_and_tells_absent_from_still,
    test_host_stall_metric_reads_a_synthetic_window,
    test_the_new_entries_of_benchmark_json,
)


@pytest.mark.parametrize("metric,family", [
    ("kzg_fused_resident_pct.blobs", "kzg_fused_products_total"),
    ("kzg_fused_resident_pct.columns", "kzg_fused_products_total"),
    ("bls_fold_resident_pct.electra", "bls_fold_products_total"),
    ("bls_fold_resident_pct.block", "bls_fold_products_total"),
    ("kzg_subgroup_resident_pct.columns", "g1_subgroup_products_total"),
    ("kzg_subgroup_resident_pct.blobs", "g1_subgroup_products_total"),
])
def test_resident_share_reads_a_synthetic_window(metric, family):
    """`kzg_fused_resident_pct.{blobs,columns}`,
    `bls_fold_resident_pct.{electra,block}` and
    `kzg_subgroup_resident_pct.{columns,blobs}`: the share of their
    family's growth on `multiply="resident"`; a program without the
    family (a parent commit) reads nothing and does not raise."""
    from benchmarks.tests.test_stage_metrics import _read

    res, mat = (frozenset({"multiply": k}.items())
                for k in ("resident", "materialized"))
    ctx = {"before": {(family, res): 100.0, (family, mat): 10.0},
           "after": {(family, res): 1090.0, (family, mat): 20.0},
           "requests": 3}
    assert _read(metric, ctx) == pytest.approx(99.0)
    assert _read(metric, {"before": {}, "after": {}, "requests": 3}) is None


def test_the_host_runtime_families_are_there_after_real_work():
    """After the verify and the state advance above (run again here at the
    same small sizes, so that the case stands alone): `span_offcpu_seconds`
    holds every BLS and tree stage the `host_offcpu_ms.*` files name that
    these sizes reach, and the collector's families hold all their label
    children."""
    import json

    from benchmarks import counters
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_transition import state_advance
    from lighthouse_tpu.testing import Harness, supervised_bls

    with supervised_bls(LHTPU_SUPERVISOR_LADDER="tpu,reference"):
        assert bls.verify_signature_sets(_aggregation_sets(), backend="tpu")
    h = Harness(n_validators=64, fork="altair", real_crypto=False)
    spe = h.spec.preset.slots_per_epoch
    state_advance(h.state, h.spec, 2 * spe - 1)
    h.state.validators.effective_balance[:] -= np.uint64(10**9)
    h.state.balances[:] += np.uint64(12345)
    state_advance(h.state, h.spec, 2 * spe + 1)
    samples = counters.samples()
    seen = {dict(labels)["span"] for (name, labels) in samples
            if name == "span_offcpu_seconds_count"}

    def named(suffix):
        with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                               f"host_offcpu_ms.{suffix}.json")) as f:
            return set(json.load(f)["args"]["any_of"]["span"])

    # the second step needs a set wider than a segment, which these are not
    assert named("block") - {"bls.aggregate.combine"} <= seen
    assert named("block") == named("electra")
    tree = {s for s in named("epoch") if s.startswith(("tree.", "sha."))}
    assert tree <= seen, tree - seen
    assert {"epoch.registry_updates"} <= seen
    for family in ("host_gc_pause_seconds_total",
                   "host_gc_collections_total"):
        for generation in "012":
            assert (family, frozenset({"generation": generation}.items())) \
                in samples
