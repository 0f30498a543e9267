"""The gossip flood cell: the configuration at full size, what decides
``correct`` shown to fail, and its rooflines counted by hand.

The control is the plain reference without its 64-bit blinding scalars: it
accepts the swapped pair.  The faults break the timed path underneath a
whole rehearsal-size run of ``run.main`` (the workload's
``rehearse_params``: 16 attestations a batch over two messages, served on
the host rung as on any CPU), or feed the production ``served_by`` rules
what a batch answered by another rung leaves behind.  The chip-size
control is ``control_at_size_flood.py``.
"""

import importlib
import inspect
import json
import os

import pytest

from benchmarks import counters, run
from benchmarks.rooflines import (
    blinded_merge, pipeline_fused, pipeline_fused_dispatched)

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (1, 2_147_483_659, 3_000_000_019)
NAME = "flood-2048"


def _files():
    workload = json.load(open(os.path.join(BENCH, "workloads", f"{NAME}.json")))
    config = json.load(open(os.path.join(
        BENCH, "configs", f"{workload['config']}.json")))
    return workload, config


def _cell(seed):
    workload, config = _files()
    params = {**workload["params"], **workload["rehearse_params"]}
    generator = importlib.import_module(
        f"benchmarks.traffic.{workload['generator']}")
    return generator.build(config, params, seed, lambda text: None)


def _wrong(compared):
    return {k for k, (value, limit) in compared.items() if value > limit}


def test_the_cell_is_the_configuration_at_full_size():
    from lighthouse_tpu.ops import bls_backend as bb
    from lighthouse_tpu.processor.beacon_processor import BeaconProcessor
    from lighthouse_tpu.types.spec import MAINNET_PRESET, ChainSpec

    workload, config = _files()
    params, preset = workload["params"], config["preset"]
    assert (MAINNET_PRESET.max_committees_per_slot,
            MAINNET_PRESET.slots_per_epoch,
            MAINNET_PRESET.target_committee_size) == (
        preset["MAX_COMMITTEES_PER_SLOT"], preset["SLOTS_PER_EPOCH"],
        preset["TARGET_COMMITTEE_SIZE"])
    assert ChainSpec.attestation_subnet_count == preset[
        "ATTESTATION_SUBNET_COUNT"] == 64
    # 2^20 validators: 32,768 attesters a slot in 64 committees of 512,
    # 16 full processor batches of them
    per_slot = config["active_validators"] // preset["SLOTS_PER_EPOCH"]
    assert per_slot == config["attesters_per_slot"] == 32768
    assert per_slot // preset["MAX_COMMITTEES_PER_SLOT"] == \
        config["committee_size"] == 512
    max_batch = inspect.signature(BeaconProcessor).parameters[
        "max_batch"].default
    assert sum(params["votes"]) == config["batch"] == max_batch == 2048
    assert per_slot // max_batch == config["batches_per_slot"] == 16
    assert len(params["votes"]) == config["messages_per_batch"] == 2
    assert params["votes"] == [31 * 64, 64]
    assert config["reduced"] == ["key_pool"] and config["architecture"] is None
    assert params["key_pool"] == config["key_pool"] == 262144
    assert (params["good"], params["bad"], params["check_requests"]) == (6, 2, 4)
    assert config["blinding_bits"] == 64
    # the hints name every shape a full batch and the halves bisection
    # hands the seam run on: the merge at both buckets, the fused program
    # at its smallest, the membership kernel at the batch
    hints = {}
    for h in params["precompile"]:
        hints.setdefault(h["entry"].split(":")[1], []).append(h["args"])
    shapes = {bb.merge_shapes(n, m) for n, m in (
        (2048, 2), (1024, 2), (256, 2), (2, 2), (2, 1), (1, 1))}
    merges = {(a[0]["zeros"][0], a[-1]) for a in hints["_blinded_merge"]}
    assert merges == {(m, g) for m, g, _ in shapes if m}
    assert [a[0]["zeros"] for a in hints["_pipeline_fused"]] == [
        [f, 27] for f in {f for _, _, f in shapes}]
    assert [a[0]["zeros"] for a in hints["_g2_subgroup_kernel"]] == [
        [bb._next_pow2(2048, floor=4), 27]]


def test_a_program_without_the_device_merge_fails_the_set_up():
    workload, config = _files()
    params = {**workload["params"], **workload["rehearse_params"],
              "precompile": [{"entry": "lighthouse_tpu.ops.msm:_no_such",
                              "args": []}]}
    generator = importlib.import_module(
        f"benchmarks.traffic.{workload['generator']}")
    with pytest.raises(SystemExit, match="has no"):
        generator.build(config, params, 3, lambda text: None)


@pytest.mark.parametrize("seed", SEEDS)
def test_reference_attributes_and_unblinded_control_accepts_the_swap(seed):
    cell = _cell(seed)
    entries = range(len(cell.batches))
    sound = [(e, tuple(cell.reference_verdicts(e))) for e in entries]
    assert [list(v) for _, v in sound] == cell.expect
    assert [len(v) - sum(v) for _, v in sound] == [0, 0, 2, 1]
    assert not _wrong(cell.check(sound))
    control = [(e, tuple(cell.reference_verdicts(e, blind=False)))
               for e in entries]
    # without the blinding the swapped pair cancels; the wrong message
    # is still caught
    assert [len(v) - sum(v) for _, v in control] == [0, 0, 0, 1]
    assert _wrong(cell.check(control)) == {"verdict_mismatches",
                                           "verdicts_off_construction"}


def _run(capsys, seed=7, seconds=1):
    rc = run.main(["--workload", NAME, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "0", "--rehearse"])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return rc, json.loads(line)


def test_a_sound_run_is_correct(capsys):
    rc, result = _run(capsys)
    assert rc == 0 and result["correct"] is True
    assert set(result["metrics"]) == {"block_verify_p50_ms", "setup_s"}


def _break_after_warm_up(monkeypatch, fault):
    import benchmarks.traffic.gossip_attestations as gen

    warm = gen.Cell.warm_up

    # the warm-up holds each pool entry to its construction and would stop
    # the run: the fault goes in after it
    def warm_then_break(self):
        warm(self)
        fault(self)

    monkeypatch.setattr(gen.Cell, "warm_up", warm_then_break)


def _one_verdict_flipped(monkeypatch):
    def fault(cell):
        serve = cell.serve

        def flipped(request):
            got = list(serve(request))
            got[3] = not got[3]
            return tuple(got)

        monkeypatch.setattr(cell, "serve", flipped)
    return fault


def _a_compile_in_the_window(monkeypatch):
    def fault(cell):
        import jax
        import jax.numpy as jnp

        from lighthouse_tpu.common import device_telemetry as dtel

        serve = cell.serve
        shapes = iter(range(1, 1 << 20))

        def compiling(request):
            fresh = dtel.instrument("benchmarks/tests::fault@fault",
                                    jax.jit(lambda x: x + 1))
            jax.block_until_ready(fresh(jnp.zeros(next(shapes))))
            return serve(request)

        monkeypatch.setattr(cell, "serve", compiling)
    return fault


@pytest.mark.parametrize("fault", [_one_verdict_flipped,
                                   _a_compile_in_the_window])
def test_faults_in_the_window_are_not_correct(capsys, monkeypatch, fault):
    _break_after_warm_up(monkeypatch, fault(monkeypatch))
    rc, result = _run(capsys, seconds=2)
    assert rc == 1 and result["correct"] is False


@pytest.mark.parametrize("off", ["span", "counter"])
def test_a_batch_or_merge_off_the_device_is_not_correct(monkeypatch, off):
    """The production rules: a batch the supervisor served on the
    reference rung, or a merge that ran in host Python, is counted."""
    from lighthouse_tpu.crypto.bls import api

    rules = _files()[0]["params"]["served_by"]
    before = counters.samples()
    api.count_merged("host" if off == "counter" else "device", 2048)
    spans = [{"name": "bls.verify",
              "attrs": {"served": "reference" if off == "span" else "tpu"}}]
    got = run.served_elsewhere(rules, spans, before, counters.samples(),
                               counters)
    assert {k for k, (value, limit) in got.items() if value > limit} == {
        "span": {"batches_off_tpu_rung"},
        "counter": {"merges_off_device"}}[off]


PEAKS = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def _span(name, **attrs):
    return {"name": name, "attrs": attrs}


def test_merge_roofline_counts_by_hand():
    # one attestation of one message: two scalar multiplications, nothing
    # to add; a key, a signature, 16 digits and a group in, two sums out
    assert blinded_merge.work(1, 1) == {"ops": (928 + 2314) * 13824,
                                        "bytes": 6 * 108 + 68 + 9 * 108}
    assert blinded_merge.INT8_OPS_PER_FP_MUL == \
        pipeline_fused.INT8_OPS_PER_FP_MUL
    assert (928, 2314) == (64 * 7 + 30 * 16, 64 * 16 + 30 * 43)
    # a window of two good batches (2,048 attestations over 2 messages)
    # and a bad one whose bisection merged its halves again: every merge
    # the trace times is counted, whatever its lanes and groups
    full = _span("bls.merge", sets=2048, messages=2, lanes=2048)
    halves = [_span("bls.merge", sets=1024, messages=2, lanes=2048),
              _span("bls.merge", sets=3, messages=1, lanes=256)]
    spans = [full, full, full] + halves + [_span("bls.limbs", chunk=0)]
    assert blinded_merge.merges(spans) == (3 * 2048 + 1027, 3 * 2 + 3)
    least, binds = blinded_merge.least_seconds({"spans": spans}, PEAKS, 5)
    assert binds == "compute"
    assert least == pytest.approx(
        (3 * (2048 * 3242 + 2046 * 59) + 1024 * 3242 + 1022 * 59
         + 3 * 3242 + 2 * 59) * 13824 / 393e12)
    # nothing merged, nothing counted
    assert blinded_merge.least_seconds({"spans": []}, PEAKS, 1)[0] == 0


def test_fused_roofline_counts_the_dispatched_lanes():
    # a merged batch is one lane a message; a bisection's unmerged
    # single set one lane; a batch that does not merge one lane a set
    spans = [_span("bls.pipeline.dispatch", chunk=0, lanes=2),
             _span("bls.pipeline.dispatch", chunk=0, lanes=1),
             _span("bls.pipeline.dispatch", chunk=0, lanes=11),
             _span("bls.merge", sets=2048, messages=2, lanes=2048)]
    assert pipeline_fused_dispatched.lanes(spans) == 14
    least, binds = pipeline_fused_dispatched.least_seconds(
        {"spans": spans}, PEAKS, 3)
    w = pipeline_fused.work(14, 3)
    assert binds == "compute"
    assert least == pytest.approx(w["ops"] / PEAKS["int8_ops_per_s"])
