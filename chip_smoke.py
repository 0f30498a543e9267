#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the device data plane starts
on an attached TPU, through the entry points a node uses.

One process, one chip, package defaults.  Phases (each prints one JSON
line; the run stops at the first failed assertion, exit code != 0):

  0 device   a TPU is attached, the native libraries built
  1 BLS      a mainnet-shaped block (131 sets, ~66k member keys) and a
             1,024-set flood through THE seam (bls.verify_signature_sets),
             checked against the pure-Python reference backend
  2 node     a mainnet-preset node built as `cli.py bn` builds it imports
             a 128-aggregate block handed over as SSZ bytes
  3 merkle   validator-registry and balances roots at 2^20, device fold
             vs hashlib/native; sha threshold calibration; one AOT
             program-store round trip
  4 epoch    process_epoch and the swap-or-not shuffle at 2^20, device
             rung vs the numpy reference

`--chips 4` runs ONLY the cross-chip phase and what it is compared with.
`--rehearse` changes sizes and skips the device assertion, nothing else;
it prints {"rehearsal": true, ...} last and never the contract line.

The two `_pipeline_fused` programs a block import needs (256 lanes for
the block's sets, 4 lanes for the gossip stage's one-set proposer check)
cost minutes of single-threaded XLA compile each, so they start compiling
on background threads right after phase 0 — what ops/prewarm's "bls"
driver does for a node, cut to the buckets this run uses — while
phases 3 and 4 run and the host builds keys, sets and the node.
Phases 1 and 2 join them (on the clock the order is 0, 3, 4, 1, 2).

The last line of stdout on a chip is exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import contextlib
import hashlib
import json
import os
import shutil
import sys
import threading
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
# the node's datadir and the AOT stores: hundreds of MB of serialized
# executables, so inside the checkout (gitignored) and NOT under
# chiprun_out/, whose way home is capped
_OUT = os.path.join(_REPO, ".chip_smoke")

REAL = dict(keys=1 << 14, aggregates=128, set_keys=512, flood=1024,
            chunk=256, ref_sets=16, registry=1 << 20, sharded_sets=256)
REHEARSAL = dict(keys=64, aggregates=1, set_keys=16, flood=8,
                 chunk=4, ref_sets=3, registry=1 << 12, sharded_sets=8)

# device sites whose swallowed errors would mean a silent step-down
_LOUD_SITES = ("bls.", "bigint.", "native_bls.", "device_telemetry.",
               "prewarm.", "program_store.")
_PIPELINE = "ops/bls_backend.py::_pipeline_fused@_pipeline_fused"


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def check(cond, what):
    if not cond:
        print(f"chip_smoke: FAILED: {what}", file=sys.stderr, flush=True)
        raise SystemExit(1)


class Tee:
    """A stream that also keeps what passes (the node's start-up log:
    its Logger binds sys.stderr when the builder is constructed)."""

    def __init__(self, stream, lines):
        self.stream, self.lines = stream, lines

    def write(self, text):
        self.lines.append(text)
        return self.stream.write(text)

    def flush(self):
        self.stream.flush()


class clock:
    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.s = round(time.perf_counter() - self.t0, 3)


# -- reading the repo's own counters -----------------------------------------


def samples(name):
    """{frozen label set: value} of one metric family, through the
    repo's own exposition round trip."""
    from lighthouse_tpu.common import promtext
    from lighthouse_tpu.common.metrics import REGISTRY

    return {frozenset(s.labels): s.value
            for fam in promtext.parse(REGISTRY.render()).values()
            for s in fam.samples if s.name == name}


def total(name, **labels):
    want = set(labels.items())
    return sum(v for k, v in samples(name).items() if want <= k)


def loud_swallowed():
    return {dict(k)["site"]: v
            for k, v in samples("offload_swallowed_errors_total").items()
            if dict(k)["site"].startswith(_LOUD_SITES) and v}


def assert_quiet(where, expect_backend):
    """No fault, no recovery, no open breaker, nothing swallowed."""
    from lighthouse_tpu.crypto import bls

    health = bls.backend_health()
    check(total("bls_supervisor_faults_total") == 0,
          f"{where}: bls_supervisor_faults_total != 0")
    check(total("bls_supervisor_recoveries_total") == 0,
          f"{where}: bls_supervisor_recoveries_total != 0")
    check(set(health.values()) == {"closed"},
          f"{where}: breaker not closed: {health}")
    check(not loud_swallowed(),
          f"{where}: swallowed device-site errors: {loud_swallowed()}")
    if expect_backend == "tpu":
        check(total("bls_verify_batches_total", backend="reference") == 0,
              f"{where}: a batch was served by the reference backend")


@contextlib.contextmanager
def bls_verify_spans():
    """Collects (sets, served) of every finished ``bls.verify`` span —
    the seam stamps ``served`` with the rung that answered."""
    from lighthouse_tpu.common import tracing

    seen = []

    def walk(d):
        if d.get("name") == "bls.verify":
            attrs = d.get("attrs", {})
            seen.append((attrs.get("sets"), attrs.get("served")))
        for child in d.get("children", ()):
            walk(child)

    def sink(root, _slot):
        walk(root.to_dict())

    tracing.TRACER.add_sink(sink)
    try:
        yield seen
    finally:
        tracing.TRACER.remove_sink(sink)


# -- phase 0 -----------------------------------------------------------------


def phase_device(args):
    os.environ["LHTPU_AOT_PREWARM"] = "0"
    import jax
    import jaxlib

    from lighthouse_tpu.common import compile_cache

    cache_dir = compile_cache.configure()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU: jax found platform {dev.platform!r}",
              file=sys.stderr)
        raise SystemExit(1)
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import native_bls
    from lighthouse_tpu.ops import sha256 as sha_ops

    check(native_bls.available(), "native BLS library did not build")
    check(sha_ops._native_sha() is not None,
          "native SHA-256 library did not build")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    field_check(args)
    say("0 device", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, **device, compile_cache_dir=cache_dir,
        compile_cache_from_env=bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        native_bls=True, native_sha=True,
        mont_mul_equals_bigint=True, mxu_redc=bi._use_mxu_redc(),
        settings_made={"LHTPU_AOT_PREWARM": "0"}, seed=args.seed,
        rehearsal=args.rehearse)
    return device


def field_check(args):
    """The base of every BLS program, before minutes are spent compiling
    them: device Montgomery products (MXU REDC on a TPU, never run on a
    chip before this script) against Python integers."""
    import jax
    import numpy as np

    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import ec

    rng = np.random.default_rng(args.seed)
    a, b = ([int.from_bytes(rng.bytes(48), "big") % bi.P_INT
             for _ in range(256)] for _ in range(2))
    got = np.asarray(jax.jit(bi.mont_mul)(
        ec.ints_to_mont_limbs(a), ec.ints_to_mont_limbs(b)))
    check([int(v) % bi.P_INT for v in bi.from_mont(got)]
          == [x * y % bi.P_INT for x, y in zip(a, b)],
          f"0: device mont_mul != Python integers "
          f"(mxu_redc={bi._use_mxu_redc()})")


# -- background compiles ------------------------------------------------------


class Warm:
    """The long compiles of the BLS path, each on its own thread (XLA
    compiles one program on one core), dispatched through the same
    instrumented entries — and the same AOT store — a real dispatch
    uses, on zero-filled operands of the run's shapes."""

    def __init__(self, sz, enabled):
        import jax.numpy as jnp

        from lighthouse_tpu.ops import bigint as bi
        from lighthouse_tpu.ops import bls_backend as bb
        from lighthouse_tpu.ops import msm

        n_block = sz["aggregates"] + 3
        block_lanes = bb._next_pow2(n_block, floor=4)

        def limbs(n):
            return jnp.zeros((n, bi.L), jnp.uint32)

        def pipeline(n):
            return lambda: bb._pipeline_fused(
                *[limbs(n)] * 10, jnp.zeros((16, n), jnp.uint32),
                jnp.zeros((n,), bool), limbs(1), limbs(1), 0)

        def subgroup(n):
            return lambda: bb._g2_subgroup_kernel(*[limbs(n)] * 4)

        def blinded_fold():
            max_k, n_pad = bb._fold_shape([sz["set_keys"]] * n_block)
            rows = limbs(2 * max_k * n_pad)
            return msm._blinded_fold(rows, rows, rows, limbs(1), limbs(1),
                                     n_pad)

        jobs = {f"_pipeline_fused@{block_lanes}": pipeline(block_lanes),
                "_pipeline_fused@4": pipeline(4),
                "_blinded_fold": blinded_fold,
                f"_g2_subgroup_kernel@{block_lanes}": subgroup(block_lanes),
                f"_g2_subgroup_kernel@{sz['flood']}": subgroup(sz["flood"])}
        self.seconds, self.errors = {}, []
        self.threads = [
            threading.Thread(target=self._one, args=job, daemon=True,
                             name=f"warm-{job[0]}")
            for job in (jobs.items() if enabled else ())]
        for t in self.threads:
            t.start()

    def _one(self, name, dispatch):
        try:
            import jax

            t0 = time.perf_counter()
            jax.block_until_ready(dispatch())
            self.seconds[name] = round(time.perf_counter() - t0, 1)
        except BaseException as e:  # reported by join(), never dropped
            self.errors.append(f"{name}: {type(e).__name__}: {e}")

    def join(self):
        with clock() as c:
            for t in self.threads:
                t.join()
        check(not self.errors, f"background compile failed: {self.errors}")
        return c.s


# -- phase 1: BLS through the seam --------------------------------------------


def _msg(seed, tag, i):
    return hashlib.sha256(f"chip_smoke/{seed}/{tag}/{i}".encode()).digest()


def build_keys(n):
    """Interop secret keys, and their public keys as a node holds them:
    interned by their compressed bytes and decompressed FROM those bytes
    (KeyValidate included, ~4 ms of pure Python each) — once per
    validator per process, so the node of phase 2 meets keys this
    process has already seen, as a running node does."""
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.state_transition.genesis import (
        interop_pubkey,
        interop_secret_key,
    )

    pks = [bls.PublicKey.interned(interop_pubkey(i)) for i in range(n)]
    for pk in pks:
        pk.point
    return [interop_secret_key(i) for i in range(n)], pks


def build_block_sets(seed, sks, pks, sz):
    """128 aggregates of 512 keys, one 512-key sync-aggregate set (drawn
    with replacement, as sync committees are), proposer and randao:
    every message distinct, each set signed ONCE with the sum of its
    members' secret keys (byte-identical to aggregating 512 signatures)."""
    import numpy as np

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls.fields import R

    rng = np.random.default_rng(seed)
    shapes = ([(sz["set_keys"], False)] * sz["aggregates"]
              + [(sz["set_keys"], True), (1, False), (1, False)])
    sets = []
    for i, (k, replace) in enumerate(shapes):
        members = [int(j) for j in rng.choice(len(sks), k, replace=replace)]
        msg = _msg(seed, "block", i)
        sig = bls.SecretKey(sum(sks[j].k for j in members) % R).sign(msg)
        sets.append(bls.SignatureSet(sig, [pks[j] for j in members], msg))
    return sets


def build_flood_sets(seed, sks, pks, n):
    from lighthouse_tpu.crypto import bls

    return [bls.SignatureSet(sks[i % len(sks)].sign(_msg(seed, "flood", i)),
                             [pks[i % len(sks)]], _msg(seed, "flood", i))
            for i in range(n)]


def off_the_wire(sets):
    """The same sets with signatures as fresh compressed bytes, so every
    verification decompresses and subgroup-checks them again."""
    from lighthouse_tpu.crypto import bls

    return [bls.SignatureSet(bls.Signature(s.signature.to_bytes()),
                             s.pubkeys, s.message) for s in sets]


def corrupted(sets, at):
    """Set ``at`` carries its neighbour's (valid, in-subgroup) signature:
    nothing is rejected early, the pairing product itself must say no."""
    from lighthouse_tpu.crypto import bls

    bad = list(sets)
    bad[at] = bls.SignatureSet(sets[at - 1].signature, sets[at].pubkeys,
                               sets[at].message)
    return bad


def phase_bls(args, sz, block_sets, flood_sets, warm, expect):
    with bls_verify_spans() as spans:
        _phase_bls(args, sz, block_sets, flood_sets, warm, expect, spans)


def _phase_bls(args, sz, block_sets, flood_sets, warm, expect, spans):
    from lighthouse_tpu.common import device_telemetry as dtel
    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.crypto.bls import api as bls_api
    from lighthouse_tpu.ops import bigint as bi
    from lighthouse_tpu.ops import bls_backend as bb

    waited_s = warm.join()

    def served(last):
        return [rung for _, rung in spans[-last:]]

    n_members = sum(len(s.pubkeys) for s in block_sets)

    # (a) block shape.  Compile step: straight on the backend function,
    # outside the supervisor's watchdog.
    with clock() as first:
        check(bb.verify_signature_sets_device(off_the_wire(block_sets))
              is True, "1a: direct device dispatch rejected a valid block")
    # asserted step: THE seam, backend auto, supervised
    bad_at = sz["ref_sets"] - 1
    with clock() as warm_t:
        ok = bls.verify_signature_sets(
            off_the_wire(block_sets), backend="auto")
    check(ok is True, "1a: seam rejected a valid block-shaped batch")
    check(bls.verify_signature_sets(
        off_the_wire(corrupted(block_sets, bad_at)), backend="auto")
        is False, "1a: seam accepted a batch with one wrong signature")
    with clock() as ref_t:
        subset = block_sets[:sz["ref_sets"]]
        ref_ok = bls_api._verify_signature_sets_reference(
            off_the_wire(subset))
        ref_bad = bls_api._verify_signature_sets_reference(
            off_the_wire(corrupted(subset, bad_at)))
    check(ref_ok is True and ref_bad is False,
          f"1a: pure-Python reference disagrees: {ref_ok}, {ref_bad}")
    if expect == "tpu":
        check(served(2) == ["tpu", "tpu"],
              f"1a: batches served by {served(2)}, not the device")
    assert_quiet("1a", expect)
    fold = dtel.snapshot().get(
        "ops/msm.py::_blinded_fold@_blinded_fold", {})
    say("1a bls block", sets=len(block_sets), member_keys=n_members,
        widest_set=max(len(s.pubkeys) for s in block_sets),
        lanes=bb._next_pow2(len(block_sets), floor=4),
        background_compile_s=warm.seconds, waited_for_compile_s=waited_s,
        first_dispatch_s=first.s, warm_s=warm_t.s,
        verdict_valid=True, verdict_one_bad=False,
        reference_subset=len(subset), reference_s=ref_t.s,
        served=served(2), mxu_redc=bi._use_mxu_redc(),
        key_aggregation_rung=("device:_blinded_fold"
                              if fold.get("dispatches") else "host"),
        key_aggregation_dispatches=fold.get("dispatches", 0))
    if not args.rehearse:
        check(bi._use_mxu_redc(), "1a: MXU REDC is off on a TPU")

    # (b) flood shape: the seam's own chunk_size -> four 256-lane chunks
    chunks0 = total("bls_pipeline_chunks_total")
    with clock() as first:
        ok = bls.verify_signature_sets(
            off_the_wire(flood_sets), backend="auto",
            chunk_size=sz["chunk"])
    check(ok is True, "1b: seam rejected a valid flood")
    with clock() as warm_t:
        ok = bls.verify_signature_sets(
            off_the_wire(flood_sets), backend="auto",
            chunk_size=sz["chunk"])
    check(ok is True, "1b: seam rejected a valid flood (second pass)")
    check(bls.verify_signature_sets(
        off_the_wire(corrupted(flood_sets, len(flood_sets) // 2)),
        backend="auto", chunk_size=sz["chunk"]) is False,
        "1b: seam accepted a flood with one wrong signature")
    n_chunks = len(flood_sets) // sz["chunk"]
    if expect == "tpu":
        check(total("bls_pipeline_chunks_total") - chunks0 == 3 * n_chunks,
              "1b: the flood did not run as fixed chunks")
        check(served(3) == ["tpu"] * 3, f"1b: served by {served(3)}")
    assert_quiet("1b", expect)
    say("1b bls flood", sets=len(flood_sets), chunk_size=sz["chunk"],
        chunks=n_chunks, first_dispatch_s=first.s, warm_s=warm_t.s,
        verdict_valid=True, verdict_one_bad=False, served=served(3))


# -- phase 2: a node imports the block ----------------------------------------


def build_node(args, sz, datadir, genesis_time):
    """Exactly what `cli.py bn` does after parsing its arguments."""
    from lighthouse_tpu.client.builder import ClientBuilder, ClientConfig

    cfg = ClientConfig(
        network="mainnet", datadir=datadir, http_port=0,
        n_genesis_validators=sz["keys"], genesis_fork="capella",
        genesis_time=genesis_time, bls_backend="auto")
    return ClientBuilder(cfg).build()


def build_twin_block(client, sz, genesis_time):
    """A twin Harness on the same genesis state attests every committee
    of slots 1-32 and produces the slot-33 block that carries them all."""
    from lighthouse_tpu.state_transition import state_advance
    from lighthouse_tpu.testing import Harness

    spec = client.spec
    twin = Harness(sz["keys"], spec=spec, fork="capella",
                   genesis_time=genesis_time)
    check(twin.state.hash_tree_root()
          == client.chain.head_state.hash_tree_root(),
          "2: twin and node disagree on the genesis state")
    spe = spec.preset.slots_per_epoch
    state_advance(twin.state, spec, spe)
    from lighthouse_tpu.state_transition import misc

    per_slot = misc.get_committee_count_per_slot(spec, sz["keys"])
    atts = [twin.attest(slot, ci)
            for slot in range(1, spe + 1) for ci in range(per_slot)]
    atts = atts[:spec.preset.max_attestations]
    block = twin.produce_block(spe + 1, attestations=atts)
    return twin, block, atts


def phase_node(args, sz, client, block, atts, expect, log_lines):
    from lighthouse_tpu.common import device_telemetry as dtel
    from lighthouse_tpu.processor.beacon_processor import (
        WorkEvent,
        WorkType,
    )

    chain = client.chain
    check(any(f"bls backend: auto -> {expect}" in ln for ln in log_lines),
          f"2: start-up log does not say 'bls backend: auto -> {expect}'")
    raw = block.serialize()
    done, result = threading.Event(), {}

    def gossip_block_work():
        try:
            signed = chain.t.signed_beacon_block_class(
                "capella").deserialize(raw)
            result["root"] = chain.process_block(signed)
        except BaseException as e:
            result["error"] = f"{type(e).__name__}: {e}"
        finally:
            done.set()

    compiles0 = dict(dtel.snapshot().get(_PIPELINE, {})
                     .get("buckets", {}))
    with bls_verify_spans() as served, clock() as c:
        admitted = client.processor.submit(WorkEvent(
            WorkType.GOSSIP_BLOCK, process=gossip_block_work))
        check(bool(admitted), f"2: processor shed the block: {admitted}")
        check(done.wait(900), "2: block import did not finish in 900 s")
    check("error" not in result, f"2: import failed: {result.get('error')}")
    root = block.message.hash_tree_root()
    check(result["root"] == root, "2: process_block returned another root")
    check(chain.head_root == root, "2: head root is not the twin's block")
    check(chain.head_state.hash_tree_root()
          == bytes(block.message.state_root), "2: state root differs")
    n_sets = len(atts) + 3
    check(sorted(n for n, _ in served) == [1, n_sets - 1],
          f"2: expected a 1-set and a {n_sets - 1}-set batch: {served}")
    # only the supervised device backends stamp `served` on the span
    check({s for _, s in served} == {expect if expect == "tpu" else None},
          f"2: batches served by {served}, expected {expect}")
    assert_quiet("2", expect)
    pipe = dtel.snapshot().get(_PIPELINE, {})
    if expect == "tpu":
        check(pipe.get("dispatches", 0) > 0,
              "2: jit_dispatch_total is 0 for _pipeline_fused")
    say("2 node", preset="mainnet", fork="capella",
        validators=sz["keys"], block_slot=int(block.message.slot),
        wall_slot=chain.current_slot(), attestations=len(atts),
        signature_sets=n_sets, block_bytes=len(raw), import_s=c.s,
        batches=served, head_is_block=True, state_root_equal=True,
        pipeline_buckets_before=compiles0,
        pipeline_buckets=pipe.get("buckets"),
        pipeline_sources=pipe.get("sources"))


# -- phase 3: merkle ----------------------------------------------------------


def aot_roundtrip(out_dir):
    """One AOT program-store round trip on hash_pairs_device: compile and
    commit, drop the store, arm it again, and the same dispatch must be
    served from disk.  Runs before the node's store is armed."""
    import numpy as np

    from lighthouse_tpu.common import device_telemetry as dtel
    from lighthouse_tpu.common import flight_recorder as flight
    from lighthouse_tpu.ops import program_store
    from lighthouse_tpu.ops import sha256 as sha_ops

    entry = "ops/sha256.py::hash_pairs_device@hash_pairs_device"
    store_dir = os.path.join(out_dir, "aot_roundtrip")
    seq0 = flight.RECORDER.seq
    pairs = np.arange(4096 * 16, dtype=np.uint32).reshape(4096, 16)
    want = sha_ops.hash_pairs_np(pairs)
    sources = []
    for _ in range(2):
        program_store.configure(store_dir)
        before = dict(dtel.snapshot().get(entry, {}).get("sources", {}))
        got = np.asarray(sha_ops.hash_pairs_device(pairs))
        after = dtel.snapshot()[entry]["sources"]
        sources.append({k: v - before.get(k, 0) for k, v in after.items()
                        if v != before.get(k, 0)})
        check(np.array_equal(got, want), "3: AOT-served hash != hashlib")
        program_store.deactivate()
    check(sources == [{"compiled": 1}, {"store_hit": 1}],
          f"3: AOT round trip served {sources}")
    check(samples("aot_store_commits_total")
          == {frozenset({("outcome", "committed")}): 1.0},
          f"3: commits: {samples('aot_store_commits_total')}")
    check(total("aot_store_hits_total") == 1,
          "3: expected exactly one aot_store_hits_total")
    corrupt = [e for e in flight.RECORDER.events_since(seq0)
               if e.get("kind") == "aot_store_corrupt"]
    check(not corrupt, f"3: aot_store_corrupt flight events: {corrupt}")
    return sources


def phase_merkle(args, sz, state, aot_sources):
    import numpy as np

    from lighthouse_tpu import types as T
    from lighthouse_tpu.ops import sha256 as sha_ops

    n = len(state.validators)
    limit = 1 << 40
    with clock() as leaves_t:
        vroots = T.ValidatorRegistryType(limit).batch_roots(state.validators)
        bal = np.zeros((n + 3) // 4 * 4, "<u8")
        bal[:n] = state.balances
        broots = np.frombuffer(bal.tobytes(), ">u4").astype(
            np.uint32).reshape(-1, 8)
    out = {}
    for name, leaves, lim in (("validators", vroots, limit),
                              ("balances", broots, limit // 4)):
        with clock() as first:
            dev = sha_ops.merkleize_words(leaves, lim, device=True)
        with clock() as warm:
            dev2 = sha_ops.merkleize_words(leaves, lim, device=True)
        with clock() as host:
            ref = sha_ops.merkleize_words(leaves, lim, device=False)
        check(np.array_equal(dev, ref) and np.array_equal(dev2, ref),
              f"3: device {name} root != hashlib/native root")
        out[name] = {"leaves": int(leaves.shape[0]),
                     "first_dispatch_s": first.s, "warm_s": warm.s,
                     "host_s": host.s,
                     "root": sha_ops.words_to_bytes(ref).hex()[:16]}
    check(total("sha256_merkle_chunks_total", path="fold_device") > 0,
          "3: the device fold path was not taken")
    calibration = sha_ops.calibrate_device_thresholds()

    check(not loud_swallowed(), f"3: swallowed: {loud_swallowed()}")
    say("3 merkle", registry=n, leaf_build_s=leaves_t.s, **out,
        calibration=calibration, aot_roundtrip=aot_sources)


# -- phase 4: epoch + shuffle --------------------------------------------------


def _epoch_faults():
    return total("epoch_supervisor_faults_total")


def phase_epoch(args, sz, state, spec, expect_rung):
    import numpy as np

    from lighthouse_tpu.state_transition import epoch_processing as ep
    from lighthouse_tpu.state_transition import shuffle
    from lighthouse_tpu.testing import registry_state_digest

    n = len(state.validators)
    rung = ep.resolve_epoch_backend(n)
    if expect_rung is not None:
        check(rung == expect_rung,
              f"4: resolve_epoch_backend({n}) == {rung!r}, "
              f"expected {expect_rung!r}")
    faults0 = _epoch_faults()
    batches0 = total("epoch_backend_batches_total", backend=rung)
    times = {}
    for label in ("first_dispatch_s", "warm_s"):
        st = state.copy()
        with clock() as c:
            ep.process_epoch(st, spec)
        times[label] = c.s
    auto_digest = registry_state_digest(st)
    check(total("epoch_backend_batches_total", backend=rung)
          - batches0 == 2, f"4: the {rung} rung did not serve both passes")
    os.environ["LHTPU_EPOCH_BACKEND"] = "reference"
    try:
        st = state.copy()
        with clock() as ref_t:
            ep.process_epoch(st, spec)
    finally:
        del os.environ["LHTPU_EPOCH_BACKEND"]
    check(auto_digest == registry_state_digest(st),
          f"4: {rung} epoch digest != reference digest")

    idx = np.arange(n, dtype=np.uint64)
    seed = hashlib.sha256(f"chip_smoke/{args.seed}/shuffle".encode()).digest()
    from lighthouse_tpu.types.spec import MAINNET_PRESET

    rounds = MAINNET_PRESET.shuffle_round_count
    with clock() as s_first:
        dev = shuffle.shuffle_list_device(idx, seed, rounds)
    with clock() as s_warm:
        dev2 = shuffle.shuffle_list_device(idx, seed, rounds)
    with clock() as s_ref:
        ref = shuffle.shuffle_list(idx, seed, rounds, device=False)
    check(np.array_equal(dev, ref) and np.array_equal(dev2, ref),
          "4: device shuffle != host shuffle")
    check(_epoch_faults() == faults0, "4: epoch fault counter moved")
    with ep._BREAKER_LOCK:
        open_until = ep._BREAKER["open_until"]
    check(open_until == 0.0, "4: epoch breaker opened")
    check(not loud_swallowed(), f"4: swallowed: {loud_swallowed()}")
    say("4 epoch", validators=n, rung=rung, epoch=times,
        epoch_reference_s=ref_t.s, digest=auto_digest[:16],
        shuffle={"indices": n, "rounds": rounds,
                 "first_dispatch_s": s_first.s, "warm_s": s_warm.s,
                 "host_s": s_ref.s})


# -- the cross-chip phase (--chips 4) -----------------------------------------


class ShardSpy:
    """Records, for an instrumented entry's array operands and results,
    which devices hold a shard and whether the shards are slices of the
    array (partitioned) or whole copies (replicated) — from
    jax.Array.addressable_shards."""

    def __init__(self, inst):
        self.inst, self.fn = inst, inst._fn
        self.inputs, self.outputs = [], []

    @staticmethod
    def _placement(tree):
        import jax

        return [{"devices": sorted(s.device.id
                                   for s in leaf.addressable_shards),
                 "shape": list(leaf.shape),
                 "shard": list(leaf.addressable_shards[0].data.shape)}
                for leaf in jax.tree_util.tree_leaves(tree)
                if hasattr(leaf, "addressable_shards")]

    def __enter__(self):
        def call(*a, **k):
            out = self.fn(*a, **k)
            self.inputs += self._placement((a, k))
            self.outputs += self._placement(out)
            return out

        self.inst._fn = call
        return self

    def __exit__(self, *exc):
        self.inst._fn = self.fn

    def evidence(self, n_dev, what, partitioned_inputs):
        """Every device holds a slice of >= ``partitioned_inputs``
        operands, and every result lives on every device."""
        ids = list(range(n_dev))
        sliced = [p for p in self.inputs
                  if p["devices"] == ids and p["shard"] != p["shape"]]
        check(len(sliced) >= partitioned_inputs,
              f"{what}: {len(sliced)} operands are partitioned over all "
              f"{n_dev} devices, expected >= {partitioned_inputs}: "
              f"{self.inputs}")
        check(self.outputs and all(p["devices"] == ids
                                   for p in self.outputs),
              f"{what}: results not on every device: {self.outputs}")
        return {"partitioned_operands": len(sliced),
                "operand": sliced[0], "result": self.outputs[0],
                "results_partitioned": sum(
                    p["shard"] != p["shape"] for p in self.outputs)}


def phase_sharded(args, sz, n_dev):
    import jax
    import numpy as np

    from lighthouse_tpu.crypto import bls
    from lighthouse_tpu.ops import epoch_kernels as ek
    from lighthouse_tpu.ops import msm, pubkey_kernels
    from lighthouse_tpu.parallel import bls_sharded, msm_sharded
    from lighthouse_tpu.state_transition import epoch_processing as ep
    from lighthouse_tpu.testing import (
        randomized_registry_state,
        registry_state_digest,
    )

    check(len(jax.devices()) >= n_dev,
          f"--chips {n_dev}: jax reports {len(jax.devices())} devices")
    mesh_ids = [d.id for d in jax.devices()[:n_dev]]
    check(mesh_ids == list(range(n_dev)), f"device ids {mesh_ids}")

    # sharded BLS vs the single-chip verdict on the same sets
    sks, pks = build_keys(min(sz["keys"], sz["sharded_sets"]))
    sets = build_flood_sets(args.seed, sks, pks, sz["sharded_sets"])
    bad = corrupted(sets, len(sets) // 2)
    # what the mesh is compared with, the single-chip seam on the same
    # sets, compiles its own fused program for minutes: on a thread,
    # joined after the other cross-chip checks
    single = {}

    def single_chip():
        try:
            with clock() as c1:
                single["ok"] = bls.verify_signature_sets(
                    off_the_wire(sets), backend="tpu")
            single["first_s"] = c1.s
        except BaseException as e:
            single["error"] = f"{type(e).__name__}: {e}"

    single_t = threading.Thread(target=single_chip, daemon=True)
    single_t.start()
    with clock() as c0:
        check(bls_sharded.verify_signature_sets_sharded(
            off_the_wire(sets), n_devices=n_dev) is True,
            "sharded BLS rejected valid sets")
    # the mesh program that call compiled (memoized per mesh and lanes)
    inst = list(bls_sharded._SHARDED_JIT_CACHE.values())[-1]
    with ShardSpy(inst) as spy, clock() as c:
        ok = bls_sharded.verify_signature_sets_sharded(
            off_the_wire(sets), n_devices=n_dev)
        no = bls_sharded.verify_signature_sets_sharded(
            off_the_wire(bad), n_devices=n_dev)
    bls_verdicts = (ok, no)
    bls_line = dict(first_dispatch_s=c0.s, sharded_warm_s=c.s,
                    shards=spy.evidence(n_dev, "bls_sharded", 7))
    # sharded epoch pass vs the single-device digest
    state, spec = randomized_registry_state(
        sz["registry"], "capella", args.seed, eject_frac=0.0)
    digests = {}
    for rung in ("sharded", "device"):
        os.environ["LHTPU_EPOCH_BACKEND"] = rung
        try:
            st = state.copy()
            with ShardSpy(ek._epoch_pass_jit()) as spy, clock() as c:
                ep.process_epoch(st, spec)
        finally:
            del os.environ["LHTPU_EPOCH_BACKEND"]
        digests[rung] = (registry_state_digest(st), c.s)
        if rung == "sharded":
            shards = spy.evidence(n_dev, "epoch_sharded", 8)
    check(digests["sharded"][0] == digests["device"][0],
          "sharded epoch digest != single-device digest")
    check(_epoch_faults() == 0, "epoch fault counter moved")
    say("x epoch_sharded", devices=n_dev, validators=sz["registry"],
        sharded_s=digests["sharded"][1], single_s=digests["device"][1],
        digest=digests["device"][0][:16], shards=shards)

    # sharded gather fold vs the single-device fold
    from lighthouse_tpu.state_transition.genesis import interop_public_key

    n_rows, n_groups, per = len(pks), 4 * n_dev, 8
    table = pubkey_kernels.build_table(
        [interop_public_key(i).point for i in range(n_rows)])
    rng = np.random.default_rng(args.seed)
    lanes = n_groups * per
    rows = rng.integers(0, n_rows, lanes).astype(np.int64)
    scalars = rng.integers(1, 1 << 63, lanes, dtype=np.uint64)
    groups = np.repeat(np.arange(n_groups), per)
    with ShardSpy(msm._gather_fold) as spy, clock() as c:
        got = msm_sharded.gather_fold_sharded(
            table, rows, scalars, groups, n_groups,
            mesh=msm_sharded.msm_mesh(n_dev))
    with clock() as c1:
        want = pubkey_kernels.gather_fold(
            table, rows, scalars, groups, n_groups)
    check(all(np.array_equal(a, b) for a, b in zip(got, want)),
          "sharded gather fold != single-device gather fold")
    say("x msm_sharded", devices=n_dev, lanes=lanes, groups=n_groups,
        sharded_s=c.s, single_s=c1.s,
        shards=spy.evidence(n_dev, "msm_sharded", 2))

    single_t.join()
    check("error" not in single, f"single-chip seam: {single.get('error')}")
    with clock() as c1:
        no1 = bls.verify_signature_sets(off_the_wire(bad), backend="tpu")
    check(bls_verdicts == (True, False)
          and (single["ok"], no1) == (True, False),
          f"sharded BLS {bls_verdicts} vs single chip {single['ok'], no1}")
    assert_quiet("x", "tpu")
    say("x bls_sharded", devices=n_dev, sets=len(sets),
        **bls_line, verdicts=list(bls_verdicts),
        single_chip_first_s=single["first_s"], single_chip_warm_s=c1.s)


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, no device assertion (CPU rehearsal)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the cross-chip phase")
    args = ap.parse_args(argv)
    sz = REHEARSAL if args.rehearse else REAL
    t_start = time.perf_counter()
    device = phase_device(args)

    if args.chips == 4:
        check(device["count"] >= 4 or args.rehearse,
              f"--chips 4 on {device['count']} device(s)")
        phase_sharded(args, sz, 4)
    else:
        from lighthouse_tpu.crypto import bls
        from lighthouse_tpu.ops import program_store
        from lighthouse_tpu.testing import randomized_registry_state

        expect = bls.resolve_auto_backend()
        check(args.rehearse or expect == "tpu",
              f"auto resolves to {expect!r} on a TPU")
        shutil.rmtree(_OUT, ignore_errors=True)
        aot_sources = aot_roundtrip(_OUT)
        datadir = os.path.join(_OUT, "node")
        # the store a node arms at start-up, at the directory the node of
        # phase 2 will arm it at: every dispatch of this run goes through
        # it, as a node's does, and the node finds its programs there
        program_store.configure(os.path.join(datadir, "aot_programs"))
        warm = Warm(sz, enabled=expect == "tpu" or args.rehearse)

        # numpy and device work first: it leaves the interpreter lock to
        # the background threads while they TRACE (pure Python, ~1.5 min
        # in all; no compile starts before its trace ends) — the pure-
        # Python key generation after it would starve them instead
        state, spec = randomized_registry_state(
            sz["registry"], "capella", args.seed, eject_frac=0.0)
        phase_merkle(args, sz, state, aot_sources)
        phase_epoch(args, sz, state, spec,
                    None if args.rehearse else "device")
        del state
        with clock() as keys_t:
            sks, pks = build_keys(sz["keys"])

        with clock() as sets_t:
            block_sets = build_block_sets(args.seed, sks, pks, sz)
            flood_sets = build_flood_sets(args.seed, sks, pks, sz["flood"])
        # the node and its twin's block are host work too: built while
        # the fused programs still compile
        log_lines = []
        spe = 32
        genesis_time = int(time.time()) - (spe + 1) * 12
        with clock() as node_t, contextlib.redirect_stderr(
                Tee(sys.stderr, log_lines)):
            client = build_node(args, sz, datadir, genesis_time)
        try:
            with clock() as twin_t:
                twin, block, atts = build_twin_block(
                    client, sz, genesis_time)
            say("host set-up", interop_keys=sz["keys"], keys_s=keys_t.s,
                signing_s=sets_t.s, node_build_s=node_t.s,
                twin_block_s=twin_t.s)
            phase_bls(args, sz, block_sets, flood_sets, warm, expect)
            phase_node(args, sz, client, block, atts, expect, log_lines)
        finally:
            client.stop()
        pipe = samples("jit_compiles_total")
        compiles = {dict(k)["bucket"]: v for k, v in pipe.items()
                    if dict(k)["entry"] == _PIPELINE}
        check(all(v == 1 for v in compiles.values()),
              f"_pipeline_fused compiled more than once per bucket: "
              f"{compiles}")
        say("compiles", pipeline_fused_compiles_by_bucket=compiles,
            swallowed=loud_swallowed())

    print(json.dumps({"cold_total_s":
                      round(time.perf_counter() - t_start, 1)}), flush=True)
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device}), flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # a failed phase may leave a background XLA compile running; tearing
    # the interpreter down under it crashes instead of exiting
    os._exit(rc)
