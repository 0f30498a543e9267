"""lhlint (tools/lint) — fixture coverage for every pass + the real-tree
baseline gate.

Every pass gets at least one positive fixture (the rule must fire) and
one negative fixture (the compliant twin must stay silent).  Fixtures are tiny synthesized packages mirroring the real
layout (``chain/beacon_chain.py``, ``ops/dispatch_pipeline.py``,
``common/env.py``…) so the passes' real module-targeting config applies
unchanged.  The real-tree tests are the tier-1 wiring: the analyzer
must exit 0 against the checked-in baseline, and the baseline must
never grow.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.lint import analyze  # noqa: E402
from tools.lint import baseline as bl  # noqa: E402

BASELINE_PATH = REPO / "tools" / "lint" / "baseline.json"


def make_pkg(tmp_path, files: dict[str, str], readme: str | None = None):
    pkg = tmp_path / "pkg"
    for rel, source in files.items():
        path = pkg / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
    readme_path = None
    if readme is not None:
        readme_path = tmp_path / "README.md"
        readme_path.write_text(readme)
    return pkg, readme_path


def rules_of(findings):
    return sorted({f.rule for f in findings})


def sans_aot(findings):
    """Drop LH606: fixture trees carry jax.jit sites without
    program-store registrations, so the AOT-coverage pass correctly
    fires there — but these tests assert OTHER passes' behavior (the
    LH606 fixtures have their own section)."""
    return [f for f in findings if f.rule != "LH606"]


# -- pass 1: lock discipline --------------------------------------------------


def test_lock_pass_flags_direct_blocking(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/beacon_chain.py": """
        import time

        class Chain:
            def bad(self):
                with self._import_lock:
                    time.sleep(1)
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH101"]
    assert "time.sleep" in findings[0].message
    assert findings[0].symbol == "Chain.bad:sleep"


def test_lock_pass_negative_outside_lock(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/beacon_chain.py": """
        import time

        class Chain:
            def good(self):
                with self._import_lock:
                    x = 1
                time.sleep(1)
    """})
    assert analyze(pkg) == []


def test_lock_pass_reaches_through_call_graph(tmp_path):
    # device fetch two calls deep, in another module, still caught
    pkg, _ = make_pkg(tmp_path, {
        "chain/beacon_chain.py": """
            from pkg.chain.helpers import commit

            class Chain:
                def bad(self):
                    with self._import_lock:
                        commit(self)
        """,
        "chain/helpers.py": """
            import jax

            def commit(chain):
                finish(chain)

            def finish(chain):
                return jax.device_get(chain.buf)
        """,
    })
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH101"]
    assert "commit->finish" in findings[0].symbol


def test_lock_pass_flags_bls_entry_and_suppression(tmp_path):
    source = """
        from pkg.crypto import bls

        class Chain:
            def bad(self):
                with self._import_lock:
                    bls.verify_signature_sets([])

            def waived(self):
                with self._import_lock:  # lhlint: allow(bls-under-lock)
                    bls.verify_signature_sets([])
    """
    pkg, _ = make_pkg(tmp_path, {"chain/beacon_chain.py": source,
                                 "crypto/bls.py": ""})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH102"]
    assert findings[0].symbol.startswith("Chain.bad")


def test_lock_order_cycle_flagged(tmp_path):
    # the satellite fixture: A→B in one function, B→A in another
    pkg, _ = make_pkg(tmp_path, {"store/locking.py": """
        def forward():
            with LOCK_A:
                with LOCK_B:
                    pass

        def backward():
            with LOCK_B:
                with LOCK_A:
                    pass
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH103", "LH103"]
    symbols = {f.symbol for f in findings}
    assert "forward:LOCK_A->LOCK_B" in symbols
    assert "backward:LOCK_B->LOCK_A" in symbols


def test_lock_order_cycle_across_modules(tmp_path):
    # shared module-level lock constants match package-wide: the A→B
    # nesting lives in one file, the B→A nesting (via a module alias)
    # in another — still a cycle
    pkg, _ = make_pkg(tmp_path, {
        "store/hot_cold.py": """
            DB_LOCK = object()
            CACHE_LOCK = object()

            def forward():
                with DB_LOCK:
                    with CACHE_LOCK:
                        pass
        """,
        "chain/beacon_chain.py": """
            from pkg.store import hot_cold

            def backward():
                with hot_cold.CACHE_LOCK:
                    with hot_cold.DB_LOCK:
                        pass
        """,
    })
    findings = [f for f in analyze(pkg) if f.rule == "LH103"]
    assert len(findings) == 2
    assert {f.file.rsplit("/", 1)[-1] for f in findings} == {
        "hot_cold.py", "beacon_chain.py"}


def test_lock_order_same_order_not_flagged(tmp_path):
    # nested-same-order pair everywhere: no cycle, no finding
    pkg, _ = make_pkg(tmp_path, {"store/locking.py": """
        def one():
            with LOCK_A:
                with LOCK_B:
                    pass

        def two():
            with LOCK_A:
                with LOCK_B:
                    pass
    """})
    assert analyze(pkg) == []


# -- pass 2: one-fetch discipline ---------------------------------------------


def test_fetch_pass_flags_stray_fetch(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/dispatch_pipeline.py": """
        import jax
        import numpy as np

        def sneaky_probe(buf):
            return np.asarray(buf)
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH201"]
    assert findings[0].symbol == "sneaky_probe:asarray"


def test_fetch_pass_allows_commit_points(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/dispatch_pipeline.py": """
        import numpy as np

        class AsyncVerdict:
            def commit(self):
                return bool(np.asarray(self._dev_ok).all())
    """})
    assert analyze(pkg) == []


# -- pass 3: shape / jit discipline -------------------------------------------


def test_shape_pass_flags_traced_branch(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/kernels.py": """
        import jax

        @jax.jit
        def bad(x, flag):
            if flag:
                return x + 1
            return x
    """})
    findings = sans_aot(analyze(pkg))
    assert [f.rule for f in findings] == ["LH301"]
    assert "flag" in findings[0].symbol


def test_shape_pass_static_argnums_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/kernels.py": """
        import jax
        from functools import partial

        @partial(jax.jit, static_argnums=(1,))
        def good(x, flag):
            if flag:
                return x + 1
            return x
    """})
    assert sans_aot(analyze(pkg)) == []


def test_shape_pass_flags_jit_in_function(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/kernels.py": """
        import jax

        def per_call(fn, x):
            return jax.jit(fn)(x)
    """})
    findings = sans_aot(analyze(pkg))
    assert [f.rule for f in findings] == ["LH302"]


def test_shape_pass_memoized_jit_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/kernels.py": """
        import jax

        _JIT_CACHE = {}

        def memoized(fn):
            got = _JIT_CACHE.get(fn)
            if got is None:
                got = _JIT_CACHE[fn] = jax.jit(fn)
            return got
    """})
    assert sans_aot(analyze(pkg)) == []


def test_shape_pass_scans_epoch_modules(tmp_path):
    # PR 6 wiring: the shape passes must reach state_transition/ and the
    # epoch kernel module, not just the BLS offload files.  A jitted
    # epoch pass branching on a traced column and a per-round jit built
    # inside the shuffle sweep are both the exact mistakes the fused
    # epoch program must never reintroduce.
    pkg, _ = make_pkg(tmp_path, {
        "state_transition/epoch_device.py": """
            import jax

            @jax.jit
            def epoch_pass(balances, leak):
                if leak:
                    return balances - 1
                return balances
        """,
        "ops/epoch_kernels.py": """
            import jax

            def shuffle_rounds(lanes, rounds):
                for r in range(rounds):
                    lanes = jax.jit(_round)(lanes, r)
                return lanes

            def _round(lanes, r):
                return lanes
        """,
    })
    findings = sans_aot(analyze(pkg))
    by_file = {f.file: f.rule for f in findings}
    assert by_file == {
        "pkg/state_transition/epoch_device.py": "LH301",
        "pkg/ops/epoch_kernels.py": "LH302",
    }


def test_shape_pass_epoch_modules_compliant_twin(tmp_path):
    # the compliant shapes: leak/fork are static_argnames (per-truth
    # compile is intended — two programs, cached), and the per-fork jit
    # is memoized in a module cache keyed by (fork, bucket)
    pkg, _ = make_pkg(tmp_path, {
        "state_transition/epoch_device.py": """
            import jax
            from functools import partial

            @partial(jax.jit, static_argnames=("leak",))
            def epoch_pass(balances, leak):
                if leak:
                    return balances - 1
                return balances
        """,
        "ops/epoch_kernels.py": """
            import jax

            _EPOCH_JIT_CACHE = {}

            def compiled_pass(fork, bucket):
                got = _EPOCH_JIT_CACHE.get((fork, bucket))
                if got is None:
                    got = _EPOCH_JIT_CACHE[(fork, bucket)] = jax.jit(_pass)
                return got

            def _pass(cols):
                return cols
        """,
    })
    assert sans_aot(analyze(pkg)) == []


def test_shape_pass_real_epoch_tree_is_clean():
    # the shipped epoch/shuffle call sites obey LH301/302 with NO
    # baseline debt: scan the real package and assert zero shape
    # findings anywhere in state_transition/ or the epoch kernel module
    findings = analyze(REPO / "lighthouse_tpu")
    shape = [f for f in findings
             if f.rule in ("LH301", "LH302")
             and (f.file.startswith("lighthouse_tpu/state_transition/")
                  or f.file == "lighthouse_tpu/ops/epoch_kernels.py")]
    assert shape == []


# -- pass 4: env registry -----------------------------------------------------

ENV_REGISTRY = """
    ENV_VARS = {}

    def _register(name, default, description):
        ENV_VARS[name] = (default, description)

    _register("LHTPU_GOOD", None, "a documented knob")
"""


def test_env_pass_flags_unregistered_read(tmp_path):
    pkg, readme = make_pkg(tmp_path, {
        "common/env.py": ENV_REGISTRY,
        "ops/thing.py": """
            import os

            GOOD = os.environ.get("LHTPU_GOOD")
            ROGUE = os.environ.get("LHTPU_ROGUE")
        """,
    }, readme="docs mention LHTPU_GOOD here")
    findings = analyze(pkg, readme=readme)
    assert [f.rule for f in findings] == ["LH401"]
    assert findings[0].symbol == "LHTPU_ROGUE"


def test_env_pass_registered_reads_negative(tmp_path):
    pkg, readme = make_pkg(tmp_path, {
        "common/env.py": ENV_REGISTRY,
        "ops/thing.py": """
            import os

            GOOD = os.getenv("LHTPU_GOOD")
            ALSO = os.environ["LHTPU_GOOD"]
        """,
    }, readme="docs mention LHTPU_GOOD here")
    assert analyze(pkg, readme=readme) == []


def test_env_pass_flags_readme_drift(tmp_path):
    pkg, readme = make_pkg(tmp_path, {"common/env.py": ENV_REGISTRY},
                           readme="no mention of the knob at all")
    findings = analyze(pkg, readme=readme)
    assert [f.rule for f in findings] == ["LH402"]
    assert findings[0].symbol == "LHTPU_GOOD"


def test_env_pass_flags_stale_readme_mention(tmp_path):
    # the reverse direction: README documents a knob the registry lost
    pkg, readme = make_pkg(tmp_path, {"common/env.py": ENV_REGISTRY},
                           readme="LHTPU_GOOD is real, LHTPU_GONE is not")
    findings = analyze(pkg, readme=readme)
    assert [f.rule for f in findings] == ["LH402"]
    assert findings[0].symbol == "readme:LHTPU_GONE"


def test_env_pass_prefix_name_not_masked(tmp_path):
    # LHTPU_GOOD documented must NOT make a registered LHTPU_GOO count
    # as documented (substring false positive)
    pkg, readme = make_pkg(tmp_path, {"common/env.py": ENV_REGISTRY + """
    _register("LHTPU_GOO", None, "prefix of the documented knob")
"""}, readme="only LHTPU_GOOD is documented")
    findings = analyze(pkg, readme=readme)
    assert [f.symbol for f in findings if f.rule == "LH402"] == [
        "LHTPU_GOO"]


# -- pass 5: metric discipline ------------------------------------------------


def test_metrics_pass_flags_problems(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"mod.py": """
        REGISTRY.counter(f"dyn_{x}_total", "h")
        REGISTRY.gauge("Bad-Name", "h")
        REGISTRY.counter("twice_total", "h")
        REGISTRY.histogram("twice_total", "h")
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH501"]
    text = "\n".join(f.message for f in findings)
    assert "dynamic metric name" in text
    assert "invalid metric name" in text
    assert "multiple kinds" in text


def test_metrics_pass_clean_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"mod.py": """
        C = REGISTRY.counter("events_total", "h")
    """})
    assert analyze(pkg) == []


def test_metrics_pass_pins_fleet_scrape_family_to_simulator(tmp_path):
    # ISSUE 16: scrape-plane accounting belongs to the observer's
    # ScrapeDiscipline — a fleet_scrape_* registration anywhere else
    # (e.g. the promtext parser growing its own series) is a finding
    pkg, _ = make_pkg(tmp_path, {"common/promtext.py": """
        REGISTRY.histogram("fleet_scrape_seconds", "h")
    """})
    findings = [f for f in analyze(pkg) if f.rule == "LH501"]
    assert findings, "fleet_scrape_ family not pinned to simulator.py"
    assert "simulator.py" in findings[0].message


def test_metrics_pass_fleet_scrape_owner_is_clean(tmp_path):
    # the owner pin is a path suffix, so the compliant twin must sit at
    # .../lighthouse_tpu/simulator.py like the real registration site
    pkg, _ = make_pkg(tmp_path, {"lighthouse_tpu/simulator.py": """
        REGISTRY.histogram("fleet_scrape_seconds", "h")
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH501"] == []


def test_check_metrics_shim_collect_still_works(tmp_path):
    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "mod.py").write_text(
        'REGISTRY.counter(f"dyn_{x}_total", "h")\n')
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_metrics
    finally:
        sys.path.pop(0)
    regs, errors = check_metrics.collect(bad)
    assert any("dynamic metric name" in e for e in errors)


# -- pass 6: supervised dispatch discipline -----------------------------------


def test_supervisor_pass_flags_unsupervised_dispatch(tmp_path):
    # _kernel reached from the supervised entry through a helper is
    # fine; the same kernel dispatched from a stray probe is flagged
    pkg, _ = make_pkg(tmp_path, {"ops/bls_backend.py": """
        import jax

        @jax.jit
        def _kernel(x):
            return x

        def verify_signature_sets_device(sets):
            return _helper(sets)

        def _helper(sets):
            return _kernel(sets)

        def rogue_probe(x):
            return _kernel(x)
    """})
    findings = sans_aot(analyze(pkg))
    assert [f.rule for f in findings] == ["LH601"]
    assert findings[0].symbol == "rogue_probe:_kernel"
    assert "not reachable from a supervisor-wrapped entry" \
        in findings[0].message


def test_supervisor_pass_assignment_jit_and_suppression(tmp_path):
    # jax.jit bound by assignment counts as a dispatch callable; an
    # explicit allow() waives the finding
    pkg, _ = make_pkg(tmp_path, {"ops/dispatch_pipeline.py": """
        import jax

        def _mul(a, b):
            return a * b

        _mul_jit = jax.jit(_mul)

        def stray(a, b):
            return _mul_jit(a, b)  # lhlint: allow(LH601)
    """})
    assert sans_aot(analyze(pkg)) == []


def test_supervisor_pass_negative_supervised_chain(tmp_path):
    # cross-module: the sharded entry reaches the shared combine helper
    pkg, _ = make_pkg(tmp_path, {
        "parallel/bls_sharded.py": """
            from pkg.ops import dispatch_pipeline as dp

            def verify_signature_sets_sharded(sets):
                return dp.combine(sets)
        """,
        "ops/dispatch_pipeline.py": """
            import jax
            from functools import partial

            @partial(jax.jit, static_argnums=(1,))
            def _pair(a, n):
                return a

            def combine(parts):
                return _pair(parts, 2)
        """,
    })
    assert sans_aot(analyze(pkg)) == []


# -- pass 7: store commit discipline ------------------------------------------


def test_store_pass_flags_raw_engine_write(tmp_path):
    # a raw hot.put next to other mutations is exactly the torn window
    pkg, _ = make_pkg(tmp_path, {"store/hot_cold.py": """
        class DB:
            def sneaky_meta_write(self, key, value):
                self.hot.put(key, value)
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH701"]
    assert findings[0].symbol == "DB.sneaky_meta_write:hot.put"
    assert "do_atomically" in findings[0].message


def test_store_pass_flags_chain_modules_and_bare_names(tmp_path):
    # chain/ is in scope too, and `cold` bound to a bare name still hits
    pkg, _ = make_pkg(tmp_path, {"chain/beacon_chain.py": """
        def prune(store):
            cold = store.cold
            cold.delete(b"fbr:0")
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH701"]
    assert findings[0].symbol == "prune:cold.delete"


def test_store_pass_negative_commit_points_and_batches(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"store/hot_cold.py": """
        class DB:
            def put_block(self, root, payload):
                self.hot.put(b"blk:" + root, payload)

            def delete_block(self, root):
                self.hot.delete(b"blk:" + root)

            def migrate(self, ops):
                self.hot.do_atomically(ops)
    """})
    assert analyze(pkg) == []


def test_store_pass_out_of_scope_modules_ignored(tmp_path):
    # network/backfill-style writers are outside the pass's modules
    pkg, _ = make_pkg(tmp_path, {"network/backfill.py": """
        def backfill(store):
            store.cold.put(b"fbr:0", b"x")
    """})
    assert analyze(pkg) == []


def test_store_pass_suppression(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"store/hot_cold.py": """
        class DB:
            def waived(self, key, value):
                self.hot.put(key, value)  # lhlint: allow(LH701)
    """})
    assert analyze(pkg) == []


# -- pass 8: accounted shed (LH603) -------------------------------------------


def test_shed_pass_flags_unaccounted_del(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"pool/naive_aggregation.py": """
        class Pool:
            def prune_below(self, slot):
                for s in [s for s in self._slots if s < slot]:
                    del self._slots[s]
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH603"]
    assert findings[0].symbol == "Pool.prune_below:_slots"
    assert "_shed_total" in findings[0].message


def test_shed_pass_flags_discarded_pop(tmp_path):
    # an Expr-statement pop throws the removed work away
    pkg, _ = make_pkg(tmp_path, {"processor/reprocess.py": """
        class Queue:
            def expire(self, root):
                self._by_root.pop(root, None)
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH603"]
    assert findings[0].symbol == "Queue.expire:_by_root.pop"


def test_shed_pass_compliant_twin_metric_literal(tmp_path):
    # same discard, accounted via a direct *_dropped_total registration
    pkg, _ = make_pkg(tmp_path, {"pool/naive_aggregation.py": """
        from lighthouse_tpu.common.metrics import REGISTRY

        class Pool:
            def prune_below(self, slot):
                for s in [s for s in self._slots if s < slot]:
                    REGISTRY.counter("pool_dropped_total", "h").inc()
                    del self._slots[s]
    """})
    assert analyze(pkg) == []


def test_shed_pass_compliant_twin_helper_call(tmp_path):
    # accounting through a package helper (record-*-drop naming) counts
    pkg, _ = make_pkg(tmp_path, {
        "pool/accounting.py": """
            def record_pool_dropped(pool, reason, n=1):
                from lighthouse_tpu.common.metrics import REGISTRY
                REGISTRY.counter("pool_dropped_total", "h").inc(n)
        """,
        "pool/naive_aggregation.py": """
            from pkg.pool.accounting import record_pool_dropped

            class Pool:
                def prune_below(self, slot):
                    for s in [s for s in self._slots if s < slot]:
                        record_pool_dropped("naive", "finalized")
                        del self._slots[s]
        """,
    })
    assert analyze(pkg) == []


def test_shed_pass_bound_pop_is_not_a_discard(tmp_path):
    # a pop whose result is processed is work HANDLED, not shed
    pkg, _ = make_pkg(tmp_path, {"processor/reprocess.py": """
        class Queue:
            def flush(self, root):
                for parked in self._by_root.pop(root, []):
                    self.processor.submit(parked)
    """})
    assert analyze(pkg) == []


def test_shed_pass_bookkeeping_receivers_exempt(tmp_path):
    # flush timestamps / restart stamps never hold work items
    pkg, _ = make_pkg(tmp_path, {"processor/beacon_processor.py": """
        class BP:
            def tidy(self, wt):
                self._batch_first_seen.pop(wt, None)
                self._dispatch_restarts.popleft()
    """})
    assert analyze(pkg) == []


def test_shed_pass_out_of_scope_modules_ignored(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"network/gossip.py": """
        class Cache:
            def evict(self, k):
                del self._seen[k]
    """})
    assert analyze(pkg) == []


def test_shed_pass_suppression(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"pool/operation_pool.py": """
        class Pool:
            def evict(self, k):
                del self._ops[k]  # lhlint: allow(LH603)
    """})
    assert analyze(pkg) == []


def test_shed_pass_real_tree_zero_findings():
    """The real tree carries NO unaccounted shed paths (fixed, not
    baselined): every processor/pool discard routes through
    _account_shed / record_pool_dropped."""
    findings = analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    assert [f for f in findings if f.rule == "LH603"] == []


# -- pass 12: accounted sync abandon (LH604) ----------------------------------


def test_sync_pass_flags_unaccounted_penalty(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"network/sync.py": """
        class SyncManager:
            def download(self, peer):
                blocks = self.rpc.request(peer, "range", b"")
                if not blocks:
                    self.peers.report(peer, "high")
                    return None
                return blocks
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH604"]
    assert findings[0].symbol == "SyncManager.download:penalty_report"
    assert "sync_*_total" in findings[0].message


def test_sync_pass_flags_handler_exit(tmp_path):
    # a return inside an except handler abandons the in-flight attempt
    pkg, _ = make_pkg(tmp_path, {"network/backfill.py": """
        class BackfillSync:
            def process_batch(self, peer):
                try:
                    chunks = self.rpc.request(peer, "range", b"")
                except ValueError:
                    return 0
                return len(chunks)
    """})
    findings = analyze(pkg)
    assert [f.rule for f in findings] == ["LH604"]
    assert findings[0].symbol == "BackfillSync.process_batch:handler_return"


def test_sync_pass_compliant_twin_metric_literal(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"network/sync.py": """
        from lighthouse_tpu.common.metrics import REGISTRY

        class SyncManager:
            def download(self, peer):
                blocks = self.rpc.request(peer, "range", b"")
                if not blocks:
                    REGISTRY.counter("sync_attempts_total", "h").labels(
                        outcome="retried").inc()
                    self.peers.report(peer, "high")
                    return None
                return blocks
    """})
    assert analyze(pkg) == []


def test_sync_pass_compliant_twin_helper_call(tmp_path):
    # funneling through a package accounting helper counts
    pkg, _ = make_pkg(tmp_path, {"network/sync.py": """
        from lighthouse_tpu.common.metrics import REGISTRY

        class SyncManager:
            def _downscore(self, peer, level, reason):
                REGISTRY.counter("sync_penalties_total", "h").labels(
                    reason=reason).inc()
                self.peers.report(peer, level)

            def download(self, peer):
                try:
                    return self.rpc.request(peer, "range", b"")
                except ValueError:
                    self._downscore(peer, "mid", "rpc_error")
                    return None
    """})
    assert analyze(pkg) == []


def test_sync_pass_out_of_scope_modules_ignored(tmp_path):
    # only the sync-plane modules are in scope — the router's penalty
    # reports have their own (gossip-delivery) accounting story
    pkg, _ = make_pkg(tmp_path, {"network/router.py": """
        class Router:
            def on_bad_block(self, peer):
                self.peers.report(peer, "mid")
    """})
    assert analyze(pkg) == []


def test_sync_pass_suppression(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"network/sync.py": """
        class SyncManager:
            def download(self, peer):
                self.peers.report(peer, "high")  # lhlint: allow(LH604)
    """})
    assert analyze(pkg) == []


def test_sync_pass_real_tree_zero_findings():
    """The real sync plane carries NO unaccounted abandons/downscores
    (fixed, not baselined): every penalty and every attempt exit routes
    through the _account*/_downscore funnels."""
    findings = analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    assert [f for f in findings if f.rule == "LH604"] == []


# -- pass 13: recorded breaker/ladder transitions (LH605) ---------------------


def test_flight_pass_flags_unrecorded_rung_change(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"processor/admission.py": """
        class AdmissionController:
            def sweep(self, depths):
                self.rung = 1
                return self.rung
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH605"]
    assert findings[0].symbol == "AdmissionController.sweep:set_rung"
    assert "flight-recorder" in findings[0].message


def test_flight_pass_flags_unrecorded_breaker_state(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"crypto/bls/api.py": """
        class Breaker:
            def record_failure(self):
                self.state = "open"
    """})
    f605 = [f for f in analyze(pkg) if f.rule == "LH605"]
    assert [f.symbol for f in f605] == ["Breaker.record_failure:set_state"]


def test_flight_pass_flags_open_until_store(tmp_path):
    pkg, _ = make_pkg(tmp_path, {
        "state_transition/epoch_processing.py": """
        _BREAKER = {"open_until": 0.0}

        def breaker_fault(now):
            _BREAKER["open_until"] = now + 1.0
    """})
    f605 = [f for f in analyze(pkg) if f.rule == "LH605"]
    assert [f.symbol for f in f605] == ["breaker_fault:set_open_until"]


def test_flight_pass_compliant_twin_direct_emit(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"processor/admission.py": """
        from lighthouse_tpu.common import flight_recorder as flight

        class AdmissionController:
            def sweep(self, depths):
                self.rung = 1
                flight.emit("ladder", old=0, new=1)
                return self.rung
    """})
    assert analyze(pkg) == []


def test_flight_pass_compliant_twin_helper_funnel(tmp_path):
    # funneling through a package helper that emits counts
    pkg, _ = make_pkg(tmp_path, {"crypto/bls/api.py": """
        from lighthouse_tpu.common import flight_recorder as flight

        def _note_transition(backend, old, new):
            flight.emit("breaker", backend=backend, old=old, new=new)

        class Breaker:
            def record_failure(self):
                old, self.state = self.state, "open"
                _note_transition(self.backend, old, "open")
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH605"] == []


def test_flight_pass_init_and_reset_exempt(tmp_path):
    # initialization is not a transition
    pkg, _ = make_pkg(tmp_path, {"crypto/bls/api.py": """
        class Breaker:
            def __init__(self):
                self.state = "closed"

            def reset(self):
                self.state = "closed"
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH605"] == []


def test_flight_pass_flags_unrecorded_chain_health_stall(tmp_path):
    # ISSUE 13: the chain-health detector's stall machine gates the
    # finality_stall trip — an unrecorded edge silences the trip itself
    pkg, _ = make_pkg(tmp_path, {"chain/chain_health.py": """
        class ChainHealthMonitor:
            def _enter_stall(self, lag):
                self.state = "stalled"
    """})
    f605 = [f for f in analyze(pkg) if f.rule == "LH605"]
    assert [f.symbol for f in f605] == \
        ["ChainHealthMonitor._enter_stall:set_state"]


def test_flight_pass_chain_health_compliant_twin(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/chain_health.py": """
        from lighthouse_tpu.common import flight_recorder as flight

        class ChainHealthMonitor:
            def _enter_stall(self, lag):
                self.state = "stalled"
                flight.trip("finality_stall", lag_epochs=lag)

            def _clear_stall(self, lag):
                self.state = "ok"
                flight.emit("finality_recovered", lag_epochs=lag)
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH605"] == []


def test_flight_pass_flags_unrecorded_chaos_edge(tmp_path):
    # ISSUE 15: the chaos controller's armed/disarmed edges ARE the
    # soak's causal record — an unrecorded edge silences the timeline
    # the drill gates on
    pkg, _ = make_pkg(tmp_path, {"chain/chaos.py": """
        class ChaosController:
            def arm(self, rec):
                rec.state = "armed"
    """})
    f605 = [f for f in analyze(pkg) if f.rule == "LH605"]
    assert [f.symbol for f in f605] == ["ChaosController.arm:set_state"]


def test_flight_pass_chaos_compliant_twin(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/chaos.py": """
        from lighthouse_tpu.common import flight_recorder as flight

        class ChaosController:
            def arm(self, rec):
                rec.state = "armed"
                flight.emit("chaos_edge", plane=rec.plane, edge="armed")
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH605"] == []


def test_flight_pass_flags_unrecorded_node_lifecycle(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"simulator.py": """
        class LocalNetwork:
            def kill(self, node):
                node.state = "killed"
    """})
    f605 = [f for f in analyze(pkg) if f.rule == "LH605"]
    assert [f.symbol for f in f605] == ["LocalNetwork.kill:set_state"]


def test_flight_pass_node_lifecycle_compliant_twin(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"simulator.py": """
        from lighthouse_tpu.common import flight_recorder as flight

        class LocalNetwork:
            def kill(self, node):
                node.state = "killed"
                flight.emit("node_kill", node=node.name)
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH605"] == []


def test_flight_pass_flags_unrecorded_reachability_edge(tmp_path):
    # ISSUE 16: the observer's per-node reachability machine — an
    # unrecorded reachable<->unreachable edge makes a scrape outage
    # forensically invisible
    pkg, _ = make_pkg(tmp_path, {"simulator.py": """
        class FleetObserver:
            def _mark_unreachable(self, name, fails):
                reach = self._reach[name]
                reach.state = "unreachable"
    """})
    f605 = [f for f in analyze(pkg) if f.rule == "LH605"]
    assert [f.symbol for f in f605] == \
        ["FleetObserver._mark_unreachable:set_state"]


def test_flight_pass_reachability_compliant_twin(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"simulator.py": """
        from lighthouse_tpu.common import flight_recorder as flight

        class FleetObserver:
            def _mark_unreachable(self, name, fails):
                reach = self._reach[name]
                reach.state = "unreachable"
                flight.emit("node_unreachable", node=name,
                            consecutive_failures=fails)

            def _mark_reachable(self, name):
                reach = self._reach[name]
                reach.state = "reachable"
                flight.emit("node_reachable", node=name)
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH605"] == []


def test_flight_pass_out_of_scope_modules_ignored(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"network/peer_manager.py": """
        class Peer:
            def ban(self):
                self.state = "banned"
    """})
    assert analyze(pkg) == []


def test_flight_pass_suppression(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"processor/admission.py": """
        class AdmissionController:
            def sweep(self, depths):
                self.rung = 1  # lhlint: allow(LH605)
    """})
    assert analyze(pkg) == []


def test_flight_pass_real_tree_zero_findings():
    """Every breaker/ladder transition in the real tree emits its
    flight-recorder event (fixed, not baselined)."""
    findings = analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    assert [f for f in findings if f.rule == "LH605"] == []


def test_exceptions_pass_network_scope(tmp_path):
    # PR 10 extended LH902 to the network plane: an unaccounted broad
    # swallow in network/ is a finding now
    pkg, _ = make_pkg(tmp_path, {"network/gossip.py": """
        def deliver(handler, msg):
            try:
                handler(msg)
            except Exception:
                return None
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH902"]


def test_exceptions_pass_real_network_tree_clean():
    """network/ carries no unaccounted swallows (fixed or justified
    inline, not baselined)."""
    findings = analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    assert [f for f in findings
            if f.rule in ("LH901", "LH902")
            and f.file.startswith("lighthouse_tpu/network/")] == []


# -- baseline machinery -------------------------------------------------------


def test_baseline_compare_new_stale(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/beacon_chain.py": """
        import time

        class Chain:
            def bad(self):
                with self._import_lock:
                    time.sleep(1)
    """})
    findings = analyze(pkg)
    key = findings[0].key
    # exactly baselined: clean
    new, stale = bl.compare(findings, {key: 1})
    assert new == [] and stale == {}
    # not baselined: regression
    new, stale = bl.compare(findings, {})
    assert [f.key for f in new] == [key]
    # over-baselined: stale warning only
    new, stale = bl.compare(findings, {key: 2, "LH999::gone.py::x": 1})
    assert new == []
    assert stale == {key: 1, "LH999::gone.py::x": 1}


# -- the real tree (tier-1 wiring) --------------------------------------------


def test_real_tree_passes_against_baseline():
    findings = analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    new, _stale = bl.compare(findings, bl.load(BASELINE_PATH))
    assert new == [], "new lhlint findings:\n" + "\n".join(
        f.render() for f in new)


def test_baseline_never_grows():
    """The gate is new-regression-only: every baselined key must still
    correspond to a real finding (stale entries warn), and — the actual
    invariant — no finding may exceed its baselined allowance.  The
    baseline can only shrink: fixing code removes entries, nothing adds
    them."""
    baseline = bl.load(BASELINE_PATH)
    findings = analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    from collections import Counter

    current = Counter(f.key for f in findings)
    grown = {k: c for k, c in current.items() if c > baseline.get(k, 0)}
    assert not grown, f"baseline would need to GROW for: {grown}"
    stale = {k: v for k, v in baseline.items() if current.get(k, 0) < v}
    if stale:  # warn-only, mirroring the CLI
        import warnings

        warnings.warn(f"stale lhlint baseline entries: {sorted(stale)}")


def test_baseline_documents_only_known_debt():
    """The two grandfathered findings are the 1-set proposer/header
    signature authentications that must precede dup-cache marks; the
    heavy-work-under-lock findings from the seed (full-block BLS batch,
    blob KZG batch) were FIXED in this PR, not baselined."""
    baseline = bl.load(BASELINE_PATH)
    assert all(k.startswith("LH102::") for k in baseline)
    assert not any("verify_block_signatures" in k for k in baseline)
    assert not any("validate_blobs" in k for k in baseline)


def test_cli_exits_zero_on_repo():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "lhlint: ok" in proc.stdout


def test_cli_fails_on_new_finding(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/beacon_chain.py": """
        import time

        def bad():
            with GLOBAL_LOCK:
                time.sleep(1)
    """})
    empty_baseline = tmp_path / "baseline.json"
    empty_baseline.write_text("{}")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--root", str(pkg),
         "--baseline", str(empty_baseline)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 1
    assert "LH101" in proc.stderr


def test_env_registry_matches_process_env_reads():
    """Every LHTPU_* read in the package resolves through (or is
    registered in) common/env.py, and the typed readers behave."""
    from lighthouse_tpu.common import env as envreg

    assert envreg.get_int("LHTPU_BENCH_TIMEOUT") == 420
    assert envreg.get("LHTPU_BLS_CHUNK") is None
    with pytest.raises(KeyError):
        envreg.get("LHTPU_NOT_A_KNOB")
    os.environ["LHTPU_BLS_CHUNK"] = "64"
    try:
        assert envreg.get_int("LHTPU_BLS_CHUNK") == 64
    finally:
        del os.environ["LHTPU_BLS_CHUNK"]


def test_readme_env_table_rows_match_registry():
    """Row-level sync: every registry entry has a README table row and
    every table row names a registered knob (env.table() is the source
    of truth the README section claims to be checked against)."""
    import re

    from lighthouse_tpu.common import env as envreg

    text = (REPO / "README.md").read_text()
    rows = {m.group(1) for m in re.finditer(
        r"^\| `(LHTPU_\w+)` \|", text, re.MULTILINE)}
    registered = {v.name for v in envreg.table()}
    assert rows == registered, (
        f"README table rows != registry: only-in-readme="
        f"{sorted(rows - registered)}, only-in-registry="
        f"{sorted(registered - rows)}")


def test_baseline_json_is_valid_and_small():
    data = json.loads(BASELINE_PATH.read_text())
    assert isinstance(data, dict)
    assert all(isinstance(v, int) and v > 0 for v in data.values())


# -- v2: pass 8 device-numeric safety (LH80x) ---------------------------------


def test_numeric_pass_flags_host_int64_lane(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/epoch_bridge.py": """
        import jax.numpy as jnp

        def bad(epochs):
            return jnp.asarray(epochs, dtype=jnp.int64)
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH801"]
    assert "enable_x64" in findings[0].message


def test_numeric_pass_x64_scope_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/epoch_bridge.py": """
        import jax
        import jax.numpy as jnp

        def good(epochs):
            with jax.enable_x64():
                return jnp.asarray(epochs, dtype=jnp.int64)
    """})
    assert analyze(pkg) == []


def test_numeric_pass_flags_unscoped_int64_dispatch(tmp_path):
    # the traced body is exempt (tracing happens at dispatch); the
    # DISPATCH outside the scope is the bug
    pkg, _ = make_pkg(tmp_path, {"chain/epoch_bridge.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kernel(cols):
            return cols.astype(jnp.int64) + 1

        def bad_dispatch(cols):
            return kernel(cols)
    """})
    findings = sans_aot(analyze(pkg))
    assert rules_of(findings) == ["LH801"]
    assert "dispatch" in findings[0].symbol


def test_numeric_pass_scoped_dispatch_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/epoch_bridge.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def kernel(cols):
            return cols.astype(jnp.int64) + 1

        def good_dispatch(cols):
            with jax.enable_x64():
                return kernel(cols)
    """})
    assert sans_aot(analyze(pkg)) == []


def test_numeric_pass_flags_true_division_on_gwei_lanes(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/rewards.py": """
        import jax.numpy as jnp

        def bad(balances):
            cols = jnp.asarray(balances)
            return cols / 32
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH802"]
    assert "gwei" in findings[0].message


def test_numeric_pass_floor_division_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/rewards.py": """
        import jax.numpy as jnp

        def good(balances):
            cols = jnp.asarray(balances)
            return cols // 32
    """})
    assert analyze(pkg) == []


def test_numeric_pass_host_float_math_not_flagged(tmp_path):
    # host-only floats (bench math, ratios) must never trip LH802: the
    # pass fires only on positively classified device/traced values
    pkg, _ = make_pkg(tmp_path, {"chain/bench.py": """
        def ratio(balance_total, n):
            return balance_total / n
    """})
    assert analyze(pkg) == []


def test_numeric_pass_flags_unclamped_uint64_bridge(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"state_transition/epoch_device.py": """
        import numpy as np
        import jax.numpy as jnp

        def bridge(exit_epochs):
            cols = exit_epochs.astype(np.uint64)
            return jnp.asarray(cols)
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH803"]
    assert "clamp" in findings[0].message


def test_numeric_pass_clamp_constant_exempts(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"state_transition/epoch_device.py": """
        import numpy as np
        import jax.numpy as jnp

        EPOCH_CLAMP = 1 << 62

        def bridge(exit_epochs):
            cols = np.minimum(exit_epochs, EPOCH_CLAMP).astype(np.uint64)
            return jnp.asarray(cols)
    """})
    assert analyze(pkg) == []


def test_numeric_pass_build_tables_none_guard_exempts(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"state_transition/epoch_device.py": """
        import numpy as np
        import jax.numpy as jnp

        EPOCH_CLAMP = 1 << 62

        def build_tables(max_eb):
            if max_eb >= EPOCH_CLAMP:
                return None
            return max_eb

        def bridge(exit_epochs):
            cols = exit_epochs.astype(np.uint64)
            return jnp.asarray(cols)
    """})
    assert analyze(pkg) == []


def test_numeric_pass_suppression(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"chain/epoch_bridge.py": """
        import jax.numpy as jnp

        def waived(epochs):
            return jnp.asarray(epochs, dtype=jnp.int64)  # lhlint: allow(LH801)
    """})
    assert analyze(pkg) == []


# -- v2: pass 9 blocking-fetch escalation (LH811) -----------------------------


def test_blocking_pass_flags_fetch_under_lock_package_wide(tmp_path):
    # api/ is NOT in LH101's lock-owner module list — LH811 covers it
    pkg, _ = make_pkg(tmp_path, {"api/http_api.py": """
        import jax.numpy as jnp

        class Api:
            def bad(self, values):
                arr = jnp.asarray(values)
                with self._lock:
                    return arr.item()
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH811"]
    assert "with self._lock" in findings[0].message


def test_blocking_pass_fetch_outside_lock_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"api/http_api.py": """
        import jax.numpy as jnp

        class Api:
            def good(self, values):
                arr = jnp.asarray(values)
                got = arr.item()
                with self._lock:
                    return got
    """})
    assert analyze(pkg) == []


def test_blocking_pass_reaches_through_call_graph_under_lock(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"api/http_api.py": """
        import jax.numpy as jnp

        def _materialize(values):
            arr = jnp.asarray(values)
            return arr.item()

        def _level3(values):
            return _materialize(values)

        def _level2(values):
            return _level3(values)

        def _level1(values):
            return _level2(values)

        class Api:
            def bad(self, values):
                with self._lock:
                    return _level1(values)
    """})
    # 4 hops deep — beyond LH101's 3-hop limit, within LH811's unlimited
    # reachability
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH811"]
    assert "reachable under" in findings[0].message


def test_blocking_pass_flags_dispatch_thread_fetch(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"processor/beacon_processor.py": """
        import jax.numpy as jnp

        def _drain(batch):
            arr = jnp.asarray(batch)
            return arr.item()

        def _dispatch_loop(batch):
            return _drain(batch)
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH811"]
    assert "dispatch thread" in findings[0].message


def test_blocking_pass_commit_points_exempt(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"processor/beacon_processor.py": """
        import jax.numpy as jnp

        def commit(batch):
            arr = jnp.asarray(batch)
            return arr.item()

        def _dispatch_loop(batch):
            return commit(batch)
    """})
    assert analyze(pkg) == []


def test_blocking_pass_host_values_not_flagged(tmp_path):
    # .item() on a host numpy value is not a device fetch — the lattice
    # must positively classify the receiver
    pkg, _ = make_pkg(tmp_path, {"api/http_api.py": """
        import numpy as np

        class Api:
            def fine(self, values):
                arr = np.asarray(values)
                with self._lock:
                    return arr.item()
    """})
    assert analyze(pkg) == []


# -- v2: pass 10 swallowed-exception discipline (LH90x) -----------------------


def test_exceptions_pass_flags_silent_pass(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"network/wire/transport.py": """
        def notify(cb):
            try:
                cb()
            except Exception:
                pass
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH901"]
    assert "record_swallowed" in findings[0].message


def test_exceptions_pass_funneled_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"network/wire/transport.py": """
        from pkg.common.metrics import record_swallowed

        def notify(cb):
            try:
                cb()
            except Exception as e:
                record_swallowed("wire.notify", e)
    """})
    assert analyze(pkg) == []


def test_exceptions_pass_narrowed_type_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"network/wire/transport.py": """
        def notify(cb):
            try:
                cb()
            except (OSError, ValueError):
                pass
    """})
    assert analyze(pkg) == []


def test_exceptions_pass_waiver(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"common/metrics.py": """
        def sink(fn):
            try:
                fn()
            except Exception:  # lhlint: allow(LH901)
                pass  # terminal sink: must never re-raise
    """})
    assert analyze(pkg) == []


def test_exceptions_pass_flags_unaccounted_swallow_in_offload(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/probe.py": """
        def probe(compute):
            try:
                return compute()
            except Exception:
                return None
    """})
    findings = analyze(pkg)
    assert rules_of(findings) == ["LH902"]
    assert "starve the breaker" in findings[0].message


def test_exceptions_pass_unaccounted_outside_offload_not_flagged(tmp_path):
    # LH902 is scoped to the offload/supervisor modules; elsewhere a
    # handled fallback is ordinary defensive code
    pkg, _ = make_pkg(tmp_path, {"api/http_api.py": """
        def probe(compute):
            try:
                return compute()
            except Exception:
                return None
    """})
    assert analyze(pkg) == []


def test_exceptions_pass_accounted_swallow_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/probe.py": """
        from pkg.common.metrics import record_swallowed

        def probe(compute):
            try:
                return compute()
            except Exception as e:
                record_swallowed("ops.probe", e)
                return None
    """})
    assert analyze(pkg) == []


def test_exceptions_pass_log_on_computed_receiver_accounted(tmp_path):
    # ``_log().warn(...)`` — the receiver is a call, not a name; the
    # terminal attribute must still count as accounting
    pkg, _ = make_pkg(tmp_path, {"ops/probe.py": """
        def probe(compute, _log):
            try:
                return compute()
            except Exception:
                _log().warn("degraded")
                return None
    """})
    assert analyze(pkg) == []


def test_exceptions_pass_reraise_accounted(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/probe.py": """
        def probe(compute):
            try:
                return compute()
            except Exception:
                cleanup()
                raise
    """})
    assert analyze(pkg) == []


# -- v2: LH602 supervision completeness ---------------------------------------


def test_supervisor_pass_flags_driver_missing_breaker_hooks(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"crypto/bls/api.py": """
        class _Supervisor:
            def verify(self, name, sets, chunk_size):
                try:
                    return run_device(sets)
                except Exception:
                    return run_reference(sets)
    """})
    findings = [f for f in analyze(pkg) if f.rule == "LH602"]
    assert sorted(f.symbol for f in findings) == [
        "_Supervisor.verify:fault-hook", "_Supervisor.verify:ok-hook"]


def test_supervisor_pass_driver_with_hooks_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"crypto/bls/api.py": """
        class _Supervisor:
            def verify(self, name, sets, chunk_size):
                try:
                    out = run_device(sets)
                    self.breakers[name].record_success()
                    return out
                except Exception:
                    self.breakers[name].record_failure()
                    return run_reference(sets)
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH602"] == []


def test_supervisor_pass_flags_renamed_driver(tmp_path):
    # the LADDERS table names `_Supervisor.verify`; a rename must fail
    # the lint until the table moves with it
    pkg, _ = make_pkg(tmp_path, {"crypto/bls/api.py": """
        class _Supervisor:
            def run(self, name, sets):
                return run_device(sets)
    """})
    findings = [f for f in analyze(pkg) if f.rule == "LH602"]
    assert [f.symbol for f in findings] == ["_Supervisor.verify:missing"]
    assert "LADDERS" in findings[0].message


def test_supervisor_pass_real_tree_ladders_complete():
    findings = analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    assert [f for f in findings if f.rule == "LH602"] == []


# -- v2: real-tree zero-findings gates ----------------------------------------


def test_real_tree_clean_for_v2_rules():
    """The PR's breadth claim: every LH80x/LH81x/LH90x finding in the
    real tree was FIXED (or carries an inline-justified waiver), not
    baselined — the baseline still holds only the two LH102 entries."""
    findings = analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    v2 = [f for f in findings
          if f.rule in ("LH801", "LH802", "LH803", "LH811",
                        "LH901", "LH902", "LH602")]
    assert v2 == [], "v2 findings in the real tree:\n" + "\n".join(
        f.render() for f in v2)


def test_real_tree_waivers_are_justified():
    """Every inline LH90x/LH602/LH100x waiver must carry prose (a
    comment beyond the allow() itself) on the same or adjacent line."""
    import re

    allow_re = re.compile(
        r"#\s*lhlint:\s*allow\((LH9\d\d|LH602|LH10\d\d)\)")
    for path in sorted((REPO / "lighthouse_tpu").rglob("*.py")):
        lines = path.read_text().splitlines()
        for i, line in enumerate(lines):
            m = allow_re.search(line)
            if not m:
                continue
            tail = line[m.end():].strip(" —-")
            nxt = lines[i + 1].strip() if i + 1 < len(lines) else ""
            assert tail or nxt.startswith("#") or "#" in nxt, (
                f"{path}:{i + 1}: waiver without justification")


# -- pass 14: AOT program-store coverage (LH606) ------------------------------


def test_aot_pass_flags_unregistered_jit_entry(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/kern.py": """
        import jax

        @jax.jit
        def f(x):
            return x + 1
    """})
    f606 = [f for f in analyze(pkg) if f.rule == "LH606"]
    assert [f.symbol for f in f606] == ["ops/kern.py::f@f"]
    assert "register_entry" in f606[0].message


def test_aot_pass_registered_twin_negative(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/kern.py": """
        import jax

        from lighthouse_tpu.ops import program_store as _pstore

        _pstore.register_entry("ops/kern.py::f@f", driver="kern")

        @jax.jit
        def f(x):
            return x + 1
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH606"] == []


def test_aot_pass_registration_may_live_in_another_module(tmp_path):
    """The registry is package-wide: a central registration module
    covers entries it does not define."""
    pkg, _ = make_pkg(tmp_path, {
        "ops/kern.py": """
        import jax

        @jax.jit
        def f(x):
            return x + 1
        """,
        "ops/registry.py": """
        from lighthouse_tpu.ops import program_store

        program_store.register_entry("ops/kern.py::f@f", driver="kern")
        """})
    assert [f for f in analyze(pkg) if f.rule == "LH606"] == []


def test_aot_pass_waiver(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/kern.py": """
        import jax

        @jax.jit  # lhlint: allow(LH606) — one-shot dryrun program
        def f(x):
            return x + 1
    """})
    assert [f for f in analyze(pkg) if f.rule == "LH606"] == []


def test_aot_pass_wrong_id_still_flags(tmp_path):
    """A registration whose literal drifted from the manifest id is a
    hole, not coverage."""
    pkg, _ = make_pkg(tmp_path, {"ops/kern.py": """
        import jax

        from lighthouse_tpu.ops import program_store as _pstore

        _pstore.register_entry("ops/kern.py::old_name@f", driver="kern")

        @jax.jit
        def f(x):
            return x + 1
    """})
    f606 = [f for f in analyze(pkg) if f.rule == "LH606"]
    assert [f.symbol for f in f606] == ["ops/kern.py::f@f"]


def test_aot_real_tree_every_manifest_entry_registered():
    """The real-tree LH606 gate: all 21 shape-manifest entries carry a
    program_store.register_entry registration (zero findings, zero
    waivers today), and the runtime registry agrees with the static
    sweep once the owner modules import."""
    findings = [f for f in analyze(REPO / "lighthouse_tpu",
                                   readme=REPO / "README.md")
                if f.rule == "LH606"]
    assert findings == [], "\n".join(f.render() for f in findings)


# -- the jit shape manifest ---------------------------------------------------

MANIFEST_PATH = REPO / "tools" / "lint" / "shape_manifest.json"


def _build_real_manifest():
    from tools.lint import build_context
    from tools.lint import manifest as mf

    ctx = build_context(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    return mf.build_manifest(ctx)


def test_manifest_matches_tree():
    """The LH402-style sync gate: the checked-in manifest must be byte-
    identical to a regeneration from the tree (``python -m tools.lint
    --manifest`` refreshes it)."""
    from tools.lint import manifest as mf

    assert MANIFEST_PATH.exists(), "run: python -m tools.lint --manifest"
    assert mf.render(_build_real_manifest()) == MANIFEST_PATH.read_text(), (
        "tools/lint/shape_manifest.json is stale — regenerate with "
        "`python -m tools.lint --manifest`")


def test_manifest_covers_every_jit_site():
    """Independent cross-check: a from-scratch AST sweep for jax.jit
    constructions (calls AND decorators) over the package must find no
    site the manifest misses."""
    import ast as _ast

    manifest = json.loads(MANIFEST_PATH.read_text())
    covered = {(e["file"], e["line"]) for e in manifest["entries"]}

    def dotted(expr):
        parts = []
        node = expr
        while isinstance(node, _ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, _ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        return None

    missing = []
    for path in sorted((REPO / "lighthouse_tpu").rglob("*.py")):
        rel = str(path.relative_to(REPO))
        tree = _ast.parse(path.read_text())
        for node in _ast.walk(tree):
            if isinstance(node, _ast.Call) \
                    and dotted(node.func) in ("jax.jit", "jit"):
                if (rel, node.lineno) not in covered:
                    missing.append(f"{rel}:{node.lineno} (call)")
            elif isinstance(node, (_ast.FunctionDef,
                                   _ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    text = dotted(dec) or (
                        dotted(dec.func)
                        if isinstance(dec, _ast.Call) else None)
                    inner = None
                    if isinstance(dec, _ast.Call) and dec.args \
                            and text in ("partial", "functools.partial"):
                        inner = dotted(dec.args[0])
                    if text in ("jax.jit", "jit") \
                            or inner in ("jax.jit", "jit"):
                        if (rel, dec.lineno) not in covered:
                            missing.append(f"{rel}:{dec.lineno} (decorator)")
    assert not missing, "jit sites absent from shape_manifest.json:\n" \
        + "\n".join(missing)


def test_manifest_entry_shape_and_owners():
    manifest = json.loads(MANIFEST_PATH.read_text())
    assert manifest["version"] == 1
    entries = manifest["entries"]
    assert entries, "manifest must enumerate the jit bucket set"
    required = {"id", "file", "line", "kind", "target", "backend",
                "static_argnums", "static_argnames", "dtypes",
                "int64_lanes", "x64_dispatch", "buckets"}
    for e in entries:
        assert required <= set(e), e["id"]
        assert e["kind"] in ("decorator", "assignment", "memoized",
                             "inline"), e["id"]
        assert e["backend"], e["id"]
        assert e["buckets"]["policy"] in ("pow2", "fixed"), e["id"]
    # the AOT prewarmer's key facts: the fused epoch pass is an int64
    # program dispatched under enable_x64, memoized per bucket
    epoch = [e for e in entries
             if e["file"] == "lighthouse_tpu/ops/epoch_kernels.py"
             and e["kind"] == "memoized"]
    assert any(e["int64_lanes"] and e["x64_dispatch"] for e in epoch)
    assert all(e["buckets"].get("memo_key") for e in epoch)
    # entries are sorted and unique by id
    ids = [e["id"] for e in entries]
    assert len(ids) == len(set(ids))
    files_lines = [(e["file"], e["line"], e["id"]) for e in entries]
    assert files_lines == sorted(files_lines)


def test_cli_manifest_mode(tmp_path):
    out = tmp_path / "manifest.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--manifest",
         "--manifest-path", str(out)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "shape manifest" in proc.stdout
    data = json.loads(out.read_text())
    assert data == json.loads(MANIFEST_PATH.read_text())


# -- CLI: exit codes, --json, perf budget -------------------------------------


def test_cli_json_output(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/probe.py": """
        def probe(compute):
            try:
                return compute()
            except Exception:
                return None
    """})
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--root", str(pkg),
         "--no-baseline", "--json"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert [d["rule"] for d in data] == ["LH902"]
    assert {"rule", "name", "file", "line", "symbol", "message",
            "new"} <= set(data[0])
    assert data[0]["new"] is True


def test_cli_json_clean_tree_is_empty_array(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/clean.py": """
        def fine():
            return 1
    """})
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--root", str(pkg),
         "--no-baseline", "--json"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 0
    assert json.loads(proc.stdout) == []


def test_cli_exit_codes_documented():
    """The documented exit-code contract (cli.py docstring) — 0 clean /
    baselined, 1 findings, 2 usage error."""
    from tools.lint import cli

    assert "0" in cli.__doc__ and "1" in cli.__doc__ and "2" in cli.__doc__
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--no-such-flag"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 2


def test_full_tree_run_stays_under_budget():
    """Engine perf gate: a COLD full-tree analyze (module-lattice memo,
    race-pass access memo AND thread-root closure memo all dropped)
    stays under the 10 s CI budget."""
    import time

    from tools.lint import dataflow, race_pass, threads

    dataflow.clear_cache()
    race_pass.clear_cache()
    threads.clear_cache()
    t0 = time.perf_counter()
    analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    cold = time.perf_counter() - t0
    assert cold < 10.0, f"cold full-tree lhlint took {cold:.1f}s"
    # warm re-run must hit the mtime-keyed memos (module lattices, race
    # accesses) and the tree-keyed closure memo (same process)
    t0 = time.perf_counter()
    analyze(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    warm = time.perf_counter() - t0
    assert warm < cold


def test_module_lattice_memo_keyed_by_mtime(tmp_path):
    """Editing a file re-analyzes it; untouched files come from the
    memo."""
    from tools.lint import dataflow

    pkg, _ = make_pkg(tmp_path, {"ops/probe.py": """
        def probe(compute):
            try:
                return compute()
            except Exception:
                return None
    """})
    assert rules_of(analyze(pkg)) == ["LH902"]
    path = pkg / "ops" / "probe.py"
    fixed = path.read_text().replace(
        "except Exception:", "except ValueError:")
    path.write_text(fixed)
    os.utime(path, (os.path.getmtime(path) + 2,) * 2)
    assert analyze(pkg) == []
    del dataflow


# -- review-round regressions -------------------------------------------------


def test_traced_closure_covers_nested_def_callees(tmp_path):
    """A helper called only from a jit target's fori_loop body traces
    with it — it must NOT be flagged as a host int64 lane (the engine's
    'can only miss, never invent' guarantee)."""
    pkg, _ = make_pkg(tmp_path, {"chain/kernels.py": """
        import jax
        import jax.numpy as jnp

        def _helper(acc):
            return acc.astype(jnp.int64)

        @jax.jit
        def kernel(cols):
            def body(i, acc):
                return _helper(acc)
            return jax.lax.fori_loop(0, 3, body, cols)
    """})
    assert sans_aot(analyze(pkg)) == []


def test_cli_manifest_refuses_unparseable_tree(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"ops/good.py": """
        import jax

        @jax.jit
        def kernel(x):
            return x
    """})
    (pkg / "ops" / "broken.py").write_text("def oops(:\n")
    out = tmp_path / "manifest.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--manifest",
         "--root", str(pkg), "--manifest-path", str(out)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 1
    assert "unparseable" in proc.stderr
    assert not out.exists()


def test_blocking_pass_owner_module_defers_to_lh101_scope(tmp_path):
    """In a LH101 owner module a 1-hop reachable fetch is LH101's alone
    (one defect, one finding); strictly deeper than 3 hops it becomes
    LH811's."""
    shallow = """
        import jax.numpy as jnp

        def _materialize(values):
            arr = jnp.asarray(values)
            return arr.item()

        class Chain:
            def bad(self, values):
                with self._import_lock:
                    return _materialize(values)
    """
    pkg, _ = make_pkg(tmp_path, {"chain/beacon_chain.py": shallow})
    assert rules_of(analyze(pkg)) == ["LH101"]

    deep = """
        import jax.numpy as jnp

        def _materialize(values):
            arr = jnp.asarray(values)
            return arr.item()

        def _l4(values):
            return _materialize(values)

        def _l3(values):
            return _l4(values)

        def _l2(values):
            return _l3(values)

        def _l1(values):
            return _l2(values)

        class Chain:
            def bad(self, values):
                with self._import_lock:
                    return _l1(values)
    """
    pkg2, _ = make_pkg(tmp_path / "deep", {"chain/beacon_chain.py": deep})
    findings = analyze(pkg2)
    assert "LH811" in rules_of(findings)
    lh811 = [f for f in findings if f.rule == "LH811"]
    assert lh811[0].symbol.startswith("_materialize")


def test_manifest_policy_not_flipped_by_metrics_buckets(tmp_path):
    """A histogram `buckets=(...)` kwarg (or a stray 'bucket' comment)
    elsewhere in the module must not stamp a fixed-shape program as
    pow2; a real pow2 pad in the dispatching caller must."""
    from tools.lint import build_context
    from tools.lint import manifest as mf

    pkg, _ = make_pkg(tmp_path, {"ops/kernels.py": """
        import jax
        import jax.numpy as jnp

        # histogram buckets live here, nothing to do with shapes
        def record(reg, s):
            reg.histogram("x_seconds", "d", buckets=(0.1, 1.0)).observe(s)

        @jax.jit
        def fixed_kernel(x):
            return x + 1

        def run_fixed(x):
            return fixed_kernel(x)

        @jax.jit
        def padded_kernel(x):
            return x + 1

        def run_padded(x, n):
            pow2 = 1 << max(n - 1, 0).bit_length()
            return padded_kernel(jnp.zeros(pow2))
    """})
    data = mf.build_manifest(build_context(pkg))
    by_target = {e["target"]: e for e in data["entries"]}
    assert by_target["fixed_kernel"]["buckets"]["policy"] == "fixed"
    assert by_target["padded_kernel"]["buckets"]["policy"] == "pow2"


def test_engine_memo_invalidated_by_cross_module_edit(tmp_path):
    """Editing module B must invalidate module A's cached lattice — the
    lattices embed resolved cross-module call edges."""
    files = {
        "api/http_api.py": """
            import jax.numpy as jnp

            from pkg.chain.helpers import fetchy

            class Api:
                def bad(self, values):
                    with self._lock:
                        return fetchy(values)
        """,
        "chain/helpers.py": """
            import jax.numpy as jnp

            def fetchy(values):
                return len(values)
        """,
    }
    pkg, _ = make_pkg(tmp_path, files)
    assert analyze(pkg) == []
    bad = pkg / "chain" / "helpers.py"
    bad.write_text(textwrap.dedent("""
        import jax.numpy as jnp

        def fetchy(values):
            arr = jnp.asarray(values)
            return arr.item()
    """))
    os.utime(bad, (os.path.getmtime(bad) + 2,) * 2)
    # api/http_api.py itself is untouched — a stale per-file memo would
    # keep its lock body's old resolved-edge view and miss this
    assert rules_of(analyze(pkg)) == ["LH811"]


# -- pass 15: cross-thread races (LH1001-1004) + the thread-root manifest ------


RACE_POOL_HEADER = """
    import threading

    class JobPool:
        def __init__(self):
            self.jobs = []
            self._lock = threading.Lock()
            threading.Thread(target=self._drain, daemon=True).start()
"""


def race_rules(findings):
    return [f for f in findings
            if f.rule in ("LH1001", "LH1002", "LH1003", "LH1004")]


def test_race_pass_flags_unlocked_shared_state(tmp_path):
    """LH1003 positive: a list mutated in place from the drain thread
    AND the main thread, no lock anywhere."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            while self.jobs:
                self.jobs.pop()

        def submit(self, job):
            self.jobs.append(job)
    """})
    findings = race_rules(analyze(pkg))
    assert rules_of(findings) == ["LH1003"]
    assert findings[0].symbol == "JobPool.jobs"
    assert "multiple thread roots" in findings[0].message


def test_race_pass_locked_twin_negative(tmp_path):
    """Compliant twin: the same shape with every compound access under
    the instance lock stays silent (and the lexical check-inside-the-
    hold also defuses LH1002)."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            with self._lock:
                while self.jobs:
                    self.jobs.pop()

        def submit(self, job):
            with self._lock:
                self.jobs.append(job)
    """})
    assert race_rules(analyze(pkg)) == []


def test_race_pass_flags_disjoint_lock_sets(tmp_path):
    """LH1001 positive: one path locks, the other mutates bare — the
    lock sets never intersect."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            while True:
                self.jobs.pop()

        def submit(self, job):
            with self._lock:
                self.jobs.append(job)
    """})
    findings = race_rules(analyze(pkg))
    assert rules_of(findings) == ["LH1001"]
    assert "disjoint lock sets" in findings[0].message


def test_race_pass_single_writer_confined_twin_negative(tmp_path):
    """The blessed confined-writer idiom: compound updates on ONE root,
    other roots touch only GIL-atomic single-key reads (len/get/[k]) —
    never a finding."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            while True:
                self.jobs.pop()

        def pending(self):
            return len(self.jobs)
    """})
    assert race_rules(analyze(pkg)) == []


def test_race_pass_cross_root_iteration_rearms(tmp_path):
    """Iterating the in-place-mutated container from ANOTHER root can
    observe torn state ("changed size during iteration") — the single-
    writer exemption does not apply."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            while True:
                self.jobs.pop()

        def snapshot(self):
            return list(self.jobs)
    """})
    assert rules_of(race_rules(analyze(pkg))) == ["LH1003"]


def test_race_pass_immutable_snapshot_twin_negative(tmp_path):
    """Atomic publish: every write is a plain store of a fresh object
    (the `self._shed_lanes = frozenset(...)` idiom) — GIL-atomic,
    never LH1001/1003."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": """
        import threading

        class LaneView:
            def __init__(self):
                self.lanes = ()
                threading.Thread(target=self._refresh, daemon=True).start()

            def _refresh(self):
                while True:
                    self.lanes = tuple(range(3))

        def reset(view: LaneView):
            view.lanes = ()
    """})
    assert race_rules(analyze(pkg)) == []


def test_race_pass_flags_check_then_act(tmp_path):
    """LH1002 positive: bare membership check, then the act under the
    lock — the resurrection window lives between them."""
    pkg, _ = make_pkg(tmp_path, {"pool/cache.py": """
        import threading

        class Cache:
            def __init__(self):
                self.entries = {}
                self._lock = threading.Lock()
                threading.Thread(target=self._sweep, daemon=True).start()

            def _sweep(self):
                while True:
                    with self._lock:
                        self.entries.clear()

            def lookup(self, key):
                if key not in self.entries:
                    with self._lock:
                        self.entries[key] = object()
                return self.entries[key]
    """})
    findings = race_rules(analyze(pkg))
    assert rules_of(findings) == ["LH1002"]
    assert "without one continuous lock hold" in findings[0].message


def test_race_pass_double_checked_locking_negative(tmp_path):
    """Compliant twin: bare check, lock, RE-check, act — the innermost
    (locked) guard decides, so the idiom the real-tree fixes use stays
    silent."""
    pkg, _ = make_pkg(tmp_path, {"pool/cache.py": """
        import threading

        class Cache:
            def __init__(self):
                self.entries = {}
                self._lock = threading.Lock()
                threading.Thread(target=self._sweep, daemon=True).start()

            def _sweep(self):
                while True:
                    with self._lock:
                        self.entries.clear()

            def lookup(self, key):
                if key not in self.entries:
                    with self._lock:
                        if key not in self.entries:
                            self.entries[key] = object()
                return self.entries.get(key)
    """})
    assert race_rules(analyze(pkg)) == []


def test_race_pass_caller_lock_inheritance(tmp_path):
    """A helper whose EVERY call site runs under the lock inherits it
    (the PeerManager._info contract) — no finding, even though the
    helper's own body mutates bare."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            with self._lock:
                self._pop_one()

        def _pop_one(self):
            if self.jobs:
                self.jobs.pop()

        def submit(self, job):
            with self._lock:
                self.jobs.append(job)
    """})
    assert race_rules(analyze(pkg)) == []


def test_race_pass_confined_to_one_root_twin_negative(tmp_path):
    """A cell only the spawned thread ever touches is not shared —
    no root pair, no finding."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            while True:
                self.jobs.pop()
                self.jobs.append(0)
    """})
    assert race_rules(analyze(pkg)) == []


def test_race_pass_suppression_requires_anchor_line(tmp_path):
    """An allow() on one of the participating access lines suppresses;
    the justification-prose policy for the real tree is asserted by
    test_real_tree_waivers_are_justified."""
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            while self.jobs:
                self.jobs.pop()

        def submit(self, job):
            self.jobs.append(job)  # lhlint: allow(LH1003) — fixture
    """})
    assert race_rules(analyze(pkg)) == []


def test_race_pass_flags_lock_inversion_across_calls(tmp_path):
    """LH1004 positive: A->B through a resolved call chain conflicting
    with a lexical B->A elsewhere — LH103 cannot see this cycle (only
    one direction is lexical), LH1004 must."""
    pkg, _ = make_pkg(tmp_path, {"net/ordering.py": """
        import threading

        LOCK_A = threading.Lock()
        LOCK_B = threading.Lock()

        def grab_b():
            with LOCK_B:
                return 1

        def forward():
            with LOCK_A:
                return grab_b()

        def backward():
            with LOCK_B:
                with LOCK_A:
                    return 2
    """})
    findings = race_rules(analyze(pkg))
    assert rules_of(findings) == ["LH1004"]
    assert "deadlock risk" in findings[0].message


def test_race_pass_consistent_lock_order_negative(tmp_path):
    """Same nesting order everywhere (even through calls): no cycle."""
    pkg, _ = make_pkg(tmp_path, {"net/ordering.py": """
        import threading

        LOCK_A = threading.Lock()
        LOCK_B = threading.Lock()

        def grab_b():
            with LOCK_B:
                return 1

        def forward():
            with LOCK_A:
                return grab_b()

        def also_forward():
            with LOCK_A:
                with LOCK_B:
                    return 2
    """})
    assert race_rules(analyze(pkg)) == []


def test_race_pass_real_tree_is_clean():
    """The PR's headline gate: zero LH1001-1004 findings on the real
    tree — every race found was FIXED (or carries an inline prose-
    justified waiver), none baselined."""
    findings = race_rules(analyze(REPO / "lighthouse_tpu",
                                  readme=REPO / "README.md"))
    assert findings == [], "race findings in the real tree:\n" + "\n".join(
        f.render() for f in findings)


# -- the thread-root manifest --------------------------------------------------

THREAD_MANIFEST_PATH = REPO / "tools" / "lint" / "thread_roots.json"


def _build_real_thread_manifest():
    from tools.lint import build_context
    from tools.lint import threads as th

    ctx = build_context(REPO / "lighthouse_tpu", readme=REPO / "README.md")
    return th.build_thread_manifest(ctx)


def test_thread_manifest_matches_tree():
    """Byte-identical sync gate, like the jit shape manifest: the
    checked-in thread_roots.json must equal a regeneration from the
    tree (`python -m tools.lint --thread-roots` refreshes it)."""
    from tools.lint import threads as th

    assert THREAD_MANIFEST_PATH.exists(), \
        "run: python -m tools.lint --thread-roots"
    assert th.render(_build_real_thread_manifest()) \
        == THREAD_MANIFEST_PATH.read_text(), (
            "tools/lint/thread_roots.json is stale — regenerate with "
            "`python -m tools.lint --thread-roots`")


def test_thread_manifest_covers_every_spawn_site():
    """Independent cross-check: a from-scratch AST sweep for spawn
    calls (threading.Thread, TaskExecutor spawn/spawn_periodic/
    spawn_blocking, run_coroutine_threadsafe) must find no site the
    manifest misses."""
    import ast as _ast

    manifest = json.loads(THREAD_MANIFEST_PATH.read_text())
    covered = {(e["file"], e["line"]) for e in manifest["roots"]}

    def dotted(expr):
        parts = []
        node = expr
        while isinstance(node, _ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if isinstance(node, _ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        return None

    missing = []
    for path in sorted((REPO / "lighthouse_tpu").rglob("*.py")):
        rel = str(path.relative_to(REPO))
        tree = _ast.parse(path.read_text())
        for node in _ast.walk(tree):
            if not isinstance(node, _ast.Call):
                continue
            text = dotted(node.func)
            if text is None:
                continue
            terminal = text.rsplit(".", 1)[-1]
            if terminal == "Thread":
                root = text.split(".", 1)[0]
                if "." in text and "threading" not in root.lower():
                    continue
            elif terminal == "run_coroutine_threadsafe":
                if not node.args:
                    continue
            elif terminal in ("spawn", "spawn_periodic", "spawn_blocking"):
                if "." not in text or not node.args or not isinstance(
                        node.args[0],
                        (_ast.Name, _ast.Attribute, _ast.Lambda)):
                    continue
            else:
                continue
            if (rel, node.lineno) not in covered:
                missing.append(f"{rel}:{node.lineno} ({terminal})")
    assert not missing, "spawn sites absent from thread_roots.json:\n" \
        + "\n".join(missing)


def test_thread_manifest_entry_shape():
    manifest = json.loads(THREAD_MANIFEST_PATH.read_text())
    assert manifest["version"] == 1
    roots = manifest["roots"]
    assert roots, "the client spawns threads; the manifest must list them"
    required = {"id", "file", "line", "kind", "spawner", "entry", "name",
                "daemon", "lifecycle"}
    ids = [r["id"] for r in roots]
    assert len(ids) == len(set(ids))
    for r in roots:
        assert required <= set(r), r.get("id")
        assert r["kind"] in ("thread", "executor", "periodic", "blocking",
                             "coroutine"), r["id"]
        assert r["lifecycle"] in ("loop", "oneshot", "periodic", "server",
                                  "pool", "coroutine"), r["id"]
        # a folded coroutine must point at a real thread root
        if "runs_on" in r:
            assert r["runs_on"] in ids, r["id"]
    files_lines = [(r["file"], r["line"], r["id"]) for r in roots]
    assert files_lines == sorted(files_lines)


def test_thread_root_discovery_folds_coroutines_into_their_loop(tmp_path):
    """A run_coroutine_threadsafe submission in the class that owns the
    loop thread attributes to THAT root (runs_on in the manifest), so
    the race pass never invents sharing inside one asyncio plane."""
    from tools.lint import build_context
    from tools.lint import threads as th

    pkg, _ = make_pkg(tmp_path, {"net/wire.py": """
        import asyncio
        import threading

        class WireNode:
            def __init__(self):
                self.loop = asyncio.new_event_loop()
                threading.Thread(target=self._run_loop,
                                 name="wire-loop", daemon=True).start()

            def _run_loop(self):
                self.loop.run_forever()

            async def _do(self):
                return 1

            def request(self):
                fut = asyncio.run_coroutine_threadsafe(self._do(),
                                                       self.loop)
                return fut.result()
    """})
    ctx = build_context(pkg)
    data = th.build_thread_manifest(ctx)
    by_kind = {r["kind"]: r for r in data["roots"]}
    assert by_kind["thread"]["name"] == "wire-loop"
    assert by_kind["thread"]["lifecycle"] == "loop"
    assert by_kind["coroutine"]["runs_on"] == by_kind["thread"]["id"]
    # and the async method's accesses attribute to the loop root
    roots_map = th.roots_by_function(ctx)
    assert th.roots_of(roots_map, "net/wire.py::WireNode._do") \
        == frozenset((by_kind["thread"]["id"],))


# -- CLI: --only / --changed report filters ------------------------------------


def test_cli_only_filters_reporting(tmp_path):
    pkg, _ = make_pkg(tmp_path, {"pool/jobs.py": RACE_POOL_HEADER + """
        def _drain(self):
            while self.jobs:
                self.jobs.pop()

        def submit(self, job):
            self.jobs.append(job)
    """})
    env = {**os.environ, "PYTHONPATH": str(REPO)}
    base = [sys.executable, "-m", "tools.lint", "--root", str(pkg),
            "--no-baseline"]
    hit = subprocess.run(base + ["--only", "LH1003"],
                         capture_output=True, text=True, cwd=REPO, env=env)
    assert hit.returncode == 1
    assert "LH1003" in hit.stderr
    # rule NAME works too
    named = subprocess.run(base + ["--only", "unlocked-shared-state"],
                           capture_output=True, text=True, cwd=REPO, env=env)
    assert named.returncode == 1
    miss = subprocess.run(base + ["--only", "LH101"],
                          capture_output=True, text=True, cwd=REPO, env=env)
    assert miss.returncode == 0, miss.stderr


def test_cli_changed_filter_accepted_on_real_tree():
    """--changed restricts reporting to files touched vs HEAD; on the
    real tree this must never FAIL (the tree is kept clean of new
    findings regardless of which files are in flight)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--changed"],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 0, proc.stderr + proc.stdout


def test_cli_thread_roots_mode(tmp_path):
    out = tmp_path / "thread_roots.json"
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--thread-roots",
         "--manifest-path", str(out)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "PYTHONPATH": str(REPO)})
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "thread-root manifest" in proc.stdout
    assert json.loads(out.read_text()) \
        == json.loads(THREAD_MANIFEST_PATH.read_text())
