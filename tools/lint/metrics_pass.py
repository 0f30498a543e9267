"""Pass 5 — metric-name discipline (LH501), absorbed from
tools/check_metrics.py (which remains as a compat shim).

Walks the package, collects every REGISTRY registration, and flags:

- dynamic metric names (f-strings/concatenation): unbounded series
  cardinality belongs in LABELS, not in the metric name;
- names not matching ``[a-z][a-z0-9_]*`` (Prometheus-safe subset);
- one name registered as two different metric kinds (counter vs gauge
  vs histogram): the registry's get-or-create would silently return
  the first kind;
- one name registered from more than one module: series ownership must
  be unambiguous (share a handle or a helper instead);
- a name under a PINNED family prefix registered outside that family's
  owner module (FAMILY_OWNERS below): cross-layer consumers must go
  through the owner's helpers, never re-register the series.

``collect()`` keeps the original (regs, errors) shape so the
check_metrics shim and its tests stay byte-compatible; ``run()`` wraps
the errors as lhlint findings.
"""

from __future__ import annotations

import ast
import pathlib
import re
import sys

KINDS = ("counter", "gauge", "histogram")
NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")

# family prefix -> sole owner module (repo-relative).  The dispatch
# pipeline's bls_pipeline_* series are recorded from the BLS backends AND
# the beacon processor; pinning the owner here keeps every registration
# funneled through ops/dispatch_pipeline's record_* helpers.
FAMILY_OWNERS = {
    "bls_pipeline_": "lighthouse_tpu/ops/dispatch_pipeline.py",
    "bls_verify_": "lighthouse_tpu/crypto/bls/api.py",
    "bls_cache_": "lighthouse_tpu/crypto/bls/api.py",
    # lanes of the key-aggregation fold by kind (PR 35): counted in
    # ops/bls_backend.aggregate_pubkeys_device through api.count_fold_lanes
    "bls_fold_": "lighthouse_tpu/crypto/bls/api.py",
    # the same-message merge's lanes and products, and every merge by
    # where it ran: counted through api.count_merge_* and
    # api.count_merged from ops/bls_backend and pool/pre_aggregation
    "bls_merge_": "lighthouse_tpu/crypto/bls/api.py",
    "pre_bls_merged_": "lighthouse_tpu/crypto/bls/api.py",
    # the offload supervisor's health/fault series (PR 4): the breaker
    # transitions are the only legitimate writer
    "bls_backend_health": "lighthouse_tpu/crypto/bls/api.py",
    "bls_supervisor_": "lighthouse_tpu/crypto/bls/api.py",
    # swallowed-error accounting funnels through the one helper
    "offload_swallowed_": "lighthouse_tpu/common/metrics.py",
    "offload_injected_": "lighthouse_tpu/ops/faults.py",
    "peer_faults_injected_": "lighthouse_tpu/ops/faults.py",
    # the sync-plane books (PR 10): each module owns its own families so
    # the LH604 zero-unaccounted-abandons invariant has a single writer
    "rpc_request": "lighthouse_tpu/network/rpc.py",
    "sync_batch": "lighthouse_tpu/network/sync.py",
    "sync_chains_": "lighthouse_tpu/network/sync.py",
    "sync_lookups_": "lighthouse_tpu/network/sync.py",
    "sync_downscores_": "lighthouse_tpu/network/sync.py",
    "backfill_": "lighthouse_tpu/network/backfill.py",
    # device epoch pass: the backend seam owns the family; epoch_device /
    # phase0_epoch / shuffle record through its helpers
    "epoch_": "lighthouse_tpu/state_transition/epoch_processing.py",
    # the stage spans of the state plane (PR 26): the tree cache, the
    # registry's element roots and the hashers all record through
    # sha256.record_merkle_stage; the slot's state root has one writer
    "merkle_stage_": "lighthouse_tpu/ops/sha256.py",
    "state_root_": "lighthouse_tpu/state_transition/slot_processing.py",
    # the stage spans of the blob plane (PR 29): the batch verifier and
    # the sliced evaluation (ops/fr.py) record through kzg's helpers; so
    # does the column plane (PR 33: crypto/das.py's cell batch and
    # chain/data_column_verification.py): kzg_cells_verified_total,
    # kzg_cell_lanes_total, kzg_interp_products_total
    "kzg_": "lighthouse_tpu/crypto/kzg.py",
    # data-column reconstruction (crypto/das.py's recovery and
    # chain/data_column_verification.reconstruct_data_columns) records
    # through kzg's helpers too
    "das_": "lighthouse_tpu/crypto/kzg.py",
    # the observatory plane (PR 11): each subsystem owns its families —
    # flight events/trips, manifest-keyed jit telemetry + the cold-start
    # headline, SLO scoring, invariant breaches, and the shared
    # bounded-structure eviction counter
    "flight_": "lighthouse_tpu/common/flight_recorder.py",
    "jit_": "lighthouse_tpu/common/device_telemetry.py",
    # the AOT program store (PR 12): store hits/misses/commits belong
    # to the store, prewarm walk outcomes to the prewarmer
    "aot_store_": "lighthouse_tpu/ops/program_store.py",
    "aot_prewarm_": "lighthouse_tpu/ops/prewarm.py",
    "time_to_first_verify": "lighthouse_tpu/common/device_telemetry.py",
    "slo_": "lighthouse_tpu/chain/slo.py",
    "invariant_": "lighthouse_tpu/common/monitors.py",
    "tracing_evicted": "lighthouse_tpu/common/metrics.py",
    # the fleet observatory (PR 13): per-node chain health owns the
    # reorg/lag/participation series, the fleet observer the fleet_*
    "reorg_": "lighthouse_tpu/chain/chain_health.py",
    "head_lag_": "lighthouse_tpu/chain/chain_health.py",
    "finality_lag_": "lighthouse_tpu/chain/chain_health.py",
    "chain_participation_": "lighthouse_tpu/chain/chain_health.py",
    "fleet_": "lighthouse_tpu/simulator.py",
    # the pull observatory (PR 16): scrape-plane accounting lives with
    # the observer's ScrapeDiscipline; promtext (the exposition parser)
    # is a consumer of the metrics plane and must register NOTHING
    "fleet_scrape_": "lighthouse_tpu/simulator.py",
    # wire-to-device ingest (PR 14): the columnar decoder owns the
    # ingest_* decode series, the pubkey plane its fold/refresh books
    "ingest_": "lighthouse_tpu/ssz/columnar.py",
    "pubkey_plane_": "lighthouse_tpu/chain/pubkey_plane.py",
    # the chaos soak (ISSUE 15): the scheduler owns the armed/disarmed
    # edge counts, the simulator the node stop/kill/restart lifecycle
    "chaos_": "lighthouse_tpu/chain/chaos.py",
    "node_lifecycle_": "lighthouse_tpu/simulator.py",
    # the process fleet (ISSUE 19): child-process lifecycle counters
    # live with the fleet, its chaos-plan edges with the fleet
    # controller (longest matching prefix wins, so these carve
    # sub-families out of the simulator-owned fleet_* space)
    "fleet_proc_": "lighthouse_tpu/fleet/fleet.py",
    "fleet_chaos_": "lighthouse_tpu/fleet/chaos.py",
    # the unified MSM plane (ISSUE 17) owns its routing gauges
    "msm_": "lighthouse_tpu/ops/msm.py",
}


def _scan_tree(rel: str, tree, regs, errors) -> None:
    """One file's REGISTRY registrations -> regs/errors (shared by the
    path-based collect() and the pre-parsed lhlint run())."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not (isinstance(func, ast.Attribute) and func.attr in KINDS):
            continue
        base = func.value
        # REGISTRY.counter(...) and reg.counter(...) alike: any
        # receiver whose name ends with "registry" (case-insensitive)
        if not (isinstance(base, ast.Name)
                and base.id.lower().endswith("registry")):
            continue
        loc = f"{rel}:{node.lineno}"
        if not node.args:
            errors.append(f"{loc}: {func.attr}() with no name argument")
            continue
        arg = node.args[0]
        if not (isinstance(arg, ast.Constant)
                and isinstance(arg.value, str)):
            errors.append(
                f"{loc}: dynamic metric name {ast.unparse(arg)!r} — "
                "move the variable part into .labels(...)")
            continue
        name = arg.value
        if not NAME_RE.match(name):
            errors.append(f"{loc}: invalid metric name {name!r} "
                          "(must match [a-z][a-z0-9_]*)")
        # exposition conformance: every registration carries a HELP
        # string (a literal or literal concatenation as the second
        # positional or help_= keyword) so # HELP lines are never empty
        help_arg = None
        if len(node.args) >= 2:
            help_arg = node.args[1]
        else:
            for kw in node.keywords:
                if kw.arg == "help_":
                    help_arg = kw.value
        if help_arg is None or (isinstance(help_arg, ast.Constant)
                                and not help_arg.value):
            errors.append(f"{loc}: {name!r} registered without a help "
                          "string — scrape output needs its # HELP line")
        regs.setdefault(name, set()).add((func.attr, rel))


def collect(package_root: pathlib.Path):
    """-> (registrations {name: set[(kind, module)]}, errors [str])."""
    regs: dict[str, set[tuple[str, str]]] = {}
    errors: list[str] = []
    package_root = pathlib.Path(package_root)
    for path in sorted(package_root.rglob("*.py")):
        rel = path.relative_to(package_root.parent)
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except SyntaxError as e:
            errors.append(f"{rel}: unparseable: {e}")
            continue
        _scan_tree(str(rel), tree, regs, errors)
    _cross_checks(regs, errors)
    return regs, errors


def _cross_checks(regs, errors) -> None:
    for name in sorted(regs):
        sites = regs[name]
        kinds = sorted({k for k, _ in sites})
        if len(kinds) > 1:
            errors.append(f"{name}: registered as multiple kinds {kinds}")
        modules = sorted({m for _, m in sites})
        if len(modules) > 1:
            errors.append(
                f"{name}: registered from multiple modules {modules}")
        # most-specific family wins: a name matching several prefixes
        # (fleet_proc_* under fleet_*) answers only to the longest one,
        # so sub-families can carve ownership out of a broader family
        matches = [p for p in FAMILY_OWNERS if name.startswith(p)]
        if matches:
            prefix = max(matches, key=len)
            owner = FAMILY_OWNERS[prefix]
            outside = [m for m in modules
                       if not m.replace("\\", "/").endswith(owner)]
            if outside:
                errors.append(
                    f"{name}: family {prefix}* is owned by {owner}, "
                    f"but registered from {outside}")


_LOC_RE = re.compile(r"^(?P<file>[^:]+\.py):(?P<line>\d+): (?P<msg>.*)$",
                     re.DOTALL)


def run(ctx) -> list:
    """lhlint pass wrapper: collect() errors -> LH501 findings."""
    from tools.lint import Finding

    # reuse the Context's already-parsed trees — no second rglob/parse
    # of the package (unparseable files are LH001 from load_package)
    regs: dict[str, set[tuple[str, str]]] = {}
    errors: list[str] = []
    for module in ctx.modules:
        _scan_tree(module.rel, module.tree, regs, errors)
    _cross_checks(regs, errors)
    findings = []
    pkg_file = ctx.pkg_root.name
    for err in errors:
        m = _LOC_RE.match(err)
        if m:
            file, line, msg = (m.group("file").replace("\\", "/"),
                               int(m.group("line")), m.group("msg"))
            symbol = re.sub(r"\d+", "", msg)[:80]
            # honor inline suppression at the flagged line
            pkg_rel = file.split("/", 1)[1] if "/" in file else file
            module = ctx.by_pkg_rel.get(pkg_rel)
            if module is not None and ctx.suppressed(
                    module, "LH501", "metric-discipline", line):
                continue
        else:
            file, line, msg = pkg_file, 0, err
            symbol = re.sub(r"\d+", "", err)[:80]
        findings.append(Finding("LH501", "metric-discipline", file, line,
                                symbol, msg))
    return findings


def main(argv: list[str]) -> int:
    """The original check_metrics CLI (kept for the compat shim)."""
    root = pathlib.Path(
        argv[1] if len(argv) > 1
        else pathlib.Path(__file__).resolve().parent.parent.parent
        / "lighthouse_tpu")
    regs, errors = collect(root)
    for err in errors:
        print(f"check_metrics: {err}", file=sys.stderr)
    if errors:
        print(f"check_metrics: FAILED ({len(errors)} problem(s), "
              f"{len(regs)} metric(s) scanned)", file=sys.stderr)
        return 1
    print(f"check_metrics: ok ({len(regs)} metric names)")
    return 0
