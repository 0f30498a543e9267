"""Pass 2 — one-fetch discipline (LH201).

The PR 2 overlap invariant: a verify batch pays exactly ONE device→host
fetch, at the commit point, after every chunk has been dispatched.  Any
extra materialization (``jax.device_get``, ``np.asarray`` on a device
value, ``.block_until_ready()``, ``.item()``) inside the pipeline
modules re-serializes host and device and silently eats the overlap.

This pass restricts fetch primitives in the three pipeline modules to
an allowlist of designated commit/fetch functions.  The allowlist is by
function name (terminal qualname component), so a refactor that MOVES a
fetch into a new helper trips the gate and forces a conscious decision.
"""

from __future__ import annotations

import ast

from tools.lint import Context, Finding
from tools.lint.callgraph import dotted_name

TARGET_MODULES = (
    "ops/dispatch_pipeline.py",
    "ops/bls_backend.py",
    "parallel/bls_sharded.py",
)

FETCH_DOTTED = {"jax.device_get", "jax.block_until_ready",
                "np.asarray", "numpy.asarray"}
FETCH_METHODS = {"block_until_ready", "item"}

# designated commit points: the functions whose JOB is the one fetch
# (or a synchronous convenience wrapper documented as such)
ALLOWED_FUNCTIONS = {
    "commit",                    # AsyncVerdict.commit — THE commit point
    "_verify_sets_pipeline",     # batch fetch + final exp
    "_final_exp_is_one",         # device final-exp readback
    "aggregate_pubkeys_device",  # one segment-sum fetch per batch
    "_merge_same_message",       # one same-message merge fetch per batch
    "batch_subgroup_check_g1",   # synchronous verdict wrappers
    "batch_subgroup_check_g2",
    "multi_pairing_sharded",     # mesh path: one combined fetch
}


def _is_fetch(call: ast.Call) -> str | None:
    dotted = dotted_name(call.func)
    if dotted is None:
        return None
    if dotted in FETCH_DOTTED:
        return dotted
    if "." in dotted and dotted.rsplit(".", 1)[-1] in FETCH_METHODS:
        return dotted
    return None


def run(ctx: Context) -> list[Finding]:
    findings: list[Finding] = []
    for pkg_rel in TARGET_MODULES:
        module = ctx.by_pkg_rel.get(pkg_rel)
        if module is None:
            continue
        findings.extend(_scan_module(ctx, module))
    return findings


def _scan_module(ctx: Context, module) -> list[Finding]:
    findings: list[Finding] = []

    def visit(node, stack: list[str]):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if child.name in ALLOWED_FUNCTIONS:
                    continue  # designated commit point: fetches allowed
                visit(child, stack + [child.name])
                continue
            if isinstance(child, ast.ClassDef):
                visit(child, stack + [child.name])
                continue
            if isinstance(child, ast.Call):
                fetch = _is_fetch(child)
                if fetch is not None:
                    qual = ".".join(stack) or "<module>"
                    if not ctx.suppressed(module, "LH201", "stray-fetch",
                                          child.lineno):
                        findings.append(Finding(
                            "LH201", "stray-fetch", module.rel,
                            child.lineno,
                            f"{qual}:{fetch.rsplit('.', 1)[-1]}",
                            f"device->host materialization `{fetch}` "
                            f"outside the designated commit points "
                            f"(allowed: {', '.join(sorted(ALLOWED_FUNCTIONS))})"))
            visit(child, stack)

    visit(module.tree, [])
    return findings
