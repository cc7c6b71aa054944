"""Pass ``env-tiers``: static jit-arg env tiers resolve OUTSIDE traced
functions.

The BVH node-format knobs (``TRC_TLAS``/``TRC_TLAS_LEAF``/
``TRC_TLAS_BLOCK``/``TRC_BVH_QUANT``/``TRC_BVH_BUILDER``/
``TRC_BVH_WIDE``) select between distinct compiled programs: their
values are threaded into jit identities as STATIC arguments, renderer
cache keys, and geometry-build memo keys. Reading one of their tier
helpers from inside a traced function would bake the first trace's
environment into the executable — the toggle-mid-process staleness bug
the resolved-outside contract (integrator.resolve_bvh_config) exists
to prevent, and exactly what lets one process hold every variant.

This pass finds the traced functions with the same static analysis as
``jit-purity`` (decorated defs, defs passed to ``jit``/``pallas_call``/
``shard_map``, factory-returned closures) and flags any call to a
declared tier-reader helper inside one. Like ``jit-purity``, the scan
is BODY-LOCAL — a tier read buried one plain-function call below a
traced def is not reachable statically, so the renderer factories
additionally thread the resolved values as explicit (static) arguments
all the way down (``use_tlas``/``quant``/``builder``/``wide``
parameters); the pass catches the direct regressions, the threading
convention covers the rest. ``pallas_enabled``, a *dispatch* tier read
per call by documented design, is not in the set — it selects a code
path, not a compiled program's static configuration.
"""

from __future__ import annotations

import ast

from tpu_render_cluster.lint.core import Finding, LintContext, SourceModule
from tpu_render_cluster.lint.jit_purity import _traced_defs

PASS_ID = "env-tiers"

# The static-jit-arg tier readers: functions whose return value must be
# threaded INTO a traced function, never read from within one.
TIER_READERS = {
    "tlas_enabled",
    "tlas_leaf_size",
    "tlas_block_r",
    "bvh_quant_mode",
    "bvh_builder",
    "bvh_wide",
    "resolve_bvh_config",
}


def _callee_name(node: ast.expr) -> str | None:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


class _TierCallScanner(ast.NodeVisitor):
    def __init__(self, module: SourceModule, qualname: str):
        self.module = module
        self.qualname = qualname
        self.findings: list[Finding] = []

    def visit_Call(self, node: ast.Call):  # noqa: N802
        name = _callee_name(node.func)
        if name in TIER_READERS:
            self.findings.append(
                Finding(
                    PASS_ID,
                    self.module.relpath,
                    node.lineno,
                    f"traced function {self.qualname!r} reads the static "
                    f"jit-arg env tier via {name}() — the value would be "
                    "baked at first trace; resolve it in the untraced "
                    "renderer factory (integrator.resolve_bvh_config) and "
                    "thread it in as a static argument",
                )
            )
        self.generic_visit(node)


def run(ctx: LintContext) -> list[Finding]:
    # Package-wide def index for cross-module factory resolution (the
    # same shape as jit_purity.run — both passes must agree on which
    # defs are traced).
    package_defs: dict[str, list[ast.AST]] = {}
    def_module: dict[int, SourceModule] = {}
    for module in ctx.modules:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.FunctionDef):
                package_defs.setdefault(node.name, []).append(node)
                def_module[id(node)] = module

    findings: list[Finding] = []
    seen: set[int] = set()
    for module in ctx.modules:
        for node in _traced_defs(module, package_defs):
            if id(node) in seen:
                continue
            seen.add(id(node))
            owner = def_module.get(id(node), module)
            scanner = _TierCallScanner(owner, node.name)
            for child in ast.iter_child_nodes(node):
                scanner.visit(child)
            findings.extend(scanner.findings)
    return findings
