"""R3 — host-sync discipline.

Every host synchronization (``.item()``, ``np.asarray`` on a device array,
``block_until_ready``, implicit ``bool()`` in ``if``/``while``/``assert``)
stalls the dispatch pipeline: the host blocks until the device catches up,
and the overlap the runtime worked for (PR 2's async slot uploads, PR 6's
double-buffered schedule) is lost for that step. The repo's policy is that
syncs happen only at *declared fence points* — places where the algorithm
itself needs a host value (the router top-k that drives expert streaming,
the demand-upload fence, final output marshalling) — and nowhere else.

This rule taints names assigned from ``jnp.*``/``jax.*`` calls or calls of
jit-built callables, then flags sync operations on tainted values in any
function that is not a declared fence point. The allowlist below *is* the
policy: adding an entry is a reviewed decision with a reason, same as a
baseline entry.

Tests and benchmarks are exempt (they synchronize by design to assert on
values); traced functions are exempt (in-trace concretization is R1's
domain).
"""
from __future__ import annotations

import ast
from typing import Dict, List, Sequence, Tuple

from repro.analysis.callgraph import CallGraph, FuncInfo, call_attr_name
from repro.analysis.findings import Finding
from repro.analysis.rules import rule
from repro.analysis.rules.donation import _linear_stmts, _path
from repro.analysis.source import ModuleSource

# (path suffix, qualname prefix, reason) — declared host-sync fence points.
DECLARED_FENCES: Tuple[Tuple[str, str, str], ...] = (
    ("serving/slot_runtime.py", "SlotStreamRuntime.decode",
     "router top-k must reach the host each step to drive expert streaming"),
    ("serving/slot_runtime.py", "SlotStreamRuntime.prefill",
     "prefill routing is read on host to warm the slot cache"),
    ("core/slot_cache.py", "ExpertSlotCache.fence",
     "the demand-upload fence is the one sanctioned blocking wait"),
    ("serving/engine.py", "JaxModelServer._route_iteration",
     "token emission and router-count feedback are the serving loop's "
     "per-step fence"),
    ("launch/serve.py", "run",
     "drain's host-clock time ends when every device computation and "
     "upload has finished"),
    ("launch/train.py", "main",
     "loss/grad-norm logging at step boundaries is an accepted sync"),
    ("train/loop.py", "train_loop",
     "loss logging at step boundaries is an accepted sync"),
)

_SYNC_CALLS = {"item", "block_until_ready", "tolist"}
_NP_SYNCS = {"asarray", "array"}
_COERCIONS = {"float", "int", "bool"}


def _is_fence(m: ModuleSource, fi: FuncInfo) -> bool:
    f = fi
    while f is not None:
        for suffix, qual, _reason in DECLARED_FENCES:
            if m.relpath.endswith(suffix) and \
                    (not qual or f.qualname.startswith(qual)):
                return True
        f = f.parent
    return False


def _in_scope(m: ModuleSource) -> bool:
    p = m.relpath
    return p.startswith("src/repro") and \
        not p.startswith("src/repro/analysis")


class _Taint:
    def __init__(self, m: ModuleSource, graph: CallGraph):
        self.m = m
        self.graph = graph
        self.tainted: Dict[str, int] = {}

    def _taints(self, expr: ast.AST) -> bool:
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                if self.graph.is_jaxish(self.m, node.func):
                    return True
                if self.graph.is_jit_callable_ref(self.m, node.func):
                    return True
            elif isinstance(node, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(node, "ctx", None), ast.Load):
                p = _path(node)
                if p in self.tainted:
                    return True
        return False

    def assign(self, targets, value: ast.AST) -> None:
        if value is None:
            return
        hot = self._taints(value)
        for t in targets:
            for leaf in (t.elts if isinstance(t, (ast.Tuple, ast.List))
                         else [t]):
                p = _path(leaf)
                if p is None:
                    continue
                if hot:
                    self.tainted[p] = getattr(leaf, "lineno", 0)
                else:
                    self.tainted.pop(p, None)

    def is_tainted(self, expr: ast.AST) -> bool:
        p = _path(expr)
        return p is not None and p in self.tainted


@rule("host-sync",
      "host synchronization (.item/np.asarray/block_until_ready/implicit "
      "bool on device values) outside a declared fence point")
def check_host_sync(modules: Sequence[ModuleSource],
                    graph: CallGraph) -> List[Finding]:
    findings: List[Finding] = []
    for fi in graph.functions:
        m = fi.module
        if not _in_scope(m) or isinstance(fi.node, ast.Lambda):
            continue
        if graph.is_traced(fi) or _is_fence(m, fi):
            continue
        taint = _Taint(m, graph)
        nested = {id(c.node) for c in fi.children.values()}

        def emit(node, what):
            findings.append(Finding(
                rule="host-sync", path=m.relpath, line=node.lineno,
                col=node.col_offset,
                message=f"{what} outside a declared fence point",
                hint="keep the value on device, or add this location to "
                     "DECLARED_FENCES in repro/analysis/rules/host_sync.py "
                     "with a reason",
                qualname=fi.qualname, code=m.line_text(node.lineno)))

        def scan_expr(expr):
            if expr is None:
                return
            for node in ast.walk(expr):
                if not isinstance(node, ast.Call):
                    continue
                func = node.func
                name = call_attr_name(func)
                if name == "block_until_ready":
                    # unambiguous: jax.block_until_ready(x), arr method,
                    # or the repo's self._jax alias — always a sync
                    emit(node, "block_until_ready()")
                elif isinstance(func, ast.Attribute) and \
                        name in _SYNC_CALLS and \
                        taint.is_tainted(func.value):
                    emit(node, f".{name}() on device value "
                               f"'{_path(func.value)}'")
                elif name in _NP_SYNCS and \
                        graph.is_numpyish(m, func) and node.args and \
                        taint.is_tainted(node.args[0]):
                    emit(node, f"np.{name}() on device value "
                               f"'{_path(node.args[0])}'")
                elif isinstance(func, ast.Name) and \
                        func.id in _COERCIONS and node.args and \
                        taint.is_tainted(node.args[0]):
                    emit(node, f"{func.id}() on device value "
                               f"'{_path(node.args[0])}'")

        for stmt in _linear_stmts(fi.node.body):
            if id(stmt) in nested or isinstance(
                    stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(stmt, ast.Assign):
                scan_expr(stmt.value)
                taint.assign(stmt.targets, stmt.value)
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                scan_expr(stmt.value)
                if stmt.value is not None:
                    taint.assign([stmt.target], stmt.value)
            elif isinstance(stmt, (ast.Expr, ast.Return)):
                scan_expr(stmt.value)
            elif isinstance(stmt, (ast.If, ast.While)):
                scan_expr(stmt.test)
                if taint.is_tainted(stmt.test):
                    emit(stmt.test,
                         "implicit bool() of device value "
                         f"'{_path(stmt.test)}' in "
                         f"{'if' if isinstance(stmt, ast.If) else 'while'}")
            elif isinstance(stmt, ast.Assert):
                scan_expr(stmt.test)
                if taint.is_tainted(stmt.test):
                    emit(stmt.test, "implicit bool() of device value "
                                    f"'{_path(stmt.test)}' in assert")
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                scan_expr(stmt.iter)
    return findings
