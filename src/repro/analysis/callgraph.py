"""Lightweight per-module call graph: which functions run under trace?

The host-sync rule needs to know whether a function's body executes
inside a ``jit`` / ``lax.scan`` / ``shard_map`` trace, because a host
sync (``.item()``, ``np.asarray``, ``float()``) is only a hazard there.
Full interprocedural analysis is out of scope; this module computes a
deliberately simple over-/under-approximation that is accurate for this
repo's idioms:

* **roots** — functions decorated with ``jit`` (bare, ``jax.jit``, or
  through ``functools.partial(jax.jit, ...)``), and functions whose
  *name* is passed to a known tracing higher-order function
  (``lax.scan``, ``lax.cond``, ``shard_map``, ``vmap``, ``grad``, …)
  or wrapped by a ``jax.jit(...)`` call expression.
* **edges** — a call (or function-reference argument) to a bare name
  that matches another function defined in the same module. Matching is
  by name, which in practice also resolves factory closures (a caller
  that does ``step = make_engine(...)`` then calls ``step(...)`` lands
  on the factory's inner ``def step``).
* **nesting** — a function lexically nested inside a traced function is
  traced (its body is built while the parent traces).

The result is the set of FunctionDef nodes considered traced, with a
human-readable reason per node for the finding message.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.engine import dotted_name, iter_functions, own_nodes

#: decorators that put the decorated function under trace
_JIT_NAMES = {"jit", "jax.jit"}
_PARTIAL_NAMES = {"partial", "functools.partial"}

#: call targets whose function-valued arguments run under trace
_TRACING_HOFS = {
    "jax.jit", "jit",
    "jax.lax.scan", "lax.scan",
    "jax.lax.cond", "lax.cond",
    "jax.lax.switch", "lax.switch",
    "jax.lax.while_loop", "lax.while_loop",
    "jax.lax.fori_loop", "lax.fori_loop",
    "jax.lax.map", "lax.map",
    "jax.lax.associative_scan", "lax.associative_scan",
    "jax.lax.custom_root", "lax.custom_root",
    "shard_map", "jax.shard_map",
    "jax.vmap", "vmap", "jax.pmap", "pmap",
    "jax.grad", "grad", "jax.value_and_grad", "value_and_grad",
    "jax.checkpoint", "jax.remat", "checkpoint", "remat",
    "jax.custom_jvp", "jax.custom_vjp",
    "pl.pallas_call", "pallas_call",
}


def _is_jit_decorator(dec: ast.AST) -> bool:
    name = dotted_name(dec)
    if name in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        cname = dotted_name(dec.func)
        if cname in _JIT_NAMES:
            return True
        if cname in _PARTIAL_NAMES and dec.args:
            return dotted_name(dec.args[0]) in _JIT_NAMES
    return False


class TracedGraph:
    """Traced-reachability over one module's function defs."""

    def __init__(self, tree: ast.Module):
        self.functions: List[ast.AST] = list(iter_functions(tree))
        self.by_name: Dict[str, List[ast.AST]] = {}
        for fn in self.functions:
            self.by_name.setdefault(fn.name, []).append(fn)

        self._parent: Dict[ast.AST, ast.AST] = {}
        for fn in self.functions:
            for child in own_nodes(fn):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    self._parent[child] = fn

        self.reason: Dict[ast.AST, str] = {}
        self._mark_roots(tree)
        self._propagate()

    # -- construction -----------------------------------------------------

    def _mark(self, fn: ast.AST, reason: str) -> None:
        if fn not in self.reason:
            self.reason[fn] = reason

    def _mark_roots(self, tree: ast.Module) -> None:
        for fn in self.functions:
            for dec in getattr(fn, "decorator_list", []):
                if _is_jit_decorator(dec):
                    self._mark(fn, "decorated with jit")
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            callee = dotted_name(node.func)
            if callee not in _TRACING_HOFS:
                continue
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                name = dotted_name(arg)
                if name in self.by_name:
                    for fn in self.by_name[name]:
                        self._mark(fn, f"passed to {callee}")

    def _calls_out(self, fn: ast.AST) -> Set[str]:
        """Names this function calls or passes onward (own scope only)."""
        out: Set[str] = set()
        for node in own_nodes(fn):
            if isinstance(node, ast.Call):
                callee = dotted_name(node.func)
                if callee:
                    out.add(callee)
                for arg in (list(node.args)
                            + [kw.value for kw in node.keywords]):
                    ref = dotted_name(arg)
                    if ref:
                        out.add(ref)
        return out

    def _propagate(self) -> None:
        changed = True
        while changed:
            changed = False
            for fn in self.functions:
                if fn in self.reason:
                    continue
                parent = self._parent.get(fn)
                if parent is not None and parent in self.reason:
                    self._mark(fn, f"nested in traced '{parent.name}'")
                    changed = True
            for fn in list(self.reason):
                for callee in self._calls_out(fn):
                    for target in self.by_name.get(callee, []):
                        if target not in self.reason:
                            self._mark(target,
                                       f"called from traced '{fn.name}'")
                            changed = True

    # -- queries ----------------------------------------------------------

    def is_traced(self, fn: ast.AST) -> bool:
        return fn in self.reason

    def why(self, fn: ast.AST) -> Optional[str]:
        return self.reason.get(fn)

    def traced_functions(self) -> List[Tuple[ast.AST, str]]:
        return [(fn, self.reason[fn]) for fn in self.functions
                if fn in self.reason]
