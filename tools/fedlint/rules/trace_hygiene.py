"""FED002 trace-hygiene — no host syncs inside traced code.

A ``.item()``, ``np.asarray``, ``float()``/``int()`` coercion, or a
Python ``if`` on a tracer value inside a ``lax.scan`` body or a
jit-reachable function either breaks tracing outright or — worse —
silently baking a runtime value in as a compile-time constant and forcing
a device sync + retrace per call. The round hot path (PR 6's fused
kernels, the block-scan round bodies) must stay a single traced program.

Which functions count as traced:

* defs decorated with ``jax.jit`` / ``jax.vmap`` / ``jax.pmap`` (directly
  or via ``functools.partial(jax.jit, ...)``),
* defs whose NAME is passed to a transform in the same module
  (``jax.jit(step)``, ``lax.scan(body, ...)``, ``pl.pallas_call(kern)``),
* defs listed in ``TRACED_FUNCTION_SITES`` in ``tools/fedlint/config.py``
  — factory-returned closures the module-local inference can't see
  (the engine's round cores, gossip/compress/dp math). Nested defs
  inherit their enclosing def's traced-ness.

The Python-``if`` check is deliberately narrow to stay useful: it only
fires when the test expression calls into ``jax.numpy``/``jax.lax`` (an
``if jnp.any(mask):`` is a tracer boolification; an ``if cfg.dp:`` is
legitimate compile-time staging).
"""
from __future__ import annotations

import ast
from typing import List, Set

from .. import Finding, Rule, register
from ..astutil import ModuleInfo, chain_matches
from ..config import TRACED_FUNCTION_SITES

_TRANSFORMS = {
    "jax.jit", "jax.vmap", "jax.pmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat",
    "jax.lax.scan", "jax.lax.map", "jax.lax.while_loop", "jax.lax.cond",
    "jax.lax.fori_loop", "jax.lax.switch", "jax.lax.associative_scan",
    "jax.experimental.pallas.pallas_call",
    "jax.shard_map",
}

# attribute chains that yield static (python-int) values even on tracers;
# coercing THOSE is fine and idiomatic
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize"}

# numpy CONVERSION entry points — the ones that take an (possibly traced)
# array in. Constant constructors (np.zeros on a static shape, np.arange)
# are fine inside traced code: they bake in as constants.
_NP_CONVERTERS = {"numpy.asarray", "numpy.array", "numpy.copy",
                  "numpy.ascontiguousarray", "numpy.asanyarray"}


@register
class TraceHygiene(Rule):
    id = "FED002"
    name = "trace-hygiene"
    scope = "file"

    def check_module(self, mod: ModuleInfo) -> List[Finding]:
        traced = self._traced_defs(mod)

        def in_traced(node: ast.AST) -> bool:
            if any(d in traced for d in mod.enclosing_defs(node)):
                return True
            chain = mod.func_chain(node)
            return any(path == mod.path and chain_matches(chain, glob)
                       for path, glob in TRACED_FUNCTION_SITES)

        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and in_traced(node):
                out.extend(self._check_call(mod, node))
            elif isinstance(node, (ast.If, ast.While)) and in_traced(node):
                out.extend(self._check_branch(mod, node))
        return out

    # -- traced-def inference ---------------------------------------------

    def _traced_defs(self, mod: ModuleInfo) -> Set[ast.AST]:
        traced_names: Set[str] = set()
        defs = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
            if isinstance(node, ast.Call) and \
                    mod.full_call_name(node.func) in _TRANSFORMS:
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        traced_names.add(arg.id)

        traced: Set[ast.AST] = set()
        for name in traced_names:
            traced.update(defs.get(name, ()))
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and any(self._traced_decorator(mod, d)
                            for d in node.decorator_list):
                traced.add(node)
        return traced

    def _traced_decorator(self, mod: ModuleInfo, dec: ast.AST) -> bool:
        if mod.full_call_name(dec) in _TRANSFORMS:
            return True
        if isinstance(dec, ast.Call):
            if mod.full_call_name(dec.func) in _TRANSFORMS:
                return True
            if mod.full_call_name(dec.func) == "functools.partial" and \
                    dec.args and \
                    mod.full_call_name(dec.args[0]) in _TRANSFORMS:
                return True
        return False

    # -- violation checks --------------------------------------------------

    def _check_call(self, mod: ModuleInfo, node: ast.Call) -> List[Finding]:
        out = []
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr == "item":
            out.append(self.finding(
                mod.path, node.lineno,
                ".item() in traced code forces a device sync (or a "
                "ConcretizationError); keep the value on device or move "
                "the readout outside the jitted region"))
        full = mod.full_call_name(func)
        if full in _NP_CONVERTERS:
            out.append(self.finding(
                mod.path, node.lineno,
                f"{full} in traced code round-trips through host numpy; "
                f"use jax.numpy (or run this on materialized outputs, "
                f"outside the traced function)"))
        if isinstance(func, ast.Name) and func.id in ("float", "int",
                                                      "bool") \
                and len(node.args) == 1 \
                and not self._static_arg(node.args[0]) \
                and not self._static_argname(mod, node):
            out.append(self.finding(
                mod.path, node.lineno,
                f"{func.id}() on a (potential) tracer concretizes it; "
                f"use .astype(...) for dtype casts or hoist the host "
                f"coercion out of the traced function"))
        return out

    def _check_branch(self, mod: ModuleInfo, node) -> List[Finding]:
        kind = "if" if isinstance(node, ast.If) else "while"
        for sub in ast.walk(node.test):
            if isinstance(sub, ast.Call):
                full = mod.full_call_name(sub.func)
                if full.startswith(("jax.numpy.", "jax.lax.")):
                    return [self.finding(
                        mod.path, node.lineno,
                        f"python `{kind}` on a {full} result boolifies a "
                        f"tracer; use jnp.where / lax.cond / lax.select "
                        f"instead")]
        return []

    def _static_argname(self, mod: ModuleInfo, node: ast.Call) -> bool:
        """float(b1) is fine when ``b1`` is one of the enclosing jitted
        def's ``static_argnames`` — a python value at trace time."""
        arg = node.args[0]
        names = {n.id for n in ast.walk(arg)
                 if isinstance(n, ast.Name)}
        if not names:
            return False
        for d in mod.enclosing_defs(node):
            if isinstance(d, ast.Lambda):
                continue
            for dec in d.decorator_list:
                if not (isinstance(dec, ast.Call) and
                        mod.full_call_name(dec.func) ==
                        "functools.partial" and dec.args and
                        mod.full_call_name(dec.args[0]) in _TRANSFORMS):
                    continue
                from ..astutil import const_str, keyword_arg
                sa = keyword_arg(dec, "static_argnames")
                if sa is None:
                    continue
                statics = set()
                if isinstance(sa, (ast.Tuple, ast.List)):
                    statics = {s for e in sa.elts
                               if (s := const_str(e)) is not None}
                elif (s := const_str(sa)) is not None:
                    statics = {s}
                # any static argname in the expression marks it as
                # config math (the other names are then shape-derived
                # locals in practice), not a tracer coercion
                if names & statics:
                    return True
        return False

    @staticmethod
    def _static_arg(arg: ast.AST) -> bool:
        """True for expressions that are static under tracing: literals,
        .shape/.ndim/... chains, len(...), and arithmetic thereof."""
        if isinstance(arg, ast.Constant):
            return True
        for sub in ast.walk(arg):
            if isinstance(sub, ast.Attribute) and sub.attr in _STATIC_ATTRS:
                return True
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Name) and \
                    sub.func.id == "len":
                return True
        return False
