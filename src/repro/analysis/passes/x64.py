"""Pass 2 — x64 discipline.

Two sub-checks, both guarding the float32-exactness story (TPUs have no
float64, so the Pallas engines must be *correct* in float32, not quietly
promoted):

  * **kernel f64**: inside ``repro/kernels/`` any ``float64`` dtype
    spelling (``jnp.float64``, ``astype("float64")``) or ``enable_x64``
    escape (context manager, ``jax.config.update("jax_enable_x64", ...)``)
    is flagged — Mosaic cannot lower it, and interpret-mode tests would
    silently diverge from real-TPU behavior;
  * **un-rebased absolute time**: anywhere, casting a variable that *names
    itself* an absolute-time column (``times``, ``t_abs``, ...) straight to
    float32 is flagged unless the enclosing function also rebases (calls a
    ``*rebase*`` helper). Multi-week absolute clocks do not fit float32 —
    difference or rebase in float64 before the cast (the Pallas engine
    sends the kernel float64-differenced gaps, ``simulator._gap_columns``).
"""
from __future__ import annotations

import ast
import re
from typing import Iterator, Optional

from ..framework import (Finding, LintConfig, Module, Rule, dotted_name,
                         terminal_name)

_F32_CASTERS = {"np.float32", "jnp.float32", "numpy.float32",
                "jax.numpy.float32"}
_ARRAY_CTORS = {"np.asarray", "np.array", "jnp.asarray", "jnp.array",
                "numpy.asarray", "numpy.array", "jax.numpy.asarray",
                "jax.numpy.array", "np.ascontiguousarray",
                "numpy.ascontiguousarray"}


def _is_f32_dtype(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return node.value == "float32"
    return terminal_name(node) == "float32"


def _is_f64_dtype(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return node.value == "float64"
    return terminal_name(node) == "float64"


class X64Discipline(Rule):
    name = "x64-discipline"
    description = ("float64/enable_x64 in Pallas kernels; float32 casts of "
                   "un-rebased absolute-time columns")

    def check(self, module: Module, config: LintConfig) -> Iterator[Finding]:
        time_re = re.compile(config.time_name_pattern)
        in_kernels = module.in_scope(config.kernel_scopes)
        # map every node to its nearest enclosing function (for the rebase
        # exemption) in one pre-pass
        enclosing = {}
        rebasing_funcs = set()

        def tag(node: ast.AST, func: Optional[ast.AST]) -> None:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                func = node
            enclosing[id(node)] = func
            for child in ast.iter_child_nodes(node):
                tag(child, func)

        tag(module.tree, None)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                tn = terminal_name(node.func)
                if tn is not None and "rebase" in tn:
                    func = enclosing.get(id(node))
                    if func is not None:
                        rebasing_funcs.add(id(func))

        for node in ast.walk(module.tree):
            if in_kernels:
                yield from self._check_kernel_f64(module, node)
            yield from self._check_unrebased_cast(module, node, time_re,
                                                  enclosing, rebasing_funcs)

    # -- kernels: no f64, no enable_x64 -------------------------------------

    def _check_kernel_f64(self, module: Module,
                          node: ast.AST) -> Iterator[Finding]:
        if terminal_name(node) == "float64":
            yield self.finding(
                module, node,
                "float64 dtype in a Pallas kernel module: TPUs have no f64 "
                "and Mosaic cannot lower it; carry float32 + the rebased "
                "decision layer instead")
        elif isinstance(node, ast.Call):
            name = dotted_name(node.func) or ""
            if name.endswith("enable_x64"):
                yield self.finding(
                    module, node,
                    "enable_x64 escape inside a kernel module: interpret-"
                    "mode tests would silently diverge from real TPUs")
            elif name.endswith("config.update") and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    node.args[0].value == "jax_enable_x64":
                yield self.finding(
                    module, node,
                    "jax_enable_x64 toggle inside a kernel module")
        elif isinstance(node, ast.Constant) and node.value == "float64":
            yield self.finding(
                module, node, "float64 dtype string in a Pallas kernel "
                              "module (TPUs have no f64)")
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                if alias.name.endswith("enable_x64"):
                    yield self.finding(
                        module, node,
                        "enable_x64 import inside a kernel module")

    # -- float32 cast of absolute time --------------------------------------

    def _check_unrebased_cast(self, module: Module, node: ast.AST,
                              time_re, enclosing: dict,
                              rebasing_funcs: set) -> Iterator[Finding]:
        if not isinstance(node, ast.Call):
            return
        subject = self._cast_subject(node)
        if subject is None:
            return
        tn = terminal_name(subject)
        if tn is None or not time_re.search(tn):
            return
        func = enclosing.get(id(node))
        if func is not None and id(func) in rebasing_funcs:
            return       # the function rebases; trust its data flow
        yield self.finding(
            module, node,
            f"float32 cast of absolute-time column {tn!r} without per-chunk "
            "rebasing: multi-week clocks lose sub-minute IAT structure in "
            "float32 (difference the clock in float64 first, as "
            "simulator._gap_columns does)")

    @staticmethod
    def _cast_subject(node: ast.Call) -> Optional[ast.AST]:
        """The value being cast to float32, if this call is such a cast."""
        func = node.func
        # X.astype(float32)
        if isinstance(func, ast.Attribute) and func.attr == "astype":
            dtype = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords if kw.arg == "dtype"), None)
            if dtype is not None and _is_f32_dtype(dtype):
                return func.value
            return None
        name = dotted_name(func)
        if name in _F32_CASTERS and node.args:
            return node.args[0]
        if name in _ARRAY_CTORS and node.args:
            dtype = node.args[1] if len(node.args) > 1 else next(
                (kw.value for kw in node.keywords if kw.arg == "dtype"), None)
            if dtype is not None and _is_f32_dtype(dtype):
                return node.args[0]
        return None
