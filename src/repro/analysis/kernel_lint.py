"""Pallas kernel lint: the universal-schedule rules, checked in source.

The batch-invariant kernel contract (paper §2.3, ``gemm_batch_invariant``):
reduction geometry must be pinned by *literals*, never derived from input
shapes.  For every ``pl.pallas_call`` in scope this pass checks:

* ``grid-reduction-extent`` — a grid axis whose index the ``out_specs``
  index_map ignores is a *reduction* axis (each step folds into the same
  output tile).  Its extent must be literal-derived: an int literal, a
  module-level constant, or ``X // literal`` chains (fixed chunk size ⇒
  the walk order and tree shape are pinned; only the trip count tracks the
  problem).  A function-parameter or shape-derived extent means the
  reduction tree can change with the workload.
* ``adaptive-block-size``     — ``min``/``max`` clamps mixing a block size
  with a shape component (``bm = min(bm, M)``).  Harmless when the axis is
  pure data parallelism, fatal when it feeds a reduction — so it is always
  reported and the harmless cases carry allowlist justifications.
* ``block-spec-shape-derived`` — a ``BlockSpec`` dimension that is neither
  literal-derived nor a whole input axis: partial shape-adaptive tiling.
* ``accum-dtype``             — a VMEM scratch accumulator or a
  ``preferred_element_type`` narrower than f32 inside a kernel body: the
  contract's combine dtype is f32.  A scratch buffer the kernel only ever
  fills by DMA (``make_async_copy`` destination, never stored to) is a
  staging copy of its input, not an accumulator, and keeps the input dtype.
* ``shape-branch-in-kernel``  — a Python ``if`` inside a kernel body: it
  branches at *trace time* on static arguments, so the compiled reduction
  structure depends on how the kernel was parameterized.  Runtime
  predication must use ``pl.when``.

Grids and specs are read from the ``pallas_call`` keywords or from its
``grid_spec=`` (``PrefetchScalarGridSpec``); a ``None`` block dim is a
squeezed unit dim, i.e. the literal 1.

Files or functions annotated ``# det: fastpath`` are exempt: they
implement the *licensed* nondeterministic fast path (split-K, kv-split
flash-decode) whose schedules the taint pass proves unreachable from the
commit side.
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, List, Optional

from repro.analysis.report import Finding

FASTPATH_RE = re.compile(r"^\s*#\s*det:\s*fastpath\s*$")
_SAFE_ACC_TAILS = {"float32", "f32"}


def _tail(node: ast.expr) -> Optional[str]:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return None


class _Module:
    """Per-file context: module constants, function defs, kernel bodies."""

    def __init__(self, path: Path, rel: str):
        self.rel = rel
        self.src = path.read_text()
        self.tree = ast.parse(self.src, filename=str(path))
        self.lines = self.src.splitlines()
        # file-level exemption: the annotation sits in the module header,
        # before the first def/class (below that it marks one function)
        first_def = min(
            (n.lineno for n in self.tree.body
             if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef))),
            default=len(self.lines) + 1,
        )
        self.file_fastpath = any(
            FASTPATH_RE.match(ln) for ln in self.lines[: first_def - 1]
        )
        self.module_assigns: Dict[str, ast.expr] = {}
        self.functions: Dict[str, ast.FunctionDef] = {}
        for node in self.tree.body:
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.module_assigns[tgt.id] = node.value
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node

    def fn_fastpath(self, fn: ast.FunctionDef) -> bool:
        start = min([fn.lineno] + [d.lineno for d in fn.decorator_list])
        prev = start - 2  # 0-indexed line above the def/decorators
        return 0 <= prev < len(self.lines) and bool(FASTPATH_RE.match(self.lines[prev]))


class _FnCtx:
    """Flow-insensitive view of one function containing pallas_call(s)."""

    def __init__(self, mod: _Module, fn: ast.FunctionDef):
        self.mod = mod
        self.fn = fn
        self.params = {
            a.arg for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
        }
        self.assigns: Dict[str, ast.expr] = {}
        self.shape_names: set = set()  # names bound to input-shape components
        self.adaptive_names: set = set()  # names already flagged adaptive
        for node in ast.walk(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and node is not fn:
                continue
            if isinstance(node, ast.Assign):
                val = node.value
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.assigns[tgt.id] = val
                    elif isinstance(tgt, ast.Tuple) and self._is_shape_expr(val):
                        for el in tgt.elts:
                            if isinstance(el, ast.Name):
                                self.shape_names.add(el.id)
                    elif (
                        isinstance(tgt, ast.Tuple)
                        and isinstance(val, ast.Tuple)
                        and len(tgt.elts) == len(val.elts)
                    ):
                        for el, v in zip(tgt.elts, val.elts):
                            if isinstance(el, ast.Name):
                                self.assigns[el.id] = v
                # M = x.shape[0] style
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name) and self._is_shape_expr(val):
                        self.shape_names.add(tgt.id)

    @staticmethod
    def _is_shape_expr(node: ast.expr) -> bool:
        # x.shape / x.shape[i] / x.shape[1], k.shape[2] ...
        if isinstance(node, ast.Attribute) and node.attr == "shape":
            return True
        if isinstance(node, ast.Subscript):
            return _FnCtx._is_shape_expr(node.value)
        if isinstance(node, ast.Tuple):
            return any(_FnCtx._is_shape_expr(e) for e in node.elts)
        return False

    def literal_derived(self, node: ast.expr, depth: int = 0) -> bool:
        """True if the reduction-relevant part of `node` is pinned by literals.

        ``X // bk`` with literal-derived ``bk`` counts: the chunk size (the
        reduction tree's shape) is fixed; only the trip count follows X.
        """
        if depth > 8:
            return False
        if isinstance(node, ast.Constant):
            # int literal, or None: a squeezed unit block dim
            return node.value is None or isinstance(node.value, int)
        if isinstance(node, ast.Name):
            if node.id in self.shape_names or node.id in self.adaptive_names:
                return False
            if node.id in self.assigns:
                return self.literal_derived(self.assigns[node.id], depth + 1)
            if node.id in self.mod.module_assigns:
                return self.literal_derived(self.mod.module_assigns[node.id], depth + 1)
            return False  # parameter or import: not provably literal
        if isinstance(node, ast.BinOp):
            if isinstance(node.op, ast.FloorDiv):
                return self.literal_derived(node.right, depth + 1)
            if isinstance(node.op, (ast.Mult, ast.Add, ast.Sub)):
                return self.literal_derived(node.left, depth + 1) and self.literal_derived(
                    node.right, depth + 1
                )
        if isinstance(node, ast.UnaryOp):
            return self.literal_derived(node.operand, depth + 1)
        return False

    def is_whole_axis(self, node: ast.expr) -> bool:
        return (
            isinstance(node, ast.Name) and node.id in self.shape_names
        ) or self._is_shape_expr(node)


def _index_map_used_params(spec_call: ast.Call) -> Optional[set]:
    """Grid-parameter indices an index_map lambda actually uses, or None."""
    lam = None
    if len(spec_call.args) >= 2 and isinstance(spec_call.args[1], ast.Lambda):
        lam = spec_call.args[1]
    for kw in spec_call.keywords:
        if kw.arg == "index_map" and isinstance(kw.value, ast.Lambda):
            lam = kw.value
    if lam is None:
        return None
    names = [a.arg for a in lam.args.args]
    used = {n.id for n in ast.walk(lam.body) if isinstance(n, ast.Name)}
    return {i for i, n in enumerate(names) if n in used}


def _resolve_kernel_fn(mod: _Module, entry: ast.expr) -> Optional[ast.FunctionDef]:
    """The kernel function behind pallas_call's first argument."""
    if isinstance(entry, ast.Call) and _tail(entry.func) == "partial" and entry.args:
        entry = entry.args[0]
    if isinstance(entry, ast.Name):
        return mod.functions.get(entry.id)
    return None


def _root_name(node: ast.expr) -> Optional[str]:
    """``buf`` for ``buf``, ``buf[...]``, ``buf.at[...]`` and the like."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _ref_root(node: ast.expr) -> Optional[str]:
    """``buf`` for a ref expression ``buf`` or ``buf.at[...]``; ``None``
    for anything else (``buf[...]`` as a value is a load, not the ref)."""
    while (
        isinstance(node, ast.Subscript)
        and isinstance(node.value, ast.Attribute)
        and node.value.attr == "at"
    ):
        node = node.value.value
    return node.id if isinstance(node, ast.Name) else None


def _dma_staging(mod: _Module, fn: ast.FunctionDef, name: str,
                 depth: int = 0) -> tuple:
    """``(clean, filled)`` for ref ``name`` inside ``fn``: ``clean`` means
    no subscript store ever targets it (following it into module helpers
    it is passed to), ``filled`` that it is some ``make_async_copy``
    destination.  A scratch buffer that is clean and filled only ever
    holds DMA'd input data."""
    filled = False
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.Subscript)
            and isinstance(node.ctx, ast.Store)
            and _root_name(node) == name
        ):
            return False, filled
        if not isinstance(node, ast.Call):
            continue
        if _tail(node.func) == "make_async_copy":
            if len(node.args) >= 2 and _ref_root(node.args[1]) == name:
                filled = True
            continue
        passed = [
            (i, None) for i, a in enumerate(node.args) if _ref_root(a) == name
        ] + [
            (None, kw.arg) for kw in node.keywords
            if _ref_root(kw.value) == name
        ]
        if not passed:
            continue
        callee = mod.functions.get(_tail(node.func) or "")
        if callee is None or depth >= 4:
            return False, filled  # handed to code we cannot see: unproven
        params = [a.arg for a in callee.args.posonlyargs + callee.args.args]
        for i, kwname in passed:
            pname = kwname if kwname is not None else (
                params[i] if i < len(params) else None
            )
            if pname is None:
                return False, filled
            clean, sub_filled = _dma_staging(mod, callee, pname, depth + 1)
            if not clean:
                return False, filled
            filled |= sub_filled
    return True, filled


def _lint_file(path: Path, rel: str) -> list[Finding]:
    findings: list[Finding] = []
    try:
        mod = _Module(path, rel)
    except SyntaxError as e:
        return [
            Finding(
                pass_name="kernel_lint",
                rule="unparseable",
                where=rel,
                message=f"cannot parse: {e}",
            )
        ]
    if "pallas_call" not in mod.src:
        return []
    if mod.file_fastpath:
        return []

    linted_kernels: set = set()

    for fname, fn in mod.functions.items():
        calls = [
            n
            for n in ast.walk(fn)
            if isinstance(n, ast.Call) and _tail(n.func) == "pallas_call"
        ]
        if not calls:
            continue
        if mod.fn_fastpath(fn):
            continue
        ctx = _FnCtx(mod, fn)
        where = f"{rel}::{fname}"

        def emit(rule: str, lineno: int, message: str) -> None:
            findings.append(
                Finding(
                    pass_name="kernel_lint",
                    rule=rule,
                    where=where,
                    message=f"line {lineno}: {message}",
                )
            )

        # adaptive block sizes anywhere in the wrapper
        for name, val in ctx.assigns.items():
            if (
                isinstance(val, ast.Call)
                and _tail(val.func) in ("min", "max")
                and any(
                    isinstance(a, ast.Name) and a.id in ctx.shape_names
                    for a in val.args
                )
            ):
                ctx.adaptive_names.add(name)
                emit(
                    "adaptive-block-size",
                    val.lineno,
                    f"'{name} = {_tail(val.func)}(...)' clamps a block size "
                    "with an input-shape component: tile geometry adapts to "
                    "the workload (fatal if the axis feeds a reduction)",
                )

        for call in calls:
            kwargs = {kw.arg: kw.value for kw in call.keywords if kw.arg}
            gspec = kwargs.get("grid_spec")
            if isinstance(gspec, ast.Call):
                for kw in gspec.keywords:
                    if kw.arg:
                        kwargs.setdefault(kw.arg, kw.value)
            kernel = _resolve_kernel_fn(mod, call.args[0] if call.args else None)
            grid = kwargs.get("grid")
            out_specs = kwargs.get("out_specs")
            in_specs = kwargs.get("in_specs")

            # reduction grid axes: ignored by the out_specs index_map
            if grid is not None and isinstance(out_specs, ast.Call):
                used = _index_map_used_params(out_specs)
                dims = (
                    list(grid.elts) if isinstance(grid, ast.Tuple) else [grid]
                )
                if used is not None:
                    for i, dim in enumerate(dims):
                        if i in used:
                            continue
                        if not ctx.literal_derived(dim):
                            emit(
                                "grid-reduction-extent",
                                dim.lineno,
                                f"grid axis {i} is a reduction axis (the "
                                "out_specs index_map ignores it) but its "
                                "extent is not literal-derived: the "
                                "reduction tree shape follows the workload",
                            )

            # BlockSpec block dims: literal-derived or whole-axis
            specs: List[ast.Call] = []
            for spec_src in (in_specs, out_specs):
                if isinstance(spec_src, ast.Call) and _tail(spec_src.func) == "BlockSpec":
                    specs.append(spec_src)
                elif isinstance(spec_src, (ast.List, ast.Tuple)):
                    specs.extend(
                        e
                        for e in spec_src.elts
                        if isinstance(e, ast.Call) and _tail(e.func) == "BlockSpec"
                    )
            for spec in specs:
                if not spec.args or not isinstance(spec.args[0], ast.Tuple):
                    continue
                for dim in spec.args[0].elts:
                    if isinstance(dim, ast.Name) and dim.id in ctx.adaptive_names:
                        continue  # already reported as adaptive-block-size
                    if ctx.literal_derived(dim) or ctx.is_whole_axis(dim):
                        continue
                    emit(
                        "block-spec-shape-derived",
                        dim.lineno,
                        "BlockSpec dimension is neither literal-derived nor "
                        "a whole input axis: shape-adaptive tiling",
                    )

            # f32 accumulators in VMEM scratch
            scratch = kwargs.get("scratch_shapes")
            entries = (
                list(scratch.elts)
                if isinstance(scratch, (ast.List, ast.Tuple))
                else ([scratch] if scratch is not None else [])
            )
            # scratch refs are the kernel's trailing positional params
            kparams = (
                [a.arg for a in kernel.args.posonlyargs + kernel.args.args]
                if kernel is not None else []
            )
            first = len(kparams) - len(entries)
            for idx, entry in enumerate(entries):
                if not (isinstance(entry, ast.Call) and _tail(entry.func) == "VMEM"):
                    continue
                if kernel is not None and first + idx >= 0:
                    clean, filled = _dma_staging(mod, kernel, kparams[first + idx])
                    if clean and filled:
                        continue  # DMA staging copy of an input, not an accumulator
                if len(entry.args) < 2:
                    continue
                dt = entry.args[1]
                tail = _tail(dt)
                resolved = tail
                if isinstance(dt, ast.Name) and dt.id in mod.module_assigns:
                    resolved = _tail(mod.module_assigns[dt.id]) or tail
                if resolved is None or resolved.lower() not in _SAFE_ACC_TAILS:
                    emit(
                        "accum-dtype",
                        dt.lineno,
                        f"VMEM scratch accumulator dtype '{resolved or '?'}' "
                        "is not f32: the contract's combine dtype is f32",
                    )

            # the kernel body: trace-time branches + narrow dot accumulators
            if kernel is None or kernel.name in linted_kernels:
                continue
            linted_kernels.add(kernel.name)
            if mod.fn_fastpath(kernel):
                continue
            kwhere = f"{rel}::{kernel.name}"
            for node in ast.walk(kernel):
                if isinstance(node, ast.If):
                    findings.append(
                        Finding(
                            pass_name="kernel_lint",
                            rule="shape-branch-in-kernel",
                            where=kwhere,
                            message=(
                                f"line {node.lineno}: Python 'if' in a kernel "
                                "body branches at trace time on static "
                                "arguments — compiled reduction structure "
                                "depends on parameterization; use pl.when "
                                "for runtime predication"
                            ),
                        )
                    )
                elif isinstance(node, ast.Call):
                    for kw in node.keywords:
                        if kw.arg != "preferred_element_type":
                            continue
                        tail = _tail(kw.value)
                        resolved = tail
                        if (
                            isinstance(kw.value, ast.Name)
                            and kw.value.id in mod.module_assigns
                        ):
                            resolved = _tail(mod.module_assigns[kw.value.id]) or tail
                        if resolved is None or resolved.lower() not in _SAFE_ACC_TAILS:
                            findings.append(
                                Finding(
                                    pass_name="kernel_lint",
                                    rule="accum-dtype",
                                    where=kwhere,
                                    message=(
                                        f"line {kw.value.lineno}: dot "
                                        f"accumulates in '{resolved or '?'}', "
                                        "not f32: sub-f32 partials make the "
                                        "result depend on the fold order"
                                    ),
                                )
                            )
    return findings


def run_pass(repo_root: Path, files: Optional[List[Path]] = None) -> list[Finding]:
    if files is None:
        files = sorted((repo_root / "src/repro/kernels").glob("*.py"))
    findings: list[Finding] = []
    for path in files:
        rel = str(path.relative_to(repo_root)) if path.is_absolute() else str(path)
        findings.extend(_lint_file(path, rel))
    return findings
