"""Jaxpr plumbing shared by the invariance prover and the hazard lint.

Three pieces:

* ``canonicalize(closed_jaxpr, batch)`` — render a jaxpr to a canonical
  text form: variables alpha-renamed by first appearance, nested jaxprs
  (pjit bodies, scan bodies, cond branches) emitted as labelled blocks in
  deterministic order.  ``compare_canonical(a, b, b1, b2)`` then checks
  two canonical forms for structural equality *modulo batch size*: lines
  must be identical except for integers, and an integer pair ``(d1, d2)``
  may differ only as a batch-affine dimension ``d = k*B + c`` with integer
  ``k >= 1`` and ``|c| <= 8`` consistent across the pair.  The affine form
  covers the real batch-derived dims (``G*W``, ``G*(W-1)``, a conv-pad
  ``C + d_conv - 1``, the MoE overflow bucket ``E*T + 1``) while a genuine
  schedule change — e.g. split-K going 4 -> 2, making a 64 -> 128 chunk —
  cannot satisfy it (the offset would be -144).  Batch sizes are chosen
  prime and >= 13 by the caller so model dims (powers of two in the smoke
  configs) and small structural constants stay clear of the affine window.
* ``walk_live(closed_jaxpr, cb)`` — visit equations that feed the jaxpr's
  outputs (``cb(eqn, path)``), skipping dead code.  ``jax.make_jaxpr``
  keeps equations whose results are dropped (e.g. MoE aux statistics in the
  serving forward); hazard-linting those would produce false positives.
  Liveness propagates through pjit bodies, scan carries (to a fixpoint,
  since a carry dead at the scan's outputs may still feed a live output
  through the next iteration), and cond branches; anything unrecognized is
  treated conservatively as live.
* ``eqn_source(eqn)`` — best-effort ``path::function`` + line attribution
  from the equation's traceback, filtered to frames under ``src/repro``.
"""

from __future__ import annotations

import re

import numpy as np
from jax._src import core as jcore

# Largest |c| accepted in the batch-affine dimension model d = k*B + c.
# Real offsets are tiny: +1 (MoE overflow bucket), -1 (drop-last slice),
# +3 (mamba conv pad).  Kept well under the minimum batch size (13) so an
# unrelated integer pair can rarely fake an affine fit — and the negative
# control catches the canonicalizer if one ever could.
AFFINE_C_MAX = 8

# pjit params that carry sharding/compilation metadata, not computation
# structure; they differ spuriously across traces and are excluded from the
# canonical form.
_SKIP_PARAMS = frozenset(
    {
        "sharding",
        "in_shardings",
        "out_shardings",
        "in_layouts",
        "out_layouts",
        "resource_env",
        "donated_invars",
        "keep_unused",
        "inline",
        "compiler_options_kvs",
        "ctx_mesh",
        "mesh",
        "check_vma",
        "symbolic_zeros",
        "num_consts",  # rendered structurally via the sub-jaxpr split
        "jvp_jaxpr_fun",  # lu.WrappedFun, not a jaxpr
        "fwd_jaxpr_thunk",
        "bwd",
        "call_jaxpr_pe",  # remat bookkeeping
    }
)


def _batch_affine(d: int, batch: int) -> bool:
    """Could ``d`` be ``k*batch + c`` for some ``k >= 1``, ``|c| <= C_MAX``?"""
    if d < batch - AFFINE_C_MAX:
        return False
    k = max(1, round(d / batch))
    return abs(d - k * batch) <= AFFINE_C_MAX


def _aval_str(aval) -> str:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return str(aval)
    dims = ",".join(str(int(d)) for d in shape)
    dtype = getattr(aval, "dtype", None)
    return f"{getattr(dtype, 'name', dtype)}[{dims}]"


class _Canon:
    def __init__(self, batch: int):
        self.batch = batch
        self.lines: list[str] = []
        self.queue: list[tuple[str, jcore.Jaxpr]] = []
        self.count = 0

    def run(self, top: jcore.Jaxpr) -> str:
        self._emit(top, "J0")
        while self.queue:
            label, jx = self.queue.pop(0)
            self._emit(jx, label)
        return "\n".join(self.lines)

    def _label(self, jx: jcore.Jaxpr) -> str:
        self.count += 1
        label = f"J{self.count}"
        self.queue.append((label, jx))
        return label

    def _emit(self, jaxpr: jcore.Jaxpr, label: str) -> None:
        names: dict[int, str] = {}

        def vname(v) -> str:
            if isinstance(v, jcore.Literal):
                return "lit:" + self._value(v.val)
            if type(v).__name__ == "DropVar":
                return "_"
            if id(v) not in names:
                names[id(v)] = f"v{len(names)}"
            return f"{names[id(v)]}:{_aval_str(v.aval)}"

        self.lines.append(f"{label}:")
        header = [vname(v) for v in list(jaxpr.constvars) + list(jaxpr.invars)]
        self.lines.append("  in " + " ".join(header))
        for eqn in jaxpr.eqns:
            outs = " ".join(vname(v) for v in eqn.outvars)
            ins = " ".join(vname(v) for v in eqn.invars)
            params = ",".join(
                f"{k}={self._value(v)}"
                for k, v in sorted(eqn.params.items())
                if k not in _SKIP_PARAMS
            )
            self.lines.append(f"  {outs} = {eqn.primitive.name}[{params}] {ins}")
        self.lines.append("  out " + " ".join(vname(v) for v in jaxpr.outvars))

    def _value(self, v) -> str:
        if isinstance(v, jcore.ClosedJaxpr):
            return self._label(v.jaxpr)
        if isinstance(v, jcore.Jaxpr):
            return self._label(v)
        if isinstance(v, bool):
            return str(v)
        if isinstance(v, (int, np.integer)):
            return str(int(v))
        if isinstance(v, (float, complex, np.floating)):
            return repr(v)
        if isinstance(v, str):
            return repr(v)
        if v is None:
            return "None"
        if isinstance(v, np.ndarray):
            if v.ndim == 0:
                return self._value(v.item())
            dims = ",".join(str(int(d)) for d in v.shape)
            if any(_batch_affine(int(d), self.batch) for d in v.shape):
                # possibly batch-shaped const (e.g. an arange over rows):
                # its values necessarily differ across batch sizes, so only
                # its structure enters the canonical form
                return f"const[{v.dtype}:{dims}]"
            return f"const[{v.dtype}:{dims}:{hash(v.tobytes())&0xFFFFFFFF:x}]"
        if isinstance(v, (tuple, list)):
            return "(" + ",".join(self._value(x) for x in v) + ")"
        if isinstance(v, dict):
            return (
                "{"
                + ",".join(f"{k}:{self._value(x)}" for k, x in sorted(v.items()))
                + "}"
            )
        try:
            s = str(v)
        except Exception:
            s = ""
        if "0x" in s or len(s) > 120 or not s:
            return f"<{type(v).__name__}>"
        return s


def dce(closed: jcore.ClosedJaxpr) -> jcore.ClosedJaxpr:
    """Dead-code-eliminate a traced jaxpr (all outputs kept).

    ``jax.make_jaxpr`` retains equations whose results never reach an
    output — e.g. the MoE aux statistics computed inside the serving
    forward — and those may legitimately be batch-*variant* (a ``1/T``
    load-balance scaling).  The contract covers computations that feed
    committed results, so both the prover and the hazard lint run on the
    DCE'd program.  Falls back to the original jaxpr if jax's internal
    DCE entry point moves.
    """
    try:
        from jax._src.interpreters import partial_eval as pe

        if closed.jaxpr.constvars:
            return closed
        new_jaxpr, used = pe.dce_jaxpr(
            closed.jaxpr,
            [True] * len(closed.jaxpr.outvars),
            instantiate=True,  # keep all binders: no arg renumbering
        )
        return jcore.ClosedJaxpr(new_jaxpr, closed.consts)
    except Exception:
        return closed


def canonicalize(closed: jcore.ClosedJaxpr, batch: int) -> str:
    return _Canon(batch).run(closed.jaxpr)


# numeric tokens in canonical lines: floats (kept verbatim) and ints
# (compared under the batch-affine model)
_NUM_RE = re.compile(r"-?\d+\.\d+(?:[eE][+-]?\d+)?|-?\d+")


def _skeleton(line: str) -> tuple[str, list]:
    nums: list = []

    def rep(m: re.Match) -> str:
        s = m.group(0)
        nums.append(float(s) if ("." in s or "e" in s or "E" in s) else int(s))
        return "§"

    return _NUM_RE.sub(rep, line), nums


def _lines_match(la: str, lb: str, b1: int, b2: int) -> bool:
    if la == lb:
        return True
    sa, na = _skeleton(la)
    sb, nb = _skeleton(lb)
    if sa != sb or len(na) != len(nb):
        return False
    for x, y in zip(na, nb):
        if x == y:
            continue
        if isinstance(x, float) or isinstance(y, float):
            return False
        # batch-affine: x = k*b1 + c, y = k*b2 + c, k >= 1, |c| <= C_MAX
        num, den = x - y, b1 - b2
        if den == 0 or num % den:
            return False
        k = num // den
        if k < 1:
            return False
        if abs(x - k * b1) > AFFINE_C_MAX:
            return False
    return True


def compare_canonical(
    a: str, b: str, b1: int, b2: int
) -> tuple[int, str, str] | None:
    """First structurally-divergent line between two canonical forms traced
    at batch sizes ``b1``/``b2``, or None when batch-invariant."""
    la, lb = a.splitlines(), b.splitlines()
    for i, (x, y) in enumerate(zip(la, lb)):
        if not _lines_match(x, y, b1, b2):
            return i, x, y
    if len(la) != len(lb):
        i = min(len(la), len(lb))
        longer = la if len(la) > len(lb) else lb
        extra = longer[i]
        return (i, extra, "<end>") if len(la) > len(lb) else (i, "<end>", extra)
    return None


# ---------------------------------------------------------------------------
# liveness-aware walking


def _invar_liveness(jaxpr: jcore.Jaxpr, out_mask: list[bool]) -> list[bool]:
    live: set[int] = {
        id(v)
        for v, keep in zip(jaxpr.outvars, out_mask)
        if keep and isinstance(v, jcore.Var)
    }
    for eqn in reversed(jaxpr.eqns):
        eqn_live = bool(getattr(eqn, "effects", None)) or any(
            isinstance(v, jcore.Var) and id(v) in live for v in eqn.outvars
        )
        if eqn_live:
            for v in eqn.invars:
                if isinstance(v, jcore.Var):
                    live.add(id(v))
    return [id(v) in live for v in jaxpr.invars]


def _scan_out_mask(
    body: jcore.Jaxpr, num_consts: int, num_carry: int, eqn_mask: list[bool]
) -> list[bool]:
    # A carry that is dead at the scan's outputs can still feed a live
    # output via the next iteration: iterate to a fixpoint.
    mask = list(eqn_mask)
    while True:
        inv = _invar_liveness(body, mask)
        changed = False
        for i in range(num_carry):
            if inv[num_consts + i] and not mask[i]:
                mask[i] = True
                changed = True
        if not changed:
            return mask


def _walk(jaxpr: jcore.Jaxpr, out_mask: list[bool], cb, path: tuple) -> None:
    live: set[int] = {
        id(v)
        for v, keep in zip(jaxpr.outvars, out_mask)
        if keep and isinstance(v, jcore.Var)
    }
    plan: list[tuple] = []
    for eqn in reversed(jaxpr.eqns):
        mask = [isinstance(v, jcore.Var) and id(v) in live for v in eqn.outvars]
        eqn_live = any(mask) or bool(getattr(eqn, "effects", None))
        plan.append((eqn, mask, eqn_live))
        if eqn_live:
            for v in eqn.invars:
                if isinstance(v, jcore.Var):
                    live.add(id(v))
    for eqn, mask, eqn_live in reversed(plan):
        if not eqn_live:
            continue
        cb(eqn, path)
        _recurse(eqn, mask, cb, path)


def _recurse(eqn, out_mask: list[bool], cb, path: tuple) -> None:
    name = eqn.primitive.name
    sub = path + (name,)
    params = eqn.params
    if name == "scan":
        body = params["jaxpr"].jaxpr
        mask = _scan_out_mask(
            body, params["num_consts"], params["num_carry"], out_mask
        )
        _walk(body, mask, cb, sub)
        return
    if name == "while":
        cond = params["cond_jaxpr"].jaxpr
        body = params["body_jaxpr"].jaxpr
        _walk(cond, [True] * len(cond.outvars), cb, sub)
        _walk(body, [True] * len(body.outvars), cb, sub)
        return
    if name == "cond":
        for br in params["branches"]:
            _walk(br.jaxpr, list(out_mask), cb, sub)
        return
    for v in params.values():
        jx = None
        if isinstance(v, jcore.ClosedJaxpr):
            jx = v.jaxpr
        elif isinstance(v, jcore.Jaxpr):
            jx = v
        elif (
            isinstance(v, (tuple, list))
            and v
            and all(isinstance(b, jcore.ClosedJaxpr) for b in v)
        ):
            for b in v:
                _walk(b.jaxpr, [True] * len(b.jaxpr.outvars), cb, sub)
            continue
        if jx is None:
            continue
        if len(jx.outvars) == len(out_mask):
            _walk(jx, list(out_mask), cb, sub)
        else:
            _walk(jx, [True] * len(jx.outvars), cb, sub)


def walk_live(closed: jcore.ClosedJaxpr, cb) -> None:
    """Call ``cb(eqn, path)`` for every equation feeding the outputs."""
    top = closed.jaxpr
    _walk(top, [True] * len(top.outvars), cb, ())


def walk_all(closed: jcore.ClosedJaxpr, cb) -> None:
    """Call ``cb(eqn, path)`` for every equation, live or dead."""

    def go(jaxpr: jcore.Jaxpr, path: tuple) -> None:
        for eqn in jaxpr.eqns:
            cb(eqn, path)
            sub = path + (eqn.primitive.name,)
            for v in eqn.params.values():
                if isinstance(v, jcore.ClosedJaxpr):
                    go(v.jaxpr, sub)
                elif isinstance(v, jcore.Jaxpr):
                    go(v, sub)
                elif (
                    isinstance(v, (tuple, list))
                    and v
                    and all(isinstance(b, jcore.ClosedJaxpr) for b in v)
                ):
                    for b in v:
                        go(b.jaxpr, sub)

    go(closed.jaxpr, ())


# ---------------------------------------------------------------------------
# source attribution


def eqn_source(eqn) -> tuple[str, int]:
    """Best-effort ``(path::function, line)`` for an equation."""
    frames = []
    try:
        from jax._src import source_info_util

        frames = list(source_info_util.user_frames(eqn.source_info.traceback))
    except Exception:
        pass
    chosen = None
    for fr in frames:
        fname = str(getattr(fr, "file_name", "")).replace("\\", "/")
        if "/repro/analysis/" in fname:
            continue  # the checker's own tracing machinery, never the cause
        if "/repro/" in fname:
            chosen = fr
            break
    if chosen is None:
        # fall back to the innermost non-checker frame (fixtures, tests)
        for fr in frames:
            fname = str(getattr(fr, "file_name", "")).replace("\\", "/")
            if "/repro/analysis/" not in fname:
                chosen = fr
                break
    if chosen is None:
        return "<untracked>", 0
    fname = str(getattr(chosen, "file_name", "?")).replace("\\", "/")
    for anchor in ("src/repro", "tests/"):
        idx = fname.find(anchor)
        if idx >= 0:
            fname = fname[idx:]
            break
    # bare name, not the qualified ``outer.<locals>.inner`` form
    func = str(getattr(chosen, "function_name", "?")).rsplit(".", 1)[-1]
    line = int(getattr(chosen, "start_line", 0) or 0)
    return f"{fname}::{func}", line
