"""Pass 3 — interval abstract interpretation over the traced jaxprs (ESSR3xx).

Where the jaxpr audit (pass 1) pattern-matches *hazards*, this pass computes
*guarantees*: a forward abstract interpretation that propagates value
intervals through every equation of an entry point's jaxpr — including
nested jit / custom_jvp / shard_map bodies and the Pallas kernel jaxprs
themselves (refs modeled as cells, ``get``/``swap`` as reads/unions) — and
certifies the integer datapath the PAMS serving path runs on:

  ESSR301  an integer-valued site's interval exceeds its storage dtype (or
           a what-if accumulator budget passed by the caller): overflow is
           not provably absent. This is a proof failure, not a measurement.
  ESSR302  a fused group's minimal accumulator bit-width exceeds the bit
           budget (default 32 — the int32 accumulators the kernels declare
           via ``preferred_element_type``). Every group's minimal width is
           also *reported* against the paper's 24-bit ASIC accumulator
           chain (`PAPER_ACC_BITS`), as signed headroom.
  ESSR303  a degenerate quantization scale in a served `QuantPack`: an
           alpha below the step floor (``|alpha| < qmax * EPS``) collapses
           the site's codes — the lattice can no longer represent the
           activation distribution it was calibrated on.
  ESSR304  an interval-unsound op: the interpreter met a primitive it has
           no sound transfer rule for. It FAILS CLOSED — the op's outputs
           become unbounded and the violation is reported; the analyzer
           never guesses a range.

The domain is *mixed concrete/interval*: an equation whose inputs are all
concretely known (weights, geometry index maps, quant codes — everything
derived from the traced arguments that are not declared abstract) is folded
by executing the primitive for real, so the certified bounds are seeded from
the ACTUAL quantized weight codes and `QuantPack` alphas rather than worst
cases. Only the declared-abstract arguments (the frame in [0,1], the
Algorithm-1 thresholds) and everything data-dependent on them carry
intervals. This is what makes the dequant/requant chains analyzable at all:
``round(clip(w, -alpha, alpha) / step)`` folds exactly because ``alpha`` and
``step`` stay correlated through concrete evaluation, where a pure interval
domain would lose the relation and blow up.

Bounds are per-tensor scalar intervals (one (lo, hi) per value). Integer
matmuls and convolutions use a refined rule when one operand is concrete:
with activation codes in ``[l, h]`` and actual weight codes ``W``, the
accumulator is bounded by ``max_j(h*P_j + l*N_j)`` where ``P_j``/``N_j`` are
the per-output sums of positive/negative weights — the static analog of the
ASIC's worst-case-input sizing of its 24-bit accumulator chain.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

from repro.analysis.jaxpr_audit import entry_point_specs
from repro.analysis.report import Violation

#: The ASIC accumulator chain the paper sizes (Sec. IV) — every group's
#: minimal bit-width is reported as signed headroom against this.
PAPER_ACC_BITS = 24

#: ESSR302 default budget: the int32 accumulators the kernel stack declares.
DEFAULT_BIT_BUDGET = 32

_INF = float("inf")


class UnsoundOpError(Exception):
    """Raised (and caught into ESSR304) when no sound transfer rule exists."""


@dataclasses.dataclass(frozen=True)
class Interval:
    """A per-tensor scalar interval: every element of the value lies in
    ``[lo, hi]``. ``Interval(-inf, inf)`` is TOP (nothing known)."""
    lo: float
    hi: float

    def __post_init__(self):
        if self.lo > self.hi:
            raise ValueError(f"empty interval [{self.lo}, {self.hi}]")

    @property
    def finite(self) -> bool:
        return math.isfinite(self.lo) and math.isfinite(self.hi)

    def union(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi))


TOP = Interval(-_INF, _INF)


def hull(v) -> Interval:
    """The interval hull of a value (identity on intervals; min/max of a
    concrete array)."""
    if isinstance(v, Interval):
        return v
    a = np.asarray(v)
    if a.size == 0:
        return Interval(0.0, 0.0)
    if a.dtype == bool:
        return Interval(float(a.min()), float(a.max()))
    return Interval(float(a.min()), float(a.max()))


def _is_concrete(v) -> bool:
    return not isinstance(v, (Interval, _Ref))


def bits_needed(lo: float, hi: float) -> Optional[int]:
    """Smallest two's-complement width representing every integer in
    [lo, hi]; None when unbounded."""
    if not (math.isfinite(lo) and math.isfinite(hi)):
        return None
    for b in range(1, 129):
        if -(2 ** (b - 1)) <= lo and hi <= 2 ** (b - 1) - 1:
            return b
    return None


def _dtype_bounds(dtype) -> Optional[Tuple[int, int]]:
    dt = np.dtype(dtype)
    if dt.kind in ("i", "u"):
        info = np.iinfo(dt)
        return int(info.min), int(info.max)
    return None


# ---------------------------------------------------------------------------
# interval arithmetic helpers
# ---------------------------------------------------------------------------

def _mul_bound(a: Interval, b: Interval) -> Interval:
    cands = []
    for x in (a.lo, a.hi):
        for y in (b.lo, b.hi):
            if (x == 0.0 and math.isinf(y)) or (y == 0.0 and math.isinf(x)):
                cands.append(0.0)
            else:
                cands.append(x * y)
    return Interval(min(cands), max(cands))


def _monotone(fn: Callable[[float], float]) -> Callable:
    def rule(a: Interval) -> Interval:
        return Interval(float(fn(a.lo)), float(fn(a.hi)))
    return rule


# ---------------------------------------------------------------------------
# the interpreter
# ---------------------------------------------------------------------------

class _Ref:
    """A Pallas ref cell: the interval hull of everything ever stored."""

    def __init__(self, init=None):
        self.value = init       # None == never written (reads give TOP)

    def read(self):
        return TOP if self.value is None else self.value

    def store(self, v):
        self.value = v if self.value is None else \
            hull(self.value).union(hull(v))


@dataclasses.dataclass(frozen=True)
class SiteRecord:
    """One certified integer site (abstract integer arithmetic)."""
    group: str
    prim: str
    dtype: str
    lo: float
    hi: float
    bits: Optional[int]


#: Arithmetic primitives whose integer outputs are certified as accumulator
#: sites (data movement can never widen a value, so it is checked but not
#: tabulated).
_ACC_PRIMS = frozenset({
    "add", "sub", "mul", "dot_general", "conv_general_dilated",
    "reduce_sum", "cumsum", "scatter-add", "convert_element_type",
})


class RangeInterpreter:
    """Forward mixed concrete/interval interpretation of one entry point."""

    def __init__(self, entry: str, acc_bits: Optional[int] = None,
                 bit_budget: int = DEFAULT_BIT_BUDGET):
        self.entry = entry
        self.acc_bits = acc_bits          # what-if accumulator budget
        self.bit_budget = bit_budget
        self.sites: List[SiteRecord] = []
        self.violations: List[Violation] = []
        self._groups: List[str] = []      # pallas kernel name stack
        self._flagged: set = set()        # (code, site) dedup

    # -- bookkeeping --------------------------------------------------------

    @property
    def group(self) -> str:
        return self._groups[-1] if self._groups else "top"

    def _flag(self, code: str, site_tail: str, message: str) -> None:
        site = f"entrypoint:{self.entry}::{site_tail}"
        if (code, site) in self._flagged:
            return
        self._flagged.add((code, site))
        self.violations.append(Violation(code, site, message))

    # -- evaluation ---------------------------------------------------------

    def run_closed(self, closed: ClosedJaxpr, invals: Sequence[Any]) -> List:
        return self.run_jaxpr(closed.jaxpr, closed.consts, invals)

    def run_jaxpr(self, jaxpr: Jaxpr, consts: Sequence[Any],
                  invals: Sequence[Any]) -> List:
        env: Dict[Any, Any] = {}

        def read(var):
            if isinstance(var, Literal):
                return np.asarray(var.val)
            return env[var]

        if len(consts) != len(jaxpr.constvars) or \
                len(invals) != len(jaxpr.invars):
            raise UnsoundOpError("jaxpr arity mismatch")
        for cv, c in zip(jaxpr.constvars, consts):
            env[cv] = c if isinstance(c, (Interval, _Ref)) else np.asarray(c)
        for iv, v in zip(jaxpr.invars, invals):
            env[iv] = v
        for eqn in jaxpr.eqns:
            outs = self.eval_eqn(eqn, [read(v) for v in eqn.invars])
            for ov, o in zip(eqn.outvars, outs):
                env[ov] = o
        return [read(v) for v in jaxpr.outvars]

    def eval_eqn(self, eqn, invals: Sequence[Any]) -> List:
        name = eqn.primitive.name
        try:
            if name in _STRUCTURED:
                outs = _STRUCTURED[name](self, eqn, invals)
            elif all(_is_concrete(v) for v in invals):
                outs = self._concrete_bind(eqn, invals)
            else:
                rule = _RULES.get(name)
                if rule is None:
                    raise UnsoundOpError(name)
                outs = rule(self, eqn, invals)
        except UnsoundOpError as e:
            self._flag("ESSR304", f"{self.group}::{name}",
                       f"no sound transfer rule for primitive '{e}' — "
                       f"outputs treated as unbounded")
            outs = [TOP] * len(eqn.outvars)
        except Exception as e:   # a rule crash is an unsoundness, not a skip
            self._flag("ESSR304", f"{self.group}::{name}",
                       f"transfer rule for '{name}' failed closed: {e!r}")
            outs = [TOP] * len(eqn.outvars)
        if len(outs) != len(eqn.outvars):
            outs = list(outs) + [TOP] * (len(eqn.outvars) - len(outs))
        self._certify(eqn, invals, outs)
        return outs

    def _concrete_bind(self, eqn, invals: Sequence[Any]) -> List:
        out = eqn.primitive.bind(*(jnp.asarray(v) for v in invals),
                                 **eqn.params)
        outs = out if eqn.primitive.multiple_results else [out]
        return [np.asarray(o) for o in outs]

    # -- certification (ESSR301/302 raw material) ---------------------------

    def _certify(self, eqn, invals, outs) -> None:
        name = eqn.primitive.name
        if name in ("get", "swap", "addupdate"):
            # a ref read/write cannot overflow by itself — the value landing
            # in the ref was already certified at the cast that produced it,
            # and `swap`'s returned old value on a fresh output buffer is
            # discarded garbage, not a computed site
            return
        for var, out in zip(eqn.outvars, outs):
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is None:
                continue
            bounds = _dtype_bounds(dt)
            if bounds is None:
                continue
            # the mathematical (pre-wraparound) interval of this site: for a
            # cast it is the INPUT's hull — the cast itself is where an
            # out-of-range value becomes undefined behavior
            if name == "convert_element_type" and invals:
                mi = hull(invals[0])
            else:
                mi = hull(out)
            abstract = isinstance(out, Interval) or (
                name == "convert_element_type" and invals
                and not _is_concrete(invals[0]))
            if not abstract and name != "convert_element_type":
                continue            # concrete arithmetic is exact by fold
            budget_lo, budget_hi = bounds
            budget_bits = None
            if self.acc_bits is not None and abstract \
                    and name in _ACC_PRIMS:
                budget_bits = self.acc_bits
                budget_lo = max(budget_lo, -(2 ** (self.acc_bits - 1)))
                budget_hi = min(budget_hi, 2 ** (self.acc_bits - 1) - 1)
            if mi.lo < budget_lo or mi.hi > budget_hi:
                what = (f"the {budget_bits}-bit accumulator budget"
                        if budget_bits is not None else f"dtype {dt}")
                self._flag(
                    "ESSR301", f"{self.group}::{name}",
                    f"interval [{mi.lo:.4g}, {mi.hi:.4g}] of '{name}' "
                    f"({dt}) exceeds {what}: overflow not provably absent")
            if abstract and name in _ACC_PRIMS:
                self.sites.append(SiteRecord(
                    self.group, name, str(dt), mi.lo, mi.hi,
                    bits_needed(mi.lo, mi.hi)))


# ---------------------------------------------------------------------------
# structured primitives: calls, pallas, refs
# ---------------------------------------------------------------------------

def _call_sub(interp: RangeInterpreter, eqn, invals, key: str) -> List:
    sub = eqn.params[key]
    if isinstance(sub, ClosedJaxpr):
        jaxpr, consts = sub.jaxpr, sub.consts
    else:
        jaxpr, consts = sub, ()
    n = len(jaxpr.invars)
    if len(invals) == n:
        args = invals
    elif len(invals) > n:        # leading consts packed into invars
        args = invals[len(invals) - n:]
    else:
        raise UnsoundOpError(f"{eqn.primitive.name} arity")
    return interp.run_jaxpr(jaxpr, consts, args)


def _eval_scan(interp: RangeInterpreter, eqn, invals) -> List:
    """``lax.scan`` / ``lax.map``. Consts and the carry enter the body at
    full shape; each xs operand is sliced along its leading axis, so the
    stacked operand's hull (which covers every slice) is a sound
    per-iteration seed. The carry is widened by hull-union across body
    passes until it stops growing — every pass re-walks the body, so
    accumulator sites stay certified under the fixpoint seeds. The ys hulls
    from the converged pass bound every iteration's slice. Fails closed if
    the carry keeps growing past the round cap."""
    sub = eqn.params["jaxpr"]
    if isinstance(sub, ClosedJaxpr):
        jaxpr, consts = sub.jaxpr, sub.consts
    else:
        jaxpr, consts = sub, ()
    n_consts = eqn.params["num_consts"]
    n_carry = eqn.params["num_carry"]
    if len(jaxpr.invars) != len(invals):
        raise UnsoundOpError("scan arity")
    const_vals = list(invals[:n_consts])
    carry = [hull(v) for v in invals[n_consts:n_consts + n_carry]]
    xs = [hull(v) for v in invals[n_consts + n_carry:]]
    outs = interp.run_jaxpr(jaxpr, consts, const_vals + carry + xs)
    for _ in range(64):
        grown = [c.union(hull(o)) for c, o in zip(carry, outs[:n_carry])]
        if grown == carry:
            break
        carry = grown
        outs = interp.run_jaxpr(jaxpr, consts, const_vals + carry + xs)
    else:
        raise UnsoundOpError("scan carry did not converge")
    return list(carry) + [hull(o) for o in outs[n_carry:]]


def _eval_pallas(interp: RangeInterpreter, eqn, invals) -> List:
    gm = eqn.params["grid_mapping"]
    jaxpr = eqn.params["jaxpr"]
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    n_idx = getattr(gm, "num_index_operands", 0)
    n_in = gm.num_inputs
    n_out = gm.num_outputs
    n_scr = getattr(gm, "num_scratch_operands", 0)
    if len(jaxpr.invars) != n_in + n_out + n_scr:
        raise UnsoundOpError("pallas kernel arity")
    in_refs = [_Ref(v) for v in invals[n_idx:n_idx + n_in]]
    out_refs = [_Ref() for _ in range(n_out)]
    scr_refs = [_Ref() for _ in range(n_scr)]
    name_info = eqn.params.get("name_and_src_info")
    kname = getattr(name_info, "name", None) or str(name_info or "pallas")
    interp._groups.append(kname)
    try:
        interp.run_jaxpr(jaxpr, (), in_refs + out_refs + scr_refs)
    finally:
        interp._groups.pop()
    # all grid steps run the same abstract body; the union of stores bounds
    # every block of the output
    return [r.read() for r in out_refs]


def _eval_get(interp, eqn, invals):
    if not isinstance(invals[0], _Ref):
        raise UnsoundOpError("get on non-ref")
    return [invals[0].read()]


def _eval_swap(interp, eqn, invals):
    ref = invals[0]
    if not isinstance(ref, _Ref):
        raise UnsoundOpError("swap on non-ref")
    old = ref.read()
    ref.store(invals[1])
    return [old]


def _eval_addupdate(interp, eqn, invals):
    ref = invals[0]
    if not isinstance(ref, _Ref):
        raise UnsoundOpError("addupdate on non-ref")
    cur = hull(ref.read())
    add = hull(invals[1])
    ref.store(Interval(cur.lo + min(0.0, add.lo), cur.hi + max(0.0, add.hi)))
    return []


_STRUCTURED: Dict[str, Callable] = {
    "jit": lambda i, e, v: _call_sub(i, e, v, "jaxpr"),
    "closed_call": lambda i, e, v: _call_sub(i, e, v, "call_jaxpr"),
    "core_call": lambda i, e, v: _call_sub(i, e, v, "call_jaxpr"),
    "remat2": lambda i, e, v: _call_sub(i, e, v, "jaxpr"),
    "custom_jvp_call": lambda i, e, v: _call_sub(i, e, v, "call_jaxpr"),
    "custom_vjp_call_jaxpr": lambda i, e, v: _call_sub(i, e, v, "fun_jaxpr"),
    "custom_vjp_call": lambda i, e, v: _call_sub(i, e, v, "call_jaxpr"),
    "shard_map": lambda i, e, v: _call_sub(i, e, v, "jaxpr"),
    "scan": _eval_scan,
    "pallas_call": _eval_pallas,
    "get": _eval_get,
    "swap": _eval_swap,
    "addupdate": _eval_addupdate,
}


# ---------------------------------------------------------------------------
# transfer rules (at least one operand abstract)
# ---------------------------------------------------------------------------

def _r(fn):
    """Adapt an Interval-only rule to the (interp, eqn, invals) signature."""
    def rule(interp, eqn, invals):
        return [fn(*(hull(v) for v in invals))]
    return rule


def _bool_out(interp, eqn, invals):
    return [Interval(0.0, 1.0)]


def _identity(interp, eqn, invals):
    return [hull(invals[0])]


def _union_all(interp, eqn, invals):
    out = hull(invals[0])
    for v in invals[1:]:
        out = out.union(hull(v))
    return [out]


def _select_n(interp, eqn, invals):
    return [_union_all(interp, eqn, invals[1:])[0]]


def _pad(interp, eqn, invals):
    return [hull(invals[0]).union(hull(invals[1]))]


def _gather(interp, eqn, invals):
    out = hull(invals[0])
    if "fill" in str(eqn.params.get("mode", "")).lower():
        out = out.union(Interval(0.0, 0.0))
    return [out]


def _scatter_add(interp, eqn, invals):
    op, upd = hull(invals[0]), hull(invals[2])
    n = max(1, int(np.prod(getattr(invals[2], "shape", ())
                           if _is_concrete(invals[2])
                           else eqn.invars[2].aval.shape)))
    return [Interval(op.lo + min(0.0, n * upd.lo),
                     op.hi + max(0.0, n * upd.hi))]


def _scatter_set(interp, eqn, invals):
    return [hull(invals[0]).union(hull(invals[2]))]


def _div(interp, eqn, invals):
    num, den = hull(invals[0]), hull(invals[1])
    if den.lo <= 0.0 <= den.hi:
        return [TOP]
    return [_mul_bound(num, Interval(1.0 / den.hi, 1.0 / den.lo))]


def _reduce_extent(eqn) -> int:
    axes = eqn.params.get("axes", ())
    shape = eqn.invars[0].aval.shape
    n = 1
    for ax in axes:
        n *= int(shape[ax])
    return max(1, n)


def _reduce_sum(interp, eqn, invals):
    a = hull(invals[0])
    n = _reduce_extent(eqn)
    return [Interval(min(n * a.lo, a.lo, 0.0), max(n * a.hi, a.hi, 0.0))]


def _cumsum(interp, eqn, invals):
    a = hull(invals[0])
    n = max(1, int(eqn.invars[0].aval.shape[eqn.params.get("axis", 0)]))
    return [Interval(min(a.lo, n * a.lo), max(a.hi, n * a.hi))]


def _argminmax(interp, eqn, invals):
    axes = eqn.params.get("axes", (0,))
    n = int(eqn.invars[0].aval.shape[axes[0]])
    return [Interval(0.0, float(max(0, n - 1)))]


def _convert(interp, eqn, invals):
    a = hull(invals[0])
    new_dtype = eqn.params.get("new_dtype")
    bounds = _dtype_bounds(new_dtype) if new_dtype is not None else None
    if bounds is not None:
        # once _certify reports an out-of-range cast, the landed value can
        # be anything in the dtype (wraparound) — clamp so one failure does
        # not cascade into fake downstream overflow proofs
        lo = max(a.lo, float(bounds[0]))
        hi = min(a.hi, float(bounds[1]))
        if lo > hi:
            return [Interval(float(bounds[0]), float(bounds[1]))]
        if a.lo < bounds[0] or a.hi > bounds[1]:
            return [Interval(float(bounds[0]), float(bounds[1]))]
        return [Interval(lo, hi)]
    return [a]


def _integer_pow(interp, eqn, invals):
    a = hull(invals[0])
    y = int(eqn.params["y"])
    if y < 0:
        return [_div(interp, eqn, [np.float64(1.0),
                                   _pow_iv(a, -y)])[0]]
    return [_pow_iv(a, y)]


def _pow_iv(a: Interval, y: int) -> Interval:
    cands = [a.lo ** y, a.hi ** y]
    if y % 2 == 0 and a.lo <= 0.0 <= a.hi:
        cands.append(0.0)
    return Interval(min(cands), max(cands))


def _contracted_sides(eqn, invals):
    """(abstract interval, concrete array, contracting axes of the concrete
    side, free axis to keep) — or None when both sides are abstract."""
    (lc, rc), _ = eqn.params["dimension_numbers"]
    lhs, rhs = invals[0], invals[1]
    if _is_concrete(rhs) and not _is_concrete(lhs):
        return hull(lhs), np.asarray(rhs, dtype=np.float64), tuple(rc)
    if _is_concrete(lhs) and not _is_concrete(rhs):
        return hull(rhs), np.asarray(lhs, dtype=np.float64), tuple(lc)
    return None


def _dot_general(interp, eqn, invals):
    refined = _contracted_sides(eqn, invals)
    if refined is not None:
        x, w, contract = refined
        pos = np.maximum(w, 0.0).sum(axis=contract)
        neg = np.minimum(w, 0.0).sum(axis=contract)
        hi = float(np.max(x.hi * pos + x.lo * neg)) if pos.size else 0.0
        lo = float(np.min(x.lo * pos + x.hi * neg)) if pos.size else 0.0
        return [Interval(lo, hi)]
    (lc, _rc), _ = eqn.params["dimension_numbers"]
    lshape = eqn.invars[0].aval.shape
    k = 1
    for ax in lc:
        k *= int(lshape[ax])
    p = _mul_bound(hull(invals[0]), hull(invals[1]))
    return [Interval(min(0.0, k * p.lo), max(0.0, k * p.hi))]


def _conv(interp, eqn, invals):
    lhs, rhs = invals[0], invals[1]
    dnums = eqn.params["dimension_numbers"]
    rhs_spec = dnums.rhs_spec          # (out_feature, in_feature, *spatial)
    x = hull(lhs)
    if any(p != (0, 0) for p in eqn.params.get("padding", ())):
        x = x.union(Interval(0.0, 0.0))   # zero padding enters the window
    if _is_concrete(rhs):
        w = np.asarray(rhs, dtype=np.float64)
        axes = tuple(ax for ax in range(w.ndim) if ax != rhs_spec[0])
        pos = np.maximum(w, 0.0).sum(axis=axes)
        neg = np.minimum(w, 0.0).sum(axis=axes)
        hi = float(np.max(x.hi * pos + x.lo * neg))
        lo = float(np.min(x.lo * pos + x.hi * neg))
        return [Interval(lo, hi)]
    w_shape = eqn.invars[1].aval.shape
    k = int(w_shape[rhs_spec[1]])
    for ax in rhs_spec[2:]:
        k *= int(w_shape[ax])
    p = _mul_bound(x, hull(rhs))
    return [Interval(min(0.0, k * p.lo), max(0.0, k * p.hi))]


_RULES: Dict[str, Callable] = {
    # elementwise arithmetic
    "add": _r(lambda a, b: Interval(a.lo + b.lo, a.hi + b.hi)),
    "sub": _r(lambda a, b: Interval(a.lo - b.hi, a.hi - b.lo)),
    "mul": _r(_mul_bound),
    "div": _div,
    "neg": _r(lambda a: Interval(-a.hi, -a.lo)),
    "abs": _r(lambda a: Interval(
        0.0 if a.lo <= 0.0 <= a.hi else min(abs(a.lo), abs(a.hi)),
        max(abs(a.lo), abs(a.hi)))),
    "max": _r(lambda a, b: Interval(max(a.lo, b.lo), max(a.hi, b.hi))),
    "min": _r(lambda a, b: Interval(min(a.lo, b.lo), min(a.hi, b.hi))),
    "clamp": _r(lambda lo, x, hi: Interval(
        min(max(x.lo, lo.lo), hi.hi), min(max(x.hi, lo.hi), hi.hi))),
    "round": _r(_monotone(np.rint)),
    "floor": _r(_monotone(math.floor)),
    "ceil": _r(_monotone(math.ceil)),
    "sign": _r(lambda a: Interval(-1.0, 1.0)),
    "sqrt": _r(lambda a: Interval(math.sqrt(max(a.lo, 0.0)),
                                  math.sqrt(max(a.hi, 0.0)))),
    "rsqrt": lambda i, e, v: (
        [TOP] if hull(v[0]).lo <= 0.0
        else [Interval(1.0 / math.sqrt(hull(v[0]).hi),
                       1.0 / math.sqrt(hull(v[0]).lo))]),
    "exp": _r(_monotone(math.exp)),
    "log": lambda i, e, v: (
        [TOP] if hull(v[0]).lo <= 0.0
        else [Interval(math.log(hull(v[0]).lo), math.log(hull(v[0]).hi))]),
    "log1p": lambda i, e, v: (
        [TOP] if hull(v[0]).lo <= -1.0
        else [Interval(math.log1p(hull(v[0]).lo),
                       math.log1p(hull(v[0]).hi))]),
    "expm1": _r(_monotone(math.expm1)),
    "tanh": _r(lambda a: Interval(math.tanh(a.lo), math.tanh(a.hi))),
    "logistic": _r(lambda a: Interval(1.0 / (1.0 + math.exp(-a.lo)),
                                      1.0 / (1.0 + math.exp(-a.hi)))),
    "sin": _r(lambda a: Interval(-1.0, 1.0)),
    "cos": _r(lambda a: Interval(-1.0, 1.0)),
    "integer_pow": _integer_pow,
    "square": _r(lambda a: _pow_iv(a, 2)),
    "stop_gradient": _identity,
    "copy": _identity,
    "is_finite": _bool_out,
    # comparisons / logic
    "lt": _bool_out, "le": _bool_out, "gt": _bool_out, "ge": _bool_out,
    "eq": _bool_out, "ne": _bool_out,
    "and": _bool_out, "or": _bool_out, "xor": _bool_out, "not": _bool_out,
    "select_n": _select_n,
    # shape / data movement (per-tensor hull is invariant)
    "reshape": _identity, "transpose": _identity, "squeeze": _identity,
    "expand_dims": _identity, "slice": _identity, "rev": _identity,
    "broadcast_in_dim": _identity, "dynamic_slice": _identity,
    "dynamic_update_slice": lambda i, e, v: [hull(v[0]).union(hull(v[1]))],
    "concatenate": _union_all,
    "pad": _pad,
    "gather": _gather,
    "sort": _identity,
    "convert_element_type": _convert,
    # reductions / scans
    "reduce_sum": _reduce_sum,
    "reduce_max": _identity, "reduce_min": _identity,
    "reduce_and": _bool_out, "reduce_or": _bool_out,
    "cumsum": _cumsum,
    "argmax": _argminmax, "argmin": _argminmax,
    # contractions
    "dot_general": _dot_general,
    "conv_general_dilated": _conv,
    # scatters
    "scatter-add": _scatter_add,
    "scatter": _scatter_set,
}


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RangeResult:
    """Everything the range pass derives from one entry point."""
    entry: str
    outputs: Any                       # pytree of Interval / concrete values
    sites: List[SiteRecord]
    violations: List[Violation]

    def groups(self) -> Dict[str, Dict[str, Any]]:
        """Per fused group: minimal accumulator bit-width + headroom."""
        out: Dict[str, Dict[str, Any]] = {}
        for s in self.sites:
            g = out.setdefault(s.group, {"acc_bits": 0, "dominant": None,
                                         "n_sites": 0})
            g["n_sites"] += 1
            b = s.bits if s.bits is not None else 999
            if b > g["acc_bits"]:
                g["acc_bits"] = b
                g["dominant"] = {"prim": s.prim, "dtype": s.dtype,
                                 "lo": s.lo, "hi": s.hi}
        for g in out.values():
            g["headroom_vs_paper"] = PAPER_ACC_BITS - g["acc_bits"]
        return out


def seed_values(args: Tuple, abstract: Dict[int, Tuple[float, float]]
                ) -> List[Any]:
    """Flattened invar seeds for ``fn(*args)``: declared-abstract arguments
    become intervals, everything else keeps its concrete traced value."""
    seeds: List[Any] = []
    for i, a in enumerate(args):
        leaves = jax.tree_util.tree_leaves(a)
        if i in abstract:
            lo, hi = abstract[i]
            seeds.extend([Interval(float(lo), float(hi))] * len(leaves))
        else:
            seeds.extend(np.asarray(leaf) for leaf in leaves)
    return seeds


def infer_ranges(fn: Callable, args: Tuple,
                 abstract: Dict[int, Tuple[float, float]],
                 entry: str = "adhoc",
                 acc_bits: Optional[int] = None,
                 bit_budget: int = DEFAULT_BIT_BUDGET) -> RangeResult:
    """Trace ``fn(*args)`` and abstract-interpret the jaxpr.

    ``abstract`` maps top-level argument positions to seed intervals; every
    other argument is folded concretely. Returns per-output abstract values
    (in the function's output pytree structure), the certified integer
    sites, and any ESSR301/302/304 violations."""
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
    interp = RangeInterpreter(entry, acc_bits=acc_bits,
                              bit_budget=bit_budget)
    seeds = seed_values(args, abstract)
    if len(seeds) != len(closed.jaxpr.invars):
        raise ValueError(
            f"seed/invar arity mismatch: {len(seeds)} seeds for "
            f"{len(closed.jaxpr.invars)} invars")
    outvals = interp.run_closed(closed, seeds)
    outputs = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(out_shape), outvals)
    result = RangeResult(entry, outputs, interp.sites, interp.violations)
    for group, info in result.groups().items():
        if info["acc_bits"] > bit_budget:
            result.violations.append(Violation(
                "ESSR302", f"entrypoint:{entry}::{group}",
                f"fused group needs a {info['acc_bits']}-bit accumulator, "
                f"over the {bit_budget}-bit budget "
                f"(dominant: {info['dominant']})"))
    return result


def check_quant_scales(pack, label: str) -> List[Violation]:
    """ESSR303 over a served `QuantPack`: an alpha below the step floor
    (``|alpha| < qmax * EPS``) floors the quantization step, so the codes of
    that site collapse instead of spanning the lattice."""
    from repro.quant.pams import EPS
    out: List[Violation] = []
    floor = pack.qmax * EPS
    for width, sites in pack.scales:
        for site, alpha in sites:
            if abs(alpha) < floor:
                out.append(Violation(
                    "ESSR303", f"quantpack[{label}]:w{width}:{site}",
                    f"alpha {alpha:.3g} below the step floor "
                    f"{floor:.3g} (qmax*EPS): codes at this site collapse"))
    return out


def bitwidth_metrics(results: List[RangeResult]) -> Dict[str, Any]:
    """The report's ``metrics["bitwidth"]`` section."""
    entries: Dict[str, Any] = {}
    for r in results:
        entries[r.entry] = {"groups": r.groups()}
    return {"paper_acc_bits": PAPER_ACC_BITS, "entries": entries}


def run_range_audit(bit_budget: int = DEFAULT_BIT_BUDGET
                    ) -> Tuple[List[Violation], Dict[str, Any]]:
    """The whole pass: certify every audited entry point + the served quant
    packs. Returns (violations, bitwidth metrics section)."""
    from repro.analysis.jaxpr_audit import _audit_setup

    violations: List[Violation] = []
    results: List[RangeResult] = []
    for name, spec in entry_point_specs().items():
        try:
            fn, args = spec.make()
            res = infer_ranges(fn, args, spec.abstract, entry=name,
                               bit_budget=bit_budget)
        except Exception as e:
            violations.append(Violation(
                "ESSR304", f"entrypoint:{name}",
                f"entry point failed to trace/interpret: {e!r}"))
            continue
        results.append(res)
        violations.extend(res.violations)
    setup = _audit_setup()
    violations.extend(check_quant_scales(setup.pack, "int8"))
    violations.extend(check_quant_scales(setup.pack_fxp10, "fxp10"))
    return violations, bitwidth_metrics(results)
