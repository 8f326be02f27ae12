"""Pass 4 — static MAC/byte cost model over the traced jaxprs.

The static counterpart of `launch/roofline.py`: where the roofline reads
compiled-HLO text for the executable that XLA *happened* to build, this pass
prices the traced graph itself — per audited entry point it counts

  * MACs: every ``dot_general`` contributes ``out_elems * K`` (K = product
    of contracting dims), every ``conv_general_dilated`` contributes
    ``out_elems * kernel_spatial * cin_per_group`` — split into integer MACs
    (both operands integer-dtyped: the PAMS int8/fxp10 datapath) and fp MACs;
  * HBM bytes: the entry point's own I/O (top-level invars + outvars +
    closed-over consts) plus, per ``pallas_call``, the operand/result blocks
    each kernel launch moves between HBM and VMEM, keyed per kernel so the
    fused-pipeline report shows which group moves what. The rank-4
    patch-batch operands/results of each launch are additionally broken out
    as ``feature_hbm_bytes`` — the inter-group activation traffic the
    paper's 79%-reduction claim is about, and the quantity the group-fused
    megakernel (`kernels/megakernel.py`) collapses to entry+exit only;
  * arithmetic intensity: MACs / HBM bytes.

``scan`` bodies are multiplied by their trip count; a ``while`` has no
static trip count, so its body is priced once and the entry is flagged
``"while_unbounded": true`` instead of silently under-counting. Costs are
structural (shapes and dtypes only — never data), so they are deterministic
across machines and safe to gate in CI: `bench_gate --audit` compares them
against the committed `ANALYSIS_baseline.json` via `report.gate_metrics`
and fails traffic regressions beyond tolerance.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import numpy as np
import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.analysis.jaxpr_audit import _sub_jaxprs, entry_point_specs


def _nelems(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _aval_bytes(aval) -> int:
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None or shape is None:
        return 0
    return _nelems(shape) * np.dtype(dtype).itemsize


def _is_int(aval) -> bool:
    return np.dtype(getattr(aval, "dtype", np.float32)).kind in ("i", "u")


def _dot_macs(eqn) -> int:
    (lc, _rc), _ = eqn.params["dimension_numbers"]
    lshape = eqn.invars[0].aval.shape
    k = 1
    for ax in lc:
        k *= int(lshape[ax])
    return _nelems(eqn.outvars[0].aval.shape) * k


def _conv_macs(eqn) -> int:
    dnums = eqn.params["dimension_numbers"]
    rhs_spec = dnums.rhs_spec            # (out_feature, in_feature, *spatial)
    rhs_shape = eqn.invars[1].aval.shape
    k = int(rhs_shape[rhs_spec[1]])      # cin per feature group
    for ax in rhs_spec[2:]:
        k *= int(rhs_shape[ax])
    return _nelems(eqn.outvars[0].aval.shape) * k


@dataclasses.dataclass
class EntryCost:
    """Static cost of one traced entry point."""
    macs: int = 0
    int_macs: int = 0
    io_bytes: int = 0
    pallas_bytes: int = 0
    feature_bytes: int = 0
    pallas_traffic: Dict[str, int] = dataclasses.field(default_factory=dict)
    while_unbounded: bool = False

    @property
    def hbm_bytes(self) -> int:
        return self.io_bytes + self.pallas_bytes

    def to_dict(self, labels: Dict[str, str]) -> Dict[str, Any]:
        hbm = self.hbm_bytes
        return {
            **labels,
            "macs": self.macs,
            "int_macs": self.int_macs,
            "io_bytes": self.io_bytes,
            "pallas_bytes": self.pallas_bytes,
            "feature_hbm_bytes": self.feature_bytes,
            "hbm_bytes": hbm,
            "arith_intensity": (self.macs / hbm) if hbm else 0.0,
            "pallas_traffic": dict(sorted(self.pallas_traffic.items())),
            "while_unbounded": self.while_unbounded,
        }


#: every kernel in this repo streams its activations as rank-4 patch-batch
#: tensors (N, h, w, C) while weights/biases/scales ride as rank <= 3
#: stationary operands — rank is therefore the structural feature/weight
#: discriminator (block-shape vs array-shape would misclassify single-step
#: grids, where every block covers its whole array).
_FEATURE_RANK = 4


def _pallas_call_bytes(eqn) -> Tuple[str, int, int]:
    """(kernel name, total HBM<->VMEM bytes one launch of this pallas_call
    moves, the FEATURE subset of those bytes): the union of its operand and
    result arrays, with rank-4 patch-batch tensors counted as feature
    (activation) traffic — the inter-group bytes the paper's 79%-reduction
    claim is about. A layer-fused chain pays feature traffic at every group
    boundary; the group-fused megakernel holds features in VMEM scratch and
    pays it only at the chain's entry and exit."""
    gm = eqn.params["grid_mapping"]
    total = feat = 0
    for bm in getattr(gm, "block_mappings", ()):
        sds = getattr(bm, "array_shape_dtype", None)
        if sds is not None:
            b = _nelems(sds.shape) * np.dtype(sds.dtype).itemsize
            total += b
            if len(sds.shape) >= _FEATURE_RANK:
                feat += b
    if total == 0:                     # fallback: eqn-level avals
        for v in list(eqn.invars) + list(eqn.outvars):
            b = _aval_bytes(v.aval)
            total += b
            if len(getattr(v.aval, "shape", ())) >= _FEATURE_RANK:
                feat += b
    name_info = eqn.params.get("name_and_src_info")
    kname = getattr(name_info, "name", None) or str(name_info or "pallas")
    return kname, total, feat


def _walk(jaxpr: Jaxpr, mult: int, cost: EntryCost) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            m = _dot_macs(eqn) * mult
            cost.macs += m
            if _is_int(eqn.invars[0].aval) and _is_int(eqn.invars[1].aval):
                cost.int_macs += m
        elif name == "conv_general_dilated":
            m = _conv_macs(eqn) * mult
            cost.macs += m
            if _is_int(eqn.invars[0].aval) and _is_int(eqn.invars[1].aval):
                cost.int_macs += m
        elif name == "pallas_call":
            kname, nbytes, fbytes = _pallas_call_bytes(eqn)
            cost.pallas_bytes += nbytes * mult
            cost.feature_bytes += fbytes * mult
            cost.pallas_traffic[kname] = (
                cost.pallas_traffic.get(kname, 0) + nbytes * mult)
        sub_mult = mult
        if name == "scan":
            sub_mult = mult * int(eqn.params.get("length", 1))
        elif name == "while":
            cost.while_unbounded = True
        elif name == "pallas_call":
            # the kernel body's eqns run once PER GRID STEP over block-shaped
            # avals — without this multiplier a 3-step per-op launch would
            # report a third of the MACs its group-fused twin reports over
            # the same math, corrupting the layer-vs-group comparison.
            steps = 1
            for g in getattr(eqn.params["grid_mapping"], "grid", ()):
                steps *= int(g)
            sub_mult = mult * max(steps, 1)
        for sub in _sub_jaxprs(eqn.params):
            inner = sub.jaxpr if isinstance(sub, ClosedJaxpr) else sub
            _walk(inner, sub_mult, cost)


def price_jaxpr(closed: ClosedJaxpr) -> EntryCost:
    """Price one traced graph (shapes/dtypes only — no data dependence)."""
    cost = EntryCost()
    for var in list(closed.jaxpr.invars) + list(closed.jaxpr.outvars):
        cost.io_bytes += _aval_bytes(getattr(var, "aval", None))
    for const in closed.consts:
        cost.io_bytes += int(getattr(const, "nbytes", 0) or 0)
    _walk(closed.jaxpr, 1, cost)
    return cost


def run_cost_audit() -> Dict[str, Any]:
    """The whole pass: price every audited entry point. Returns the report's
    ``metrics["static_costs"]`` section; entries that fail to trace land in
    ``"errors"`` (and the baseline diff flags the coverage loss)."""
    entries: Dict[str, Any] = {}
    errors: Dict[str, str] = {}
    for name, spec in entry_point_specs().items():
        try:
            fn, args = spec.make()
            closed = jax.make_jaxpr(fn)(*args)
        except Exception as e:
            errors[name] = repr(e)
            continue
        entries[name] = price_jaxpr(closed).to_dict(spec.labels)
    out: Dict[str, Any] = {"entries": entries}
    if errors:
        out["errors"] = errors
    return out
