"""Trip-count-aware static FLOP/byte accounting over serve-path jaxprs.

XLA's ``compiled.cost_analysis()`` counts every ``while``/``scan`` body
ONCE, not × trip-count (the costing.py docstring documents the exact
1/8-undercount on a length-8 scan), which is why the analytic model in
:mod:`repro.launch.costing` could only ever be validated on loop-free
single-layer configs. This module closes that gap from the other side:
it walks the traced jaxpr of every serve-path callable (recursing into
pjit / remat / custom-vjp bodies like :func:`~repro.analysis.jaxpr_audit
.iter_eqns`) and **multiplies loop-body costs by statically-extracted
trip counts** — ``scan`` carries its ``length`` in ``eqn.params``,
``pallas_call`` carries its grid, ``cond`` branches count at their
maximum. A ``while`` has no static trip count; rather than silently
undercounting (the exact failure mode the paper warns about: an
optimistic paper model diverging from the mapped design) it emits an
explicit ``audit-unbounded-loop`` diagnostic attributed to the innermost
``/src/repro/`` frame.

Counted quantities per target:

* ``flops`` — contraction FLOPs only (``dot_general`` at
  ``2 · |out| · K``, ``conv_general_dilated`` at
  ``2 · |out| · C_in/groups · Πk``), matching the analytic model's
  every-einsum convention (elementwise/norm FLOPs are deliberately
  excluded on both sides);
* ``gather_bytes`` / ``scatter_bytes`` — byte traffic of explicit
  gather/scatter ops (output resp. update size × itemsize), with the
  slice attributed to ``layers/attention.py`` split out as
  ``kv_gather_bytes`` — the paged-KV stream the engine's
  ``_kv_bytes_tick`` and ``benchmarks/roofline.py`` also price;
* ``pallas_stream_bytes`` — grid × block-shape input traffic of fused
  kernels (the *upper bound* the fused path touches; liveness-elided
  pages cannot be seen statically, so this is recorded, not reconciled);
* ``peak_bytes`` — peak live buffer bytes from a first-order linear-scan
  liveness over the jaxpr (loop bodies contribute one iteration's
  residency, call bodies their own peak);
* ``loops`` / ``unbounded`` — every loop-like eqn with its resolved trip
  count, or its diagnostic when unprovable.

Reconciliation (:func:`reconcile_target`): targets whose name maps to a
model-forward phase are compared against
:func:`repro.launch.costing.serve_target_cost` and drift beyond the
per-quantity tolerance raises an ``audit-cost-drift`` violation through
the same :class:`~repro.analysis.report.Violation` machinery as every
other rule. Helper targets (slot copies, samplers, pool maintenance)
have no analytic counterpart; they are recorded with ``analytic: null``
and never drift-checked — coverage is reported, not faked.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.analysis.jaxpr_audit import AuditTarget, _site, _subjaxprs, _trace
from repro.analysis.report import Violation

__all__ = ["StaticCost", "LoopRecord", "count_jaxpr", "cost_target",
           "reconcile_target", "cost_audit_targets", "FLOPS_RTOL",
           "KV_BYTES_RTOL", "DRIFT_PHASES"]

#: per-quantity drift tolerances (documented in docs/static-analysis.md):
#: FLOPs at the same ±2 % the loop-free validation in tests/test_costing.py
#: uses; KV gather bytes are exact by construction (both sides derive from
#: the same CacheSpec leaves) so anything past float noise is a bug.
FLOPS_RTOL = 0.02
KV_BYTES_RTOL = 1e-6

#: target phases with a model-forward analytic counterpart; everything
#: else (slot copies, samplers, pool maintenance) is recorded un-checked
DRIFT_PHASES = (
    "prefill", "decode", "verify", "prefill_chunk",
    "paged_decode", "paged_decode_hw", "paged_decode_fused",
    "paged_verify", "paged_verify_fused", "paged_suffix_prefill",
)

#: the file whose gathers stream the KV cache (gather_paged_kv and the
#: quantized-pool scale gathers live here)
_KV_GATHER_FILE = "src/repro/layers/attention.py"

#: call-like primitives whose single body executes exactly once per
#: enclosing execution (handled generically via _subjaxprs)
_SCATTER_PRIMS = ("scatter", "scatter-add", "scatter_add", "scatter-mul",
                  "scatter_mul", "scatter-min", "scatter-max",
                  "scatter_min", "scatter_max", "scatter_apply")


@dataclasses.dataclass
class LoopRecord:
    """One loop-like eqn: its kind, resolved trip count and source site."""

    kind: str                 # "scan" | "while" | "pallas_grid"
    length: Optional[int]     # None = statically unprovable
    path: str                 # nesting path, e.g. "pjit/scan"
    file: str
    line: int


@dataclasses.dataclass
class StaticCost:
    """Trip-count-corrected static counts for one traced callable."""

    flops: float = 0.0
    gather_bytes: float = 0.0
    scatter_bytes: float = 0.0
    kv_gather_bytes: float = 0.0
    pallas_stream_bytes: float = 0.0
    peak_bytes: float = 0.0
    arg_bytes: float = 0.0
    out_bytes: float = 0.0
    n_eqns: int = 0
    loops: List[LoopRecord] = dataclasses.field(default_factory=list)
    unbounded: List[LoopRecord] = dataclasses.field(default_factory=list)

    def merge_max(self, other: "StaticCost") -> None:
        """Elementwise max of the count fields (cond-branch policy: a
        branchy target is priced at its most expensive branch)."""
        for f in ("flops", "gather_bytes", "scatter_bytes",
                  "kv_gather_bytes", "pallas_stream_bytes"):
            setattr(self, f, max(getattr(self, f), getattr(other, f)))
        self.n_eqns += other.n_eqns
        self.loops.extend(other.loops)
        self.unbounded.extend(other.unbounded)


def _itemsize(dtype) -> int:
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        return 0                  # extended dtypes (PRNG keys): not counted


def _aval_bytes(aval) -> float:
    size = getattr(aval, "size", None)
    if size is None:
        return 0.0
    return float(size) * _itemsize(getattr(aval, "dtype", None))


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _dot_flops(eqn) -> float:
    """2 · |out| · K for a dot_general (K = Π contracting dims; an
    outer-product einsum has K = 1 and still costs 2/element — the same
    MAC convention the analytic model uses)."""
    (lhs_c, _), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval
    contract = _prod(lhs.shape[i] for i in lhs_c)
    return 2.0 * float(eqn.outvars[0].aval.size) * contract


def _conv_flops(eqn) -> float:
    """2 · |out| · (C_in / groups) · Π kernel-spatial."""
    dn = eqn.params["dimension_numbers"]
    rhs = eqn.invars[1].aval
    k_spatial = _prod(rhs.shape[i] for i in dn.rhs_spec[2:])
    in_ch = rhs.shape[dn.rhs_spec[1]]       # already / feature_group_count
    return 2.0 * float(eqn.outvars[0].aval.size) * in_ch * k_spatial


def _pallas_grid(eqn) -> Optional[int]:
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", None)
    if grid is None:
        return None
    try:
        return _prod(int(g) for g in grid)
    except (TypeError, ValueError):
        return None                          # dynamic grid dims


def _pallas_stream_bytes(eqn, grid: int) -> float:
    """Grid × block-shape bytes of every input block — what the kernel's
    BlockSpecs cause to be streamed per full sweep (upper bound; index
    maps may revisit or elide pages, which is invisible statically)."""
    gm = eqn.params.get("grid_mapping")
    mappings = getattr(gm, "block_mappings", ()) or ()
    n_in = getattr(gm, "num_inputs", len(mappings))
    total = 0.0
    for bm in list(mappings)[:n_in]:
        aval = getattr(bm, "array_aval", None)
        shape = getattr(bm, "block_shape", None)
        if aval is None or shape is None:
            continue
        # block dims are Blocked(block_size=n) entries; squeezed dims count 1
        blk = _prod(int(getattr(s, "block_size", 1)) for s in shape)
        total += float(blk) * _itemsize(aval.dtype) * grid
    return total


def _loop_site(eqn, path: Tuple[str, ...], kind: str,
               length: Optional[int]) -> LoopRecord:
    file, line = _site(eqn)
    return LoopRecord(kind=kind, length=length,
                      path="/".join(path + (eqn.primitive.name,)),
                      file=file, line=line)


def count_jaxpr(jaxpr, *, mult: float = 1.0, path: Tuple[str, ...] = (),
                acc: Optional[StaticCost] = None) -> StaticCost:
    """Walk one (open) jaxpr, accumulating trip-count-weighted costs."""
    if acc is None:
        acc = StaticCost()
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        acc.n_eqns += 1

        if name == "dot_general":
            acc.flops += mult * _dot_flops(eqn)
        elif name == "conv_general_dilated":
            acc.flops += mult * _conv_flops(eqn)

        elif name == "gather":
            b = mult * sum(_aval_bytes(o.aval) for o in eqn.outvars)
            acc.gather_bytes += b
            # KV stream = gathers of the (pool, block, ...) cache tensors;
            # rank-<3 gathers at the same site are block-table/index
            # lookups, not KV traffic
            if (_site(eqn)[0] == _KV_GATHER_FILE
                    and getattr(eqn.invars[0].aval, "ndim", 0) >= 3):
                acc.kv_gather_bytes += b
        elif name in _SCATTER_PRIMS:
            # operand layout: (operand, indices, updates)
            upd = eqn.invars[2].aval if len(eqn.invars) >= 3 else None
            if upd is not None:
                acc.scatter_bytes += mult * _aval_bytes(upd)

        elif name == "scan":
            length = eqn.params.get("length")
            inner = eqn.params["jaxpr"].jaxpr
            if length is None:
                acc.unbounded.append(_loop_site(eqn, path, "scan", None))
                count_jaxpr(inner, mult=mult, path=path + (name,), acc=acc)
            else:
                acc.loops.append(_loop_site(eqn, path, "scan", int(length)))
                count_jaxpr(inner, mult=mult * int(length),
                            path=path + (name,), acc=acc)

        elif name == "while":
            # no static trip count — count the body ONCE and diagnose
            # loudly instead of silently undercounting
            acc.unbounded.append(_loop_site(eqn, path, "while", None))
            count_jaxpr(eqn.params["cond_jaxpr"].jaxpr, mult=mult,
                        path=path + (name,), acc=acc)
            count_jaxpr(eqn.params["body_jaxpr"].jaxpr, mult=mult,
                        path=path + (name,), acc=acc)

        elif name == "pallas_call":
            grid = _pallas_grid(eqn)
            inner = eqn.params.get("jaxpr")
            if grid is None:
                acc.unbounded.append(
                    _loop_site(eqn, path, "pallas_grid", None))
                grid = 1
            else:
                acc.loops.append(
                    _loop_site(eqn, path, "pallas_grid", grid))
                acc.pallas_stream_bytes += mult * _pallas_stream_bytes(
                    eqn, grid)
            if inner is not None and hasattr(inner, "eqns"):
                count_jaxpr(inner, mult=mult * grid, path=path + (name,),
                            acc=acc)

        elif name == "cond":
            branches = eqn.params.get("branches", ())
            branch_costs = []
            for br in branches:
                sub = br.jaxpr if hasattr(br, "jaxpr") else br
                branch_costs.append(count_jaxpr(
                    sub, mult=mult, path=path + (name,)))
            if branch_costs:
                worst = branch_costs[0]
                for bc in branch_costs[1:]:
                    worst.merge_max(bc)
                acc.flops += worst.flops
                acc.gather_bytes += worst.gather_bytes
                acc.scatter_bytes += worst.scatter_bytes
                acc.kv_gather_bytes += worst.kv_gather_bytes
                acc.pallas_stream_bytes += worst.pallas_stream_bytes
                acc.n_eqns += worst.n_eqns
                acc.loops.extend(worst.loops)
                acc.unbounded.extend(worst.unbounded)

        else:
            # pjit / remat / custom_jvp / custom_vjp / closed_call bodies
            # execute exactly once per enclosing execution
            for sub in _subjaxprs(eqn):
                count_jaxpr(sub, mult=mult, path=path + (name,), acc=acc)
    return acc


# ---------------------------------------------------------------------------
# peak live buffer bytes: first-order linear-scan liveness
# ---------------------------------------------------------------------------


def _peak_live_bytes(jaxpr) -> float:
    """Peak of Σ live-value bytes over a single in-order execution.

    First-order: inputs/constants are live until their last top-level
    use; an eqn's outputs go live before it executes; a call/loop body
    contributes its own (recursive) peak minus its argument bytes while
    its eqn executes — loop bodies count one iteration's residency
    (buffers are reused across iterations, which is the point of a loop).
    """
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if hasattr(v, "aval") and not hasattr(v, "val"):  # skip Literals
                last_use[v] = i
    for v in jaxpr.outvars:
        if hasattr(v, "aval") and not hasattr(v, "val"):
            last_use[v] = len(jaxpr.eqns)

    live: Dict[Any, float] = {}
    for v in tuple(jaxpr.invars) + tuple(jaxpr.constvars):
        if v in last_use:
            live[v] = _aval_bytes(v.aval)
    peak = sum(live.values())

    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            if v in last_use:
                live[v] = _aval_bytes(v.aval)
        inner_extra = 0.0
        for sub in _subjaxprs(eqn):
            sub = getattr(sub, "jaxpr", sub)      # unwrap ClosedJaxpr
            arg_bytes = sum(_aval_bytes(iv.aval)
                            for iv in tuple(sub.invars)
                            + tuple(sub.constvars))
            inner_extra = max(inner_extra,
                              _peak_live_bytes(sub) - arg_bytes)
        peak = max(peak, sum(live.values()) + max(inner_extra, 0.0))
        for v in list(live):
            if last_use.get(v, -1) <= i:
                del live[v]
    return peak


# ---------------------------------------------------------------------------
# per-target costing + reconciliation
# ---------------------------------------------------------------------------


def cost_target(target: AuditTarget) -> Tuple[StaticCost, List[Violation]]:
    """Trace one target and count its static costs; unprovable trip
    counts surface as ``audit-unbounded-loop`` violations (error on
    drift-checked phases — the reconciliation would silently undercount —
    warning on helper targets, whose counts are recorded, not checked)."""
    closed = _trace(target)
    cost = count_jaxpr(closed.jaxpr)
    cost.peak_bytes = _peak_live_bytes(closed.jaxpr)
    cost.arg_bytes = sum(_aval_bytes(v.aval) for v in closed.jaxpr.invars)
    cost.out_bytes = sum(_aval_bytes(v.aval) for v in closed.jaxpr.outvars)

    checked = target_phase(target.name) in DRIFT_PHASES
    violations = [
        Violation(
            rule="audit-unbounded-loop", target=target.name,
            file=lr.file, line=lr.line, provenance=lr.path,
            severity="error" if checked else "warning",
            message=(f"{lr.kind} with no statically-provable trip count — "
                     "its body is counted once, so every derived cost is "
                     "a lower bound" + (
                         " and the drift check against the analytic model "
                         "is unsound for this target" if checked else "")))
        for lr in cost.unbounded
    ]
    return cost, violations


def target_phase(name: str) -> str:
    """``"moe/paged_decode_hw@mesh"`` → ``"paged_decode_hw"``."""
    return name.split("/", 1)[1].split("@", 1)[0]


def _drift(static: float, analytic: float) -> float:
    if analytic == 0.0:
        return 0.0 if static == 0.0 else math.inf
    return static / analytic - 1.0


def reconcile_target(target: AuditTarget, static: StaticCost,
                     analytic: Optional[Dict[str, float]], *,
                     flops_rtol: float = FLOPS_RTOL,
                     kv_bytes_rtol: float = KV_BYTES_RTOL,
                     ) -> Tuple[Optional[Dict[str, float]], List[Violation]]:
    """Compare static counts against the analytic prediction.

    Returns ``(drift, violations)`` where ``drift`` maps quantity →
    signed relative drift (``static/analytic − 1``), or ``None`` when
    the target has no analytic counterpart.
    """
    if analytic is None:
        return None, []
    out: List[Violation] = []
    drift: Dict[str, float] = {}

    d = _drift(static.flops, analytic["flops"])
    drift["flops"] = d
    if abs(d) > flops_rtol:
        out.append(Violation(
            rule="audit-cost-drift", target=target.name, file="", line=0,
            provenance=f"phase={target_phase(target.name)}",
            message=(f"static contraction FLOPs {static.flops:.6g} vs "
                     f"analytic {analytic['flops']:.6g} "
                     f"(drift {d:+.2%}, tolerance ±{flops_rtol:.0%}) — "
                     "launch/costing.py and the traced computation "
                     "disagree")))

    kv_pred = analytic.get("kv_gather_bytes")
    if kv_pred is not None:
        d = _drift(static.kv_gather_bytes, kv_pred)
        drift["kv_gather_bytes"] = d
        if abs(d) > kv_bytes_rtol:
            out.append(Violation(
                rule="audit-cost-drift", target=target.name, file="",
                line=0, provenance=f"phase={target_phase(target.name)}",
                message=(f"static KV gather bytes "
                         f"{static.kv_gather_bytes:.6g} vs analytic "
                         f"{kv_pred:.6g} (drift {d:+.2%}) — "
                         "kv_bytes_per_token / _kv_bytes_tick / roofline "
                         "accounting has diverged from the traced gather")))
    return drift, out


def _loop_meta(cost: StaticCost) -> Dict[str, Any]:
    return {
        "scans": sum(1 for l in cost.loops if l.kind == "scan"),
        "pallas_grids": sum(1 for l in cost.loops
                            if l.kind == "pallas_grid"),
        "max_trip_count": max((l.length for l in cost.loops
                               if l.length is not None), default=0),
        "unbounded": len(cost.unbounded),
    }


def cost_audit_targets(targets: Sequence[AuditTarget], *,
                       flops_rtol: float = FLOPS_RTOL,
                       kv_bytes_rtol: float = KV_BYTES_RTOL,
                       ) -> Tuple[List[Dict[str, Any]], List[Violation]]:
    """Cost-audit a target list → (analysis-v2 target records, violations).

    Predictions come from :func:`repro.launch.costing.serve_target_cost`,
    keyed exactly the way ``targets.py`` keys its audit targets.
    """
    from repro.configs.registry import get_config, smoke_config
    from repro.launch.costing import serve_target_cost
    from repro.analysis.targets import (SMOKE_BY_FAMILY, AUDIT_SHAPE)

    cfgs = {fam: smoke_config(get_config(arch))
            for fam, arch in SMOKE_BY_FAMILY.items()}
    records: List[Dict[str, Any]] = []
    violations: List[Violation] = []
    for t in targets:
        cost, v = cost_target(t)
        violations.extend(v)
        phase = target_phase(t.name)
        analytic = None
        if phase in DRIFT_PHASES:
            analytic = serve_target_cost(cfgs[t.family], phase,
                                         **AUDIT_SHAPE)
            analytic = {k: v for k, v in analytic.items()
                        if k != "components"}
        drift, dv = reconcile_target(t, cost, analytic,
                                     flops_rtol=flops_rtol,
                                     kv_bytes_rtol=kv_bytes_rtol)
        violations.extend(dv)
        records.append({
            "target": t.name,
            "family": t.family,
            "phase": phase,
            "mesh": t.mesh is not None,
            "drift_checked": analytic is not None,
            "static": {
                "flops": cost.flops,
                "gather_bytes": cost.gather_bytes,
                "scatter_bytes": cost.scatter_bytes,
                "kv_gather_bytes": cost.kv_gather_bytes,
                "pallas_stream_bytes": cost.pallas_stream_bytes,
                "peak_bytes": cost.peak_bytes,
                "arg_bytes": cost.arg_bytes,
                "out_bytes": cost.out_bytes,
            },
            "analytic": analytic,
            "drift": drift,
            "loops": _loop_meta(cost),
        })
    return records, violations
