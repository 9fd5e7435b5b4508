"""Jaxpr-level serve-path auditor.

Traces a serve callable with :func:`jax.make_jaxpr` over abstract
``ShapeDtypeStruct`` inputs — no FLOPs, no allocation — and walks the
resulting ClosedJaxpr (recursing into pjit / scan / cond / remat bodies)
checking the repo invariants the end-to-end tests can only probe one
shape at a time:

* **no-host-transfer** — no ``device_put`` / host-callback primitives
  inside a jitted hot path (each is a device sync per tick);
* **donation-honored** — every leaf of a donated argument appears in the
  lowering's input-output aliasing table (``tf.aliasing_output``), i.e.
  donation survived the in/out sharding specs;
* **f32-upcast-allowlist** — bf16/f16 → f32 ``convert_element_type`` only
  at the named accumulation sites (``layers/numerics.py`` helpers and
  ``layers/attention.py``); an upcast anywhere else is an unbudgeted 2×
  memory-stream regression (the paper's accumulate-wide-store-narrow
  discipline made checkable);
* **kv-constraint-coverage** — on a mesh, KV-cache-shaped intermediates
  carry ``sharding_constraint`` ops whose spec matches the
  ``serve_rules_for(family)`` table (a dropped ``_constrain_cache`` means
  GSPMD remats the donated cache every step);
* **determinism** — deterministic targets contain no PRNG primitives, and
  the bitwise-reproducible families (ssm / hybrid) never touch the
  ``model`` mesh axis (no model-axis collectives, no model-axis specs).

This is the analogue of inspecting the synthesized netlist instead of
trusting the HDL (PAPER.md): the jaxpr is what actually runs.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.report import Violation
from repro.parallel.sharding import activate

__all__ = ["AuditTarget", "audit_target", "audit_targets", "iter_eqns"]

#: primitives that force a host round-trip / transfer inside a hot path
BANNED_PRIMITIVES = {
    "device_put", "pure_callback", "io_callback", "callback",
    "debug_callback", "infeed", "outfeed",
}

#: unkeyed-or-not, any PRNG primitive on a deterministic path breaks
#: bitwise reproducibility (keys must enter through explicit rng args on
#: the sampling targets only)
PRNG_PRIMITIVES = {
    "random_seed", "random_bits", "random_wrap", "random_unwrap",
    "random_fold_in", "random_gamma", "threefry2x32",
}

#: cross-device collectives — checked for the ``model`` axis on ssm/hybrid
#: (``*_invariant``: a psum / all_gather whose result no longer varies over
#: the axis, as shard_map traces them)
COLLECTIVE_PRIMITIVES = {
    "psum", "psum2", "psum_invariant", "pmax", "pmin", "all_gather",
    "all_gather_invariant", "all_to_all", "ppermute", "reduce_scatter",
    "pgather",
}

#: the only files allowed to originate a bf16/f16 → f32 upcast on a serve
#: path (relative to the repo root)
UPCAST_ALLOWLIST = (
    "src/repro/layers/numerics.py",
    "src/repro/layers/attention.py",
    # fused paged-attention kernels accumulate (m, l, acc) in f32 and
    # dequantize int8 KV in-register — both are budgeted upcasts
    "src/repro/kernels/paged_attention.py",
)

_SMALL_FLOATS = (jnp.bfloat16, jnp.float16)


@dataclasses.dataclass(frozen=True)
class AuditTarget:
    """One serve-path callable plus everything needed to trace and lower
    it exactly the way the engine does (donation, in/out shardings)."""

    name: str
    family: str
    fn: Any
    args: Tuple[Any, ...]
    donate: Tuple[int, ...] = ()
    deterministic: bool = True
    mesh: Any = None
    rules: Any = None
    in_shardings: Any = None
    out_shardings: Any = None
    #: operand shape → expected normalized constraint spec (mesh targets
    #: that touch KV state; empty disables the coverage rule)
    kv_specs: Tuple[Tuple[Tuple[int, ...], Tuple[Any, ...]], ...] = ()


def _subjaxprs(eqn):
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else (v,)
        for x in items:
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def iter_eqns(jaxpr, path: Tuple[str, ...] = ()) -> Iterator[Tuple[Any, Tuple[str, ...]]]:
    """Yield ``(eqn, nesting_path)`` over a jaxpr and all inner jaxprs
    (pjit bodies, scan/while/cond branches, remat, custom_jvp, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub, path + (eqn.primitive.name,))


def _site(eqn) -> Tuple[str, int]:
    """Innermost repo frame of the primitive's traceback → (file, line)."""
    tb = getattr(eqn.source_info, "traceback", None)
    if tb is None:
        return "", 0
    for fr in tb.frames:
        fname = fr.file_name.replace("\\", "/")
        idx = fname.find("/src/repro/")
        if idx >= 0:
            return fname[idx + 1:], fr.line_num
        if "/repro/" in fname:  # installed/editable layouts
            return "src/repro/" + fname.split("/repro/", 1)[1], fr.line_num
    return "", 0


def _norm_spec(spec, ndim: int) -> Tuple[Any, ...]:
    """PartitionSpec → comparable tuple padded to ``ndim`` entries."""
    entries = tuple(spec) + (None,) * (ndim - len(tuple(spec)))
    out = []
    for e in entries:
        if isinstance(e, tuple):
            e = e[0] if len(e) == 1 else tuple(e)
        out.append(e)
    return tuple(out)


def _mentions_model(spec_entries) -> bool:
    for e in spec_entries:
        axes = e if isinstance(e, tuple) else (e,)
        if "model" in axes:
            return True
    return False


def _trace(target: AuditTarget):
    ctx = activate(target.mesh, target.rules) if target.mesh is not None \
        else contextlib.nullcontext()
    with ctx:
        return jax.make_jaxpr(target.fn)(*target.args)


def _live_donated_leaves(target: AuditTarget, closed) -> int:
    """Donated leaves that survive dead-code elimination.

    ``jax.jit`` (``keep_unused=False``) drops arguments the output never
    depends on — e.g. recurrent leaves a spec-decode commit replaces
    wholesale from the verify snapshot. A dead donated leaf cannot (and
    need not) alias, so only live leaves count toward the expectation.
    """
    n_out = len(closed.jaxpr.outvars)
    try:
        from jax.interpreters import partial_eval as pe
        _, used_inputs = pe.dce_jaxpr(closed.jaxpr, [True] * n_out)
    except Exception:
        used_inputs = [True] * len(closed.jaxpr.invars)
    sizes = [len(jax.tree.leaves(a)) for a in target.args]
    offsets = [sum(sizes[:i]) for i in range(len(sizes))]
    live = 0
    for i in target.donate:
        live += sum(bool(u)
                    for u in used_inputs[offsets[i]:offsets[i] + sizes[i]])
    return live


def _check_donation(target: AuditTarget, closed) -> List[Violation]:
    """Lower exactly like the engine's ``_build`` and count aliased
    outputs: every *live* leaf of a donated argument must alias."""
    kwargs: Dict[str, Any] = {"donate_argnums": target.donate}
    if target.mesh is not None:
        if target.in_shardings is not None:
            kwargs["in_shardings"] = target.in_shardings
        if target.out_shardings is not None:
            kwargs["out_shardings"] = target.out_shardings
    ctx = activate(target.mesh, target.rules) if target.mesh is not None \
        else contextlib.nullcontext()
    import warnings
    with ctx, warnings.catch_warnings():
        # an unhonored donation warns at lowering time; the violation
        # record below is the actionable signal
        warnings.simplefilter("ignore")
        lowered = jax.jit(target.fn, **kwargs).lower(*target.args)
    text = lowered.as_text()
    n_aliased = text.count("tf.aliasing_output")
    n_donated = _live_donated_leaves(target, closed)
    if n_aliased < n_donated:
        return [Violation(
            rule="donation-honored", target=target.name, file="", line=0,
            message=(f"only {n_aliased}/{n_donated} donated leaves appear "
                     "in the lowering's input-output aliasing — donation "
                     "dropped (dtype/shape/sharding mismatch between the "
                     "donated input and its output)"),
            provenance=f"donate_argnums={target.donate}")]
    return []


def audit_target(target: AuditTarget) -> List[Violation]:
    """Run every jaxpr rule against one serve callable."""
    out: List[Violation] = []
    closed = _trace(target)
    reproducible = target.family in ("ssm", "hybrid")
    kv_specs = dict(target.kv_specs)
    seen_kv_constraint = False

    for eqn, path in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        prov = "/".join(path + (name,))

        if name in BANNED_PRIMITIVES:
            file, line = _site(eqn)
            out.append(Violation(
                rule="no-host-transfer", target=target.name, file=file,
                line=line, provenance=prov,
                message=f"{name} primitive inside a jitted serve path"))

        elif name == "convert_element_type":
            src = eqn.invars[0].aval.dtype
            dst = eqn.params.get("new_dtype")
            if src in _SMALL_FLOATS and dst == jnp.float32:
                file, line = _site(eqn)
                if file not in UPCAST_ALLOWLIST:
                    out.append(Violation(
                        rule="f32-upcast-allowlist", target=target.name,
                        file=file, line=line, provenance=prov,
                        message=(f"{src} -> float32 upcast outside the "
                                 "allowlisted accumulation sites (route it "
                                 "through a layers/numerics.py helper)")))

        elif name in PRNG_PRIMITIVES and target.deterministic:
            file, line = _site(eqn)
            out.append(Violation(
                rule="determinism", target=target.name, file=file,
                line=line, provenance=prov,
                message=(f"PRNG primitive {name} on a deterministic serve "
                         "path")))

        elif name in COLLECTIVE_PRIMITIVES and reproducible:
            axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
            if not isinstance(axes, tuple):
                axes = (axes,)
            if "model" in axes:
                file, line = _site(eqn)
                out.append(Violation(
                    rule="determinism", target=target.name, file=file,
                    line=line, provenance=prov,
                    message=(f"model-axis collective {name} on a "
                             "bitwise-reproducible family (serve_rules_for "
                             "must keep ssm/hybrid off the model axis)")))

        elif name == "sharding_constraint":
            aval = eqn.invars[0].aval
            sharding = eqn.params.get("sharding")
            spec = getattr(sharding, "spec", None)
            if spec is None:
                continue
            entries = _norm_spec(spec, aval.ndim)
            if reproducible and _mentions_model(entries):
                file, line = _site(eqn)
                out.append(Violation(
                    rule="determinism", target=target.name, file=file,
                    line=line, provenance=prov,
                    message=("model-axis sharding constraint "
                             f"{entries} on a bitwise-reproducible family")))
            expected = kv_specs.get(tuple(aval.shape))
            if expected is not None:
                seen_kv_constraint = True
                if entries != expected:
                    file, line = _site(eqn)
                    out.append(Violation(
                        rule="kv-constraint-coverage", target=target.name,
                        file=file, line=line, provenance=prov,
                        message=(f"KV constraint {entries} on shape "
                                 f"{tuple(aval.shape)} does not match the "
                                 f"serve_rules_for table ({expected})")))

    if kv_specs and target.mesh is not None and not seen_kv_constraint:
        out.append(Violation(
            rule="kv-constraint-coverage", target=target.name, file="",
            line=0, provenance="<no sharding_constraint found>",
            message=("no sharding_constraint on any KV-cache-shaped value — "
                     "the cache layout is unpinned and GSPMD may reshard "
                     "the donated buffer every step")))

    if target.donate:
        out.extend(_check_donation(target, closed))
    return out


def audit_targets(targets) -> List[Violation]:
    out: List[Violation] = []
    for t in targets:
        out.extend(audit_target(t))
    return out
