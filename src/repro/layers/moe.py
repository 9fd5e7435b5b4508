"""Mixture-of-Experts layer: top-k router + capacity scatter dispatch.

Dispatch design (EP-friendly, dry-run shardable):

  1. router logits ``(T, E)`` → top-k expert ids + softmax gates;
  2. each (token, choice) claims a slot in its expert's capacity buffer —
     slot rank computed by a cumsum over the one-hot assignment matrix
     (linear in T·E, *not* the quadratic GShard (T, E, C) dispatch einsum);
  3. tokens scatter (``.at[].add`` — differentiable) into ``(E, C, d)``;
     with experts sharded over the ``model`` axis this scatter IS the
     all-to-all (XLA SPMD inserts it);
  4. dense per-expert SwiGLU via batched einsum over the expert axis;
  5. gather back + gate-weighted combine (the token-side MOA: k operands).

Tokens over capacity are dropped (standard capacity-factor semantics); the
auxiliary load-balancing loss (Switch §2.2 style) is returned so trainers
can regularize the router.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.layers.common import Params, dense_init
from repro.layers.numerics import einsum_f32, silu_f32
from repro.moa import active_strategy
from repro.tracing import MOE, layer_scope

__all__ = ["init_moe", "moe_forward"]


def init_moe(rng, *, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.float32) -> Params:
    kr, kg, ku, kd = jax.random.split(rng, 4)
    return {
        "router": dense_init(kr, (d_model, n_experts), dtype, fan_in=d_model),
        "w_gate": dense_init(kg, (n_experts, d_model, d_ff), dtype, fan_in=d_model),
        "w_up": dense_init(ku, (n_experts, d_model, d_ff), dtype, fan_in=d_model),
        "w_down": dense_init(kd, (n_experts, d_ff, d_model), dtype, fan_in=d_ff),
    }


@layer_scope(MOE)
def moe_forward(params: Params, x, *, n_experts: int, top_k: int,
                capacity_factor: float = 1.25, group_size: int = 4096,
                compute_dtype=jnp.bfloat16,
                strategy=None) -> Tuple[jax.Array, jax.Array]:
    """Apply the MoE to ``x: (B, S, d)``. Returns ``(y, aux_loss)``.

    GShard-style grouping: tokens are split into G groups of ``group_size``
    and capacity applies per group. This keeps the slot-rank cumsum local
    (a (group, E) tensor instead of a (T, E) global sequential cumsum —
    at 1M train tokens the global version is both 0.5 TB and a serial
    dependency chain; grouped, it is embarrassingly parallel over data
    shards).

    ``strategy`` (``cfg.moa_for("moe")``; anything :func:`repro.moa.resolve`
    accepts) schedules the expert d/d_ff contractions — vmapped over the
    expert axis since each expert has its own weights — and the token-side
    top-k combine. ``None`` with no active scope keeps the einsum paths.
    """
    B, S, d = x.shape
    T = B * S
    G = max(T // group_size, 1)
    while T % G:
        G -= 1
    tg = T // G                                                    # tokens/group
    xt = x.reshape(G, tg, d).astype(compute_dtype)
    strat = active_strategy(strategy)

    def expert_dot(spec, operands, weights):
        """Per-expert contraction ``(G, E, C, a) x (E, a, b)`` → (G, E, C, b).

        Each expert owns its weight matrix, so the strategy's 2-D ``dot``
        is vmapped over the expert axis (jnp scan and Pallas kernels both
        batch cleanly under vmap).
        """
        if strat is None:
            return einsum_f32(spec, operands,
                              weights.astype(compute_dtype),
                              out_dtype=compute_dtype)
        return jax.vmap(
            lambda xe, we: strat.dot(xe, we.astype(compute_dtype),
                                     out_dtype=compute_dtype),
            in_axes=(1, 0), out_axes=1)(operands, weights)

    # --- routing -------------------------------------------------------------
    if strat is None:
        logits = jnp.einsum("gtd,de->gte", xt,
                            params["router"].astype(compute_dtype)) \
            .astype(jnp.float32)
    else:
        logits = strat.dot(xt, params["router"].astype(compute_dtype),
                           out_dtype=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                        # (G, tg, E)
    gate_vals, expert_ids = jax.lax.top_k(probs, top_k)            # (G, tg, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # --- per-group slot assignment --------------------------------------------
    capacity = max(int(tg * top_k / n_experts * capacity_factor), 1)
    flat_ids = expert_ids.reshape(G, tg * top_k)                   # (G, tk)
    onehot = jax.nn.one_hot(flat_ids, n_experts, dtype=jnp.int32)  # (G, tk, E)
    ranks = jnp.cumsum(onehot, axis=1) - onehot
    slot = jnp.sum(ranks * onehot, axis=-1)                        # (G, tk)
    keep = slot < capacity

    # --- dispatch (the all-to-all under EP sharding) ---------------------------
    xrep = jnp.repeat(xt, top_k, axis=1)                           # (G, tk, d)
    safe_slot = jnp.where(keep, slot, 0)
    contrib = jnp.where(keep[..., None], xrep, 0).astype(compute_dtype)
    buf = jnp.zeros((G, n_experts, capacity, d), compute_dtype)
    g_idx = jnp.arange(G)[:, None]
    buf = buf.at[g_idx, flat_ids, safe_slot].add(contrib)

    # --- expert compute ----------------------------------------------------------
    gates = expert_dot("gecd,edf->gecf", buf, params["w_gate"])
    ups = expert_dot("gecd,edf->gecf", buf, params["w_up"])
    h = silu_f32(gates, out_dtype=compute_dtype) * ups
    out_buf = expert_dot("gecf,efd->gecd", h, params["w_down"])

    # --- combine (token-side MOA over k expert outputs) -------------------------
    gathered = out_buf[g_idx, flat_ids, safe_slot]                 # (G, tk, d)
    gathered = jnp.where(keep[..., None], gathered, 0)
    weighted = gathered * gate_vals.reshape(G, tg * top_k, 1) \
        .astype(compute_dtype)
    weighted = weighted.reshape(G, tg, top_k, d)
    if strat is None:
        y = jnp.sum(weighted, axis=2)
    else:
        y = strat.sum(weighted, axis=2).astype(compute_dtype)

    # --- Switch-style load-balance auxiliary loss --------------------------------
    density = jnp.mean(
        jax.nn.one_hot(expert_ids[..., 0], n_experts, dtype=jnp.float32),
        axis=(0, 1))
    router_prob = jnp.mean(probs, axis=(0, 1))
    aux = n_experts * jnp.sum(density * router_prob)

    return y.reshape(B, S, d), aux
