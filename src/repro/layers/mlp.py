"""Feed-forward blocks: SwiGLU (llama family) and GELU (encoder family).

The d_ff contraction of ``w_down`` is the widest MOA in most dense archs
(llama3-405b: 53 248 operands) — it routes through the model's MOA
strategy (``cfg.moa_for("mlp")``) via :func:`repro.layers.linear.project`.
``strategy`` accepts anything :func:`repro.moa.resolve` does (spec string,
strategy instance, legacy ReductionStrategy).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.layers.common import Params, dense_init
from repro.layers.linear import project
from repro.layers.numerics import silu_f32
from repro.tracing import MLP, layer_scope

__all__ = ["init_swiglu", "swiglu", "init_gelu_mlp", "gelu_mlp"]


def init_swiglu(rng, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    kg, ku, kd = jax.random.split(rng, 3)
    return {
        "w_gate": dense_init(kg, (d_model, d_ff), dtype, fan_in=d_model),
        "w_up": dense_init(ku, (d_model, d_ff), dtype, fan_in=d_model),
        "w_down": dense_init(kd, (d_ff, d_model), dtype, fan_in=d_ff),
    }


@layer_scope(MLP)
def swiglu(params: Params, x, *, strategy=None,
           compute_dtype=jnp.bfloat16):
    g = project({"w": params["w_gate"]}, x, strategy=strategy,
                compute_dtype=compute_dtype)
    u = project({"w": params["w_up"]}, x, strategy=strategy,
                compute_dtype=compute_dtype)
    h = silu_f32(g, out_dtype=compute_dtype) * u
    return project({"w": params["w_down"]}, h, strategy=strategy,
                   compute_dtype=compute_dtype)


def init_gelu_mlp(rng, d_model: int, d_ff: int, dtype=jnp.float32) -> Params:
    ki, ko = jax.random.split(rng)
    return {
        "w_in": dense_init(ki, (d_model, d_ff), dtype, fan_in=d_model),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(ko, (d_ff, d_model), dtype, fan_in=d_ff),
        "b_out": jnp.zeros((d_model,), dtype),
    }


@layer_scope(MLP)
def gelu_mlp(params: Params, x, *, strategy=None,
             compute_dtype=jnp.bfloat16):
    h = project({"w": params["w_in"], "b": params["b_in"]}, x,
                strategy=strategy, compute_dtype=compute_dtype)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(compute_dtype)
    return project({"w": params["w_out"], "b": params["b_out"]}, h,
                   strategy=strategy, compute_dtype=compute_dtype)
