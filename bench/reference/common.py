"""Plain float32 building blocks of the references: no kernels, no cache, no
batching tricks. Every contraction runs at ``Precision.HIGHEST`` (on a TPU a
float32 matmul is otherwise computed in bfloat16 passes).

``quant="int8"`` turns each weight contraction into a symmetric int8 x int8
product (per-row activation scales, per-output-column weight scales) with an
int32 accumulator: the lower-precision control of the correctness check.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).astype(jnp.int8), scale


def matmul(x, w, quant=None):
    """``x (..., k) @ w (k, n)`` in float32; with ``quant="int8"`` the
    operands are first rounded to int8 (per-row activation and per-column
    weight scales) and accumulated in int32."""
    if quant is None:
        return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)
    if quant != "int8":
        raise ValueError(f"unknown quant {quant!r}")
    xq, xs = _int8(x, -1)
    wq, ws = _int8(w, 0)
    acc = jnp.einsum("...k,kn->...n", xq, wq,
                     preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def rms_norm(scale, x, eps=1e-6):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * scale


def silu(x):
    return x * jax.nn.sigmoid(x)


def normal(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


def near_one(key, shape):
    """Norm scales around 1, so that the check sees every scale."""
    return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def rope(x, positions, theta):
    """Rotate ``x (B, S, H, D)``: pairs are (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, q_block=256):
    """Softmax attention over ``q (B, S, H, D)``, ``k, v (B, S, Hk, D)``,
    causal, in blocks of query rows so that the scores fit."""
    B, S, H, D = q.shape
    Hk = k.shape[2]
    G = H // Hk
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    q_block = min(q_block, S)
    n = -(-S // q_block)
    qp = jnp.pad(q, ((0, 0), (0, n * q_block - S), (0, 0), (0, 0)))
    qb = jnp.moveaxis(qp.reshape(B, n, q_block, H, D), 1, 0)

    def block(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                       precision=HIGHEST) / math.sqrt(D)
        rows = i * q_block + jnp.arange(q_block)
        mask = jnp.arange(S)[None, :] <= rows[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    o = lax.map(block, (jnp.arange(n), qb))
    return jnp.moveaxis(o, 0, 1).reshape(B, n * q_block, H, D)[:, :S]


def logits_at(h, unembed, positions, quant=None):
    """Next-token logits ``(B, T, V)`` at ``positions (B, T)`` of the final
    normed hidden state ``h (B, S, d)``; ``unembed`` is ``(V, d)``."""
    hp = jnp.take_along_axis(h, positions[..., None], axis=1)
    return matmul(hp, unembed.T, quant)


def gaps(ref_logits, tokens):
    """How far the reference's logit of each token in ``tokens (B, T)`` lies
    below the reference's best logit at that position."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], axis=-1)[..., 0]
    return best - got
