"""Plain float32 Mamba-2 mixer (arXiv:2405.21060): in-projection, causal
depthwise convolution, the selective state-space recurrence step by step,
gated RMS norm, out-projection. The recurrence is written as the
recurrence, one position at a time, not as the chunked SSD algorithm."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from bench.reference.common import (HIGHEST, matmul, near_one, normal,
                                    rms_norm, silu)


def sizes(cfg):
    d_inner = cfg["expand"] * cfg["d_model"]
    n_heads = d_inner // cfg["headdim"]
    gn = cfg["n_groups"] * cfg["d_state"]
    return d_inner, n_heads, gn


def init_mixer(key, cfg, n_layers):
    """Weights of ``n_layers`` stacked mixers, in the serving layout."""
    d = cfg["d_model"]
    K = cfg["d_conv"]
    d_inner, H, gn = sizes(cfg)
    conv_dim = d_inner + 2 * gn
    ks = jax.random.split(key, 8)
    L = (n_layers,)
    lo, hi = math.log(1e-3), math.log(1e-1)
    dt = jnp.exp(lo + (hi - lo) * jax.random.uniform(ks[4], L + (H,)))
    return {
        "in_proj": normal(ks[0], L + (d, 2 * d_inner + 2 * gn + H), d ** -0.5),
        "conv_w": normal(ks[1], L + (K, conv_dim), K ** -0.5),
        "conv_b": normal(ks[2], L + (conv_dim,), 0.1),
        "a_log": jnp.log(jax.random.uniform(ks[3], L + (H,), minval=1.0,
                                            maxval=16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d_skip": jax.random.uniform(ks[5], L + (H,), minval=0.5,
                                     maxval=1.5),
        "gate_norm": {"scale": near_one(ks[6], L + (d_inner,))},
        "out_proj": normal(ks[7], L + (d_inner, d), d_inner ** -0.5),
    }


def mixer(p, x, cfg, quant=None):
    """One mixer over ``x (B, S, d_model)`` from a zero state."""
    B, S, _ = x.shape
    d_inner, H, gn = sizes(cfg)
    P, N, G = cfg["headdim"], cfg["d_state"], cfg["n_groups"]
    K = p["conv_w"].shape[0]
    proj = matmul(x, p["in_proj"], quant)
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:2 * d_inner + 2 * gn]
    dt = proj[..., 2 * d_inner + 2 * gn:]
    # causal depthwise convolution: out_t = sum_k w_k x_{t-K+1+k} + b
    xpad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xpad[:, k:k + S] * p["conv_w"][k] for k in range(K))
    xbc = silu(conv + p["conv_b"])
    xs = xbc[..., :d_inner].reshape(B, S, H, P)
    hpg = H // G
    bm = jnp.repeat(xbc[..., d_inner:d_inner + gn].reshape(B, S, G, N), hpg,
                    axis=2)
    cm = jnp.repeat(xbc[..., d_inner + gn:].reshape(B, S, G, N), hpg, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                 # (B, S, H)
    decay = jnp.exp(dt * -jnp.exp(p["a_log"]))              # (B, S, H)

    def step(h, inp):
        x_t, b_t, c_t, dt_t, a_t = inp
        h = h * a_t[..., None, None] + jnp.einsum(
            "bh,bhp,bhn->bhpn", dt_t, x_t, b_t, precision=HIGHEST)
        y = jnp.einsum("bhn,bhpn->bhp", c_t, h, precision=HIGHEST)
        return h, y

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt, decay))
    _, y = lax.scan(step, jnp.zeros((B, H, P, N), jnp.float32), seq,
                    unroll=8)
    y = jnp.moveaxis(y, 0, 1) + xs * p["d_skip"][:, None]
    y = y.reshape(B, S, d_inner) * silu(z)
    y = rms_norm(p["gate_norm"]["scale"], y)
    return matmul(y, p["out_proj"], quant)


def stack(layers, h, cfg, quant=None):
    """Residual Mamba-2 layers ``h += mixer(rms(h))`` over stacked weights."""

    def body(h, layer):
        hn = rms_norm(layer["norm"]["scale"], h)
        return h + mixer(layer["mixer"], hn, cfg, quant), None

    h, _ = lax.scan(body, h, layers)
    return h


def init_layers(key, cfg, n_layers):
    k1, k2 = jax.random.split(key)
    return {"norm": {"scale": near_one(k1, (n_layers, cfg["d_model"]))},
            "mixer": init_mixer(k2, cfg, n_layers)}
