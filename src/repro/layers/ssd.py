"""Mamba-2 SSD (state-space duality) block — chunked scan formulation.

The SSD chunked algorithm (Dao & Gu, arXiv:2405.21060 §6) is the clearest
LM-scale instance of the paper's §3.1 strategy *succeeding* on TPU: the
sequence-length reduction (an S-operand MOA per state dimension) is split
into chunks of ``ssd_chunk`` operands — intra-chunk handled by a spatial
(MXU) "adder tree" (the quadratic einsum), inter-chunk handled by a *serial
accumulator* (``lax.scan`` carrying the SSM state). ``ssd_chunk`` is the
cluster size ``n_c``; the roofline benchmarks sweep it.

Layout notes: heads are a leading axis (sharded over ``model``); all decay
arithmetic in f32.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.layers.numerics import (f32_upcast, silu_f32, softplus_f32,
                                   sum_f32)

from repro.layers.common import Params, dense_init, init_rms_norm, rms_norm
from repro.tracing import SSD, layer_scope

__all__ = [
    "init_mamba2_block", "mamba2_forward", "mamba2_decode",
    "init_ssm_state", "ssd_chunked",
]


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def _segsum(a):
    """Within-chunk pairwise decay sums: out[..., l, s] = sum_{s<i<=l} a_i.

    ``a: (..., L)`` → ``(..., L, L)`` lower-triangular (else -inf).
    """
    L = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, a, b, c, *, chunk: int, h0=None):
    """SSD: y_t = C_t^T h_t,  h_t = exp(a_t) h_{t-1} + B_t x_t^T.

    Args:
      x: (B, S, H, P)   per-head inputs (already dt-scaled).
      a: (B, S, H)      per-step log decay (dt * A, negative).
      b: (B, S, H, N)   input maps  (groups already broadcast to heads).
      c: (B, S, H, N)   output maps.
      chunk: intra/inter split — the serialized-MOA cluster size.
      h0: optional initial state (B, H, P, N).

    Returns: (y, h_last) with y (B, S, H, P), h_last (B, H, P, N).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    if S % chunk:
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))  # exp(0)=1 decay, x=0: no-op
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = x.shape[1]
    n_chunks = Sp // chunk

    def to_chunks(t):
        return t.reshape((B, n_chunks, chunk) + t.shape[2:])

    xc, ac, bc, cc = map(to_chunks, (x, f32_upcast(a), b, c))
    a_cs = jnp.cumsum(ac, axis=2)                      # (B, C, L, H)

    # 1. intra-chunk (spatial tree / MXU quadratic term)
    Lmat = jnp.exp(_segsum(jnp.moveaxis(ac, -1, 2)))   # (B, C, H, L, L)
    y_diag = jnp.einsum("bclhn,bcshn,bchls,bcshp->bclhp",
                        f32_upcast(cc), f32_upcast(bc),
                        Lmat, f32_upcast(xc))

    # 2. per-chunk end states
    decay_to_end = jnp.exp(a_cs[:, :, -1:, :] - a_cs)  # (B, C, L, H)
    states = jnp.einsum("bclhn,bclh,bclhp->bchpn",
                        f32_upcast(bc), decay_to_end,
                        f32_upcast(xc))                # (B, C, H, P, N)

    # 3. inter-chunk recurrence — the serial accumulator (§3.1)
    chunk_decay = jnp.exp(a_cs[:, :, -1, :])           # (B, C, H)
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    def step(h_prev, xs):
        st, dec = xs
        h_next = h_prev * dec[..., None, None] + st
        return h_next, h_prev

    (h_last, h_prevs) = lax.scan(
        step, f32_upcast(h0),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)              # (B, C, H, P, N)

    # 4. state → output within each chunk
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp",
                       f32_upcast(cc), h_prevs, jnp.exp(a_cs))
    y = (y_diag + y_off).reshape(B, Sp, H, P)[:, :S]
    return y.astype(x.dtype), h_last


# ---------------------------------------------------------------------------
# Mamba-2 block (in_proj → conv → SSD → gated norm → out_proj)
# ---------------------------------------------------------------------------


def init_mamba2_block(rng, *, d_model: int, d_state: int, headdim: int,
                      n_groups: int = 1, d_conv: int = 4, expand: int = 2,
                      dtype=jnp.float32) -> Params:
    d_inner = expand * d_model
    n_heads = d_inner // headdim
    conv_dim = d_inner + 2 * n_groups * d_state
    k_in, k_conv, k_out, k_dt = jax.random.split(rng, 4)
    d_in_proj = 2 * d_inner + 2 * n_groups * d_state + n_heads
    # dt bias: softplus^-1 of dt in [1e-3, 1e-1] (mamba2 default init)
    u = jax.random.uniform(k_dt, (n_heads,), jnp.float32)
    dt_init = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))
    return {
        "in_proj": dense_init(k_in, (d_model, d_in_proj), dtype, fan_in=d_model),
        "conv_w": dense_init(k_conv, (d_conv, conv_dim), dtype, fan_in=d_conv),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.arange(1, n_heads + 1, dtype=jnp.float32)),
        "dt_bias": dt_bias.astype(jnp.float32),
        "d_skip": jnp.ones((n_heads,), jnp.float32),
        "gate_norm": init_rms_norm(d_inner, dtype),
        "out_proj": dense_init(k_out, (d_inner, d_model), dtype, fan_in=d_inner),
    }


def _split_in_proj(z_xbc_dt, *, d_inner, n_groups, d_state, n_heads):
    zs = d_inner
    xs = d_inner
    bs = n_groups * d_state
    z, xp, b, c, dt = jnp.split(
        z_xbc_dt, [zs, zs + xs, zs + xs + bs, zs + xs + 2 * bs], axis=-1)
    return z, xp, b, c, dt


def _causal_depthwise_conv(x, w, b, hist=None):
    """x (B, S, C), w (K, C): depthwise causal conv (pad left K-1).

    ``hist`` (B, K-1, C), when given, replaces the zero left-pad with the
    last K-1 conv inputs of an earlier segment — the chunked-prefill
    continuation. The summation order is identical either way (a fixed
    K-term sum per position), so a history-padded chunk is bit-identical
    to the same positions inside one long conv.
    """
    K = w.shape[0]
    if hist is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([hist.astype(x.dtype), x], axis=1)
    # sum_k w[k] * x[t - (K-1) + k] — small K, unrolled (K=4)
    y = sum(xp[:, k:k + x.shape[1], :] * w[k] for k in range(K))
    return y + b


@layer_scope(SSD)
def mamba2_forward(params: Params, x, *, d_state: int, headdim: int,
                   n_groups: int = 1, expand: int = 2, ssd_chunk: int = 256,
                   compute_dtype=jnp.bfloat16,
                   initial_state=None) -> Tuple[jax.Array, jax.Array]:
    """Mamba-2 mixer over ``x: (B, S, d_model)`` → ``(y, last_state)``.

    ``initial_state`` is either the legacy SSM state array ``(B, H, P, N)``
    or a dict ``{"h", "conv"}`` (the per-layer slice of
    :func:`init_ssm_state`) — the dict form also seeds the depthwise conv
    with the previous segment's last ``d_conv - 1`` inputs, which is what
    makes chunked prefill a bit-identical continuation.
    """
    B, S, d_model = x.shape
    d_inner = expand * d_model
    n_heads = d_inner // headdim

    conv_hist = None
    if isinstance(initial_state, dict):
        conv_hist = initial_state["conv"]
        initial_state = initial_state["h"]

    proj = x.astype(compute_dtype) @ params["in_proj"].astype(compute_dtype)
    z, xp, b, c, dt = _split_in_proj(
        proj, d_inner=d_inner, n_groups=n_groups, d_state=d_state,
        n_heads=n_heads)

    conv_in = jnp.concatenate([xp, b, c], axis=-1)
    conv_out = _causal_depthwise_conv(
        conv_in, params["conv_w"].astype(compute_dtype),
        params["conv_b"].astype(compute_dtype), hist=conv_hist)
    conv_out = silu_f32(conv_out, out_dtype=compute_dtype)
    xp, b, c = jnp.split(conv_out, [d_inner, d_inner + n_groups * d_state],
                         axis=-1)

    dt = softplus_f32(dt, bias=params["dt_bias"])                     # (B,S,H)
    A = -jnp.exp(params["a_log"])                                     # (H,)
    a = dt * A                                                        # (B,S,H)

    xh = xp.reshape(B, S, n_heads, headdim)
    heads_per_group = n_heads // n_groups
    bh = jnp.repeat(b.reshape(B, S, n_groups, d_state), heads_per_group, axis=2)
    ch = jnp.repeat(c.reshape(B, S, n_groups, d_state), heads_per_group, axis=2)

    x_dt = xh * dt[..., None].astype(xh.dtype)
    y, h_last = ssd_chunked(x_dt, a, bh, ch, chunk=ssd_chunk, h0=initial_state)
    y = y + xh * params["d_skip"][None, None, :, None].astype(y.dtype)

    y = y.reshape(B, S, d_inner)
    y = rms_norm(params["gate_norm"],
                 (f32_upcast(y)
                  * silu_f32(z)).astype(compute_dtype))
    return y @ params["out_proj"].astype(compute_dtype), h_last


def init_ssm_state(batch: int, *, d_model: int, d_state: int, headdim: int,
                   n_groups: int = 1, d_conv: int = 4, expand: int = 2):
    d_inner = expand * d_model
    n_heads = d_inner // headdim
    conv_dim = d_inner + 2 * n_groups * d_state
    return {
        "h": jnp.zeros((batch, n_heads, headdim, d_state), jnp.float32),
        "conv": jnp.zeros((batch, d_conv - 1, conv_dim), jnp.bfloat16),
    }


@layer_scope(SSD)
def mamba2_decode(params: Params, x, state, *, d_state: int, headdim: int,
                  n_groups: int = 1, expand: int = 2,
                  compute_dtype=jnp.bfloat16):
    """Single-token step: ``x (B, 1, d_model)``, recurrent state update.

    The decode recurrence *is* the paper's serial accumulator with n_c = 1:
    one MAC per state element per step, zero working set beyond the state.
    """
    B, _, d_model = x.shape
    d_inner = expand * d_model
    n_heads = d_inner // headdim

    proj = x[:, 0].astype(compute_dtype) @ params["in_proj"].astype(compute_dtype)
    z, xp, b, c, dt = _split_in_proj(
        proj, d_inner=d_inner, n_groups=n_groups, d_state=d_state,
        n_heads=n_heads)

    conv_in = jnp.concatenate([xp, b, c], axis=-1)      # (B, conv_dim)
    conv_hist = jnp.concatenate(
        [state["conv"].astype(compute_dtype), conv_in[:, None]], axis=1)
    w = params["conv_w"].astype(compute_dtype)          # (K, C)
    conv_out = sum_f32(conv_hist * w[None], axis=1,
                       out_dtype=compute_dtype) + params["conv_b"] \
        .astype(compute_dtype)
    conv_out = silu_f32(conv_out, out_dtype=compute_dtype)
    xp, b, c = jnp.split(conv_out, [d_inner, d_inner + n_groups * d_state],
                         axis=-1)

    dt = softplus_f32(dt, bias=params["dt_bias"])                     # (B,H)
    A = -jnp.exp(params["a_log"])
    dA = jnp.exp(dt * A)                                              # (B,H)

    xh = f32_upcast(xp.reshape(B, n_heads, headdim))
    heads_per_group = n_heads // n_groups
    bh = f32_upcast(
        jnp.repeat(b.reshape(B, n_groups, d_state), heads_per_group, axis=1))
    ch = f32_upcast(
        jnp.repeat(c.reshape(B, n_groups, d_state), heads_per_group, axis=1))

    h = state["h"] * dA[..., None, None] + jnp.einsum(
        "bh,bhp,bhn->bhpn", dt, xh, bh)
    y = jnp.einsum("bhn,bhpn->bhp", ch, h) + xh * params["d_skip"][None, :, None]

    y = y.reshape(B, d_inner)
    y = rms_norm(params["gate_norm"],
                 (y * silu_f32(z)).astype(compute_dtype))
    out = y @ params["out_proj"].astype(compute_dtype)
    new_state = {"h": h, "conv": conv_hist[:, 1:].astype(state["conv"].dtype)}
    return out[:, None], new_state
