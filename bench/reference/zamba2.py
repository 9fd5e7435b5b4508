"""Plain float32 reference of the served zamba2 hybrid.

The architecture as this repository serves it (arXiv:2411.15242, with the
simplifications that ``docs/architecture.md`` states): ``n_layers`` Mamba-2
layers; after each ``attn_every`` of them one shared attention + SwiGLU block
with rotary positions, whose two input RMS norms are the application's own;
the remaining layers at the end; final RMS norm; an untied unembedding.
Departures from the published Zamba2: the shared block reads the residual
stream alone (not its concatenation with the embeddings), and per-application
norms stand in for the per-application LoRA adapters.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import ssm
from bench.reference.common import (causal_attention, matmul, near_one,
                                    normal, rms_norm, rope, silu)


def n_apps(cfg):
    return cfg["n_layers"] // cfg["attn_every"]


def init(key, cfg):
    """The benchmark's weights for one seed, in the serving layout."""
    d, V, F = cfg["d_model"], cfg["vocab"], cfg["d_ff"]
    H, Hk, D = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    A = n_apps(cfg)
    ks = jax.random.split(key, 14)
    return {
        "embed": {"table": normal(ks[0], (V, d), 0.02),
                  "unembed": normal(ks[1], (V, d), d ** -0.5)},
        "layers": ssm.init_layers(ks[2], cfg, cfg["n_layers"]),
        "shared_attn": {"wq": normal(ks[3], (d, H * D), d ** -0.5),
                        "wk": normal(ks[4], (d, Hk * D), d ** -0.5),
                        "wv": normal(ks[5], (d, Hk * D), d ** -0.5),
                        "wo": normal(ks[6], (H * D, d), (H * D) ** -0.5)},
        "shared_mlp": {"w_gate": normal(ks[7], (d, F), d ** -0.5),
                       "w_up": normal(ks[8], (d, F), d ** -0.5),
                       "w_down": normal(ks[9], (F, d), F ** -0.5)},
        "app_norms": {"attn": {"scale": near_one(ks[10], (A, d))},
                      "mlp": {"scale": near_one(ks[11], (A, d))}},
        "final_norm": {"scale": near_one(ks[12], (d,))},
    }


def unembedding(params):
    return params["embed"]["unembed"]


def _shared_block(p, norms, h, cfg, quant):
    B, S, _ = h.shape
    H, Hk, D = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    pos = jnp.arange(S)
    hn = rms_norm(norms["attn"], h)
    a = p["shared_attn"]
    q = rope(matmul(hn, a["wq"], quant).reshape(B, S, H, D), pos,
             cfg["rope_theta"])
    k = rope(matmul(hn, a["wk"], quant).reshape(B, S, Hk, D), pos,
             cfg["rope_theta"])
    v = matmul(hn, a["wv"], quant).reshape(B, S, Hk, D)
    o = causal_attention(q, k, v).reshape(B, S, H * D)
    h = h + matmul(o, a["wo"], quant)
    hn = rms_norm(norms["mlp"], h)
    m = p["shared_mlp"]
    g = silu(matmul(hn, m["w_gate"], quant)) * matmul(hn, m["w_up"], quant)
    return h + matmul(g, m["w_down"], quant)


def hidden(params, tokens, cfg, quant=None):
    """Final normed hidden state ``(B, S, d_model)`` of ``tokens (B, S)``."""
    h = params["embed"]["table"][tokens]
    every = cfg["attn_every"]
    layers = params["layers"]
    for i in range(n_apps(cfg)):
        group = jax.tree.map(lambda a: a[i * every:(i + 1) * every], layers)
        h = ssm.stack(group, h, cfg, quant)
        norms = {k: v["scale"][i] for k, v in params["app_norms"].items()}
        h = _shared_block(params, norms, h, cfg, quant)
    tail = jax.tree.map(lambda a: a[n_apps(cfg) * every:], layers)
    if cfg["n_layers"] > n_apps(cfg) * every:
        h = ssm.stack(tail, h, cfg, quant)
    return rms_norm(params["final_norm"]["scale"], h)
