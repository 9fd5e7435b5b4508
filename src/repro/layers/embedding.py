"""Token embedding + (optionally tied) output projection."""

from __future__ import annotations

import jax.numpy as jnp

from repro.layers.common import Params, truncated_normal_init
from repro.tracing import EMBED, LOGITS, layer_scope

__all__ = ["init_embedding", "embed", "unembed"]


def init_embedding(rng, vocab: int, d_model: int, *, tie: bool = True,
                   dtype=jnp.float32) -> Params:
    import jax

    ke, ku = jax.random.split(rng)
    p = {"table": truncated_normal_init(ke, (vocab, d_model), 0.02, dtype)}
    if not tie:
        p["unembed"] = truncated_normal_init(ku, (vocab, d_model),
                                             d_model ** -0.5, dtype)
    return p


@layer_scope(EMBED)
def embed(params: Params, token_ids, *, compute_dtype=jnp.bfloat16):
    """Lookup: (B, S) int -> (B, S, d). A gather — the one-hot matmul MOA
    degenerate case (all-but-one operand zero; SCM removes them for free)."""
    return params["table"].astype(compute_dtype)[token_ids]


@layer_scope(LOGITS)
def unembed(params: Params, x, *, compute_dtype=jnp.bfloat16):
    """Logits: (B, S, d) -> (B, S, V). Vocab-dim output — shard over model
    axis and keep the softmax vocab-parallel (see losses.py)."""
    table = params.get("unembed", params["table"]).astype(compute_dtype)
    return jnp.einsum("bsd,vd->bsv", x.astype(compute_dtype), table,
                      preferred_element_type=jnp.float32)
