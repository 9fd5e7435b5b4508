"""Plain float32 reference of the served Mamba-2 language model
(arXiv:2405.21060): tied embedding, ``n_layers`` residual Mamba-2 layers
``h += mixer(rms(h))``, final RMS norm, logits against the embedding."""

from __future__ import annotations

import jax

from bench.reference import ssm
from bench.reference.common import near_one, normal, rms_norm


def init(key, cfg):
    """The benchmark's weights for one seed, in the serving layout."""
    ks = jax.random.split(key, 3)
    return {
        "embed": {"table": normal(ks[0], (cfg["vocab"], cfg["d_model"]),
                                  cfg["d_model"] ** -0.5)},
        "layers": ssm.init_layers(ks[1], cfg, cfg["n_layers"]),
        "final_norm": {"scale": near_one(ks[2], (cfg["d_model"],))},
    }


def unembedding(params):
    return params["embed"]["table"]


def hidden(params, tokens, cfg, quant=None):
    """Final normed hidden state ``(B, S, d_model)`` of ``tokens (B, S)``."""
    h = params["embed"]["table"][tokens]
    h = ssm.stack(params["layers"], h, cfg, quant)
    return rms_norm(params["final_norm"]["scale"], h)
