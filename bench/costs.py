"""Operations and bytes that the served work needs, from the configuration
and the traffic alone: the numerators of the roofline shares and of the
model-FLOP utilization. They count the work, not how the program does it:
what an implementation computes or moves beyond this (padding, idle slots,
weight copies, recomputation) is not counted.

The SSD terms follow ``launch/costing.py`` (decode ``4*H*P*N + 2*H*N``);
a projection is ``2*m*k*n``.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline bound: the larger of compute time and memory time."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


# -- kernels -----------------------------------------------------------------

def paged_attention_call(cfg: dict, contexts, block_size: int):
    """(flops, bytes) of one paged-attention decode call over the live slots
    whose attended lengths (cursor + 1) are ``contexts``: the live bf16 K and
    V pages, one query and one output row per slot, and ``QK^T`` and ``PV``."""
    H, Hk, D = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    flops = sum(4 * H * D * n for n in contexts)
    pages = sum(-(-n // block_size) for n in contexts)
    nbytes = pages * block_size * Hk * D * BF16 * 2 \
        + len(contexts) * 2 * H * D * BF16
    return flops, nbytes


def shared_block_projections(cfg: dict):
    """``(k, n)`` of every projection in one application of the shared
    attention + SwiGLU block (the ``dot_moa`` call sites)."""
    d, F = cfg["d_model"], cfg["d_ff"]
    H, Hk, D = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return [(d, H * D), (d, Hk * D), (d, Hk * D), (H * D, d),
            (d, F), (d, F), (F, d)]


def n_shared_applications(cfg: dict) -> int:
    every = cfg.get("attn_every", 0)
    return cfg["n_layers"] // every if every else 0


# -- model -------------------------------------------------------------------

def _ssm_sizes(cfg: dict):
    d_inner = cfg["expand"] * cfg["d_model"]
    H = d_inner // cfg["headdim"]
    gn = cfg["n_groups"] * cfg["d_state"]
    return d_inner, H, gn


def matmul_params_per_token(cfg: dict) -> int:
    """Weights every token multiplies by, without the unembedding."""
    d = cfg["d_model"]
    d_inner, H, gn = _ssm_sizes(cfg)
    mixer = d * (2 * d_inner + 2 * gn + H) + d_inner * d
    n = cfg["n_layers"] * mixer
    apps = n_shared_applications(cfg)
    if apps:
        n += apps * sum(k * m for k, m in shared_block_projections(cfg))
    return n


def token_flops(cfg: dict, position: int, *, logits: bool) -> float:
    """Model FLOPs of one token at ``position`` (0-based): projections, the
    depthwise convolution, the SSD state update and read-out, attention over
    ``position + 1`` keys in every shared-block application, and the
    unembedding where the token's logits are needed."""
    d = cfg["d_model"]
    d_inner, H, gn = _ssm_sizes(cfg)
    P, N = cfg["headdim"], cfg["d_state"]
    f = 2 * matmul_params_per_token(cfg)
    f += cfg["n_layers"] * (2 * cfg["d_conv"] * (d_inner + 2 * gn)
                            + 4 * H * P * N + 2 * H * N)
    apps = n_shared_applications(cfg)
    if apps:
        f += apps * 4 * cfg["n_heads"] * cfg["head_dim"] * (position + 1)
    if logits:
        f += 2 * d * cfg["vocab"]
    return f


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Model FLOPs of a prefill: every prompt token, logits for the last
    (the sum of :func:`token_flops` over positions, in closed form)."""
    base = token_flops(cfg, -1, logits=False)
    per_key = token_flops(cfg, 0, logits=False) - base
    return prompt_len * base + per_key * prompt_len * (prompt_len + 1) / 2 \
        + 2 * cfg["d_model"] * cfg["vocab"]
