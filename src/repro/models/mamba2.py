"""Mamba-2 LM (attention-free SSD stack) — mamba2-370m and friends.

Per layer:  h += mamba2(rms(h)).  No positional encoding (the recurrence
carries order). Decode keeps per-layer (ssm_state, conv_state) — constant
memory in sequence length, which is why long_500k runs for this family.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.layers.common import Params, init_rms_norm, rms_norm
from repro.layers.embedding import embed, init_embedding, unembed
from repro.layers.ssd import (init_mamba2_block, init_ssm_state,
                              mamba2_decode, mamba2_forward)
from repro.models import transformer as dense
from repro.models import verify_common
from repro.parallel import constrain

__all__ = ["init_params", "forward", "init_cache", "prefill",
           "prefill_chunk", "decode_step", "verify_step", "commit_verified"]


#: Static-auditor registration (:mod:`repro.analysis.targets`): the serve
#: callables this family module exposes, its KV stack key (None = no KV),
#: and whether the paged layout / suffix prefill apply. The auditor
#: enumerates targets from this table, so a family module that grows a new
#: serve entry point must declare it here to be covered by CI.
SERVE_AUDIT = {
    "phases": ("prefill", "decode", "verify", "commit"),
    "paged": False,
    "kv_key": None,
    "suffix_prefill": False,
    "prefill_chunk": True,
}


def _init_layer(rng, cfg: ModelConfig) -> Params:
    return {
        "norm": init_rms_norm(cfg.d_model, cfg.pdtype),
        "mixer": init_mamba2_block(
            rng, d_model=cfg.d_model, d_state=cfg.d_state,
            headdim=cfg.headdim, n_groups=cfg.n_groups, d_conv=cfg.d_conv,
            expand=cfg.expand, dtype=cfg.pdtype),
    }


def init_params(rng, cfg: ModelConfig) -> Params:
    ke, kl = jax.random.split(rng)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: _init_layer(k, cfg))(layer_keys)
    return {
        "embed": init_embedding(ke, cfg.vocab, cfg.d_model,
                                tie=cfg.tie_embeddings, dtype=cfg.pdtype),
        "layers": layers,
        "final_norm": init_rms_norm(cfg.d_model, cfg.pdtype),
    }


def _layer_fwd(layer: Params, h, *, cfg: ModelConfig, initial_state=None):
    hn = rms_norm(layer["norm"], h)
    y, h_last = mamba2_forward(
        layer["mixer"], hn, d_state=cfg.d_state, headdim=cfg.headdim,
        n_groups=cfg.n_groups, expand=cfg.expand, ssd_chunk=cfg.ssd_chunk,
        compute_dtype=cfg.cdtype, initial_state=initial_state)
    return h + constrain(y, "batch", "seq", "embed"), h_last


def forward(params: Params, batch: dict, cfg: ModelConfig):
    h = embed(params["embed"], batch["tokens"], compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", "seq", "embed")

    def body(carry, layer):
        out, _ = _layer_fwd(layer, carry, cfg=cfg)
        return out, None

    h, _ = lax.scan(dense._remat(body, cfg), h, params["layers"])
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h, compute_dtype=cfg.cdtype)
    return constrain(logits, "batch", "seq", "vocab")


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    del max_len  # constant-size state: the SSM's whole point
    one = init_ssm_state(batch, d_model=cfg.d_model, d_state=cfg.d_state,
                         headdim=cfg.headdim, n_groups=cfg.n_groups,
                         d_conv=cfg.d_conv, expand=cfg.expand)
    return {
        "layers": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), one),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(params: Params, batch: dict, cfg: ModelConfig, *, max_len: int):
    """Chunked-scan prefill; emits final (ssm, conv) state per layer."""
    del max_len
    h = embed(params["embed"], batch["tokens"], compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", "seq", "embed")
    S = h.shape[1]

    def body(carry, layer):
        out, h_last = _layer_fwd(layer, carry, cfg=cfg)
        # conv state: last (d_conv - 1) conv inputs of this layer. Recompute
        # the projection on the tail positions only (cheap, avoids carrying
        # the full conv stream through the scan).
        hn = rms_norm(layer["norm"], carry)[:, -(cfg.d_conv - 1):]
        proj = hn.astype(cfg.cdtype) @ layer["mixer"]["in_proj"] \
            .astype(cfg.cdtype)
        d_inner = cfg.d_inner
        bs = cfg.n_groups * cfg.d_state
        xp = proj[..., d_inner:2 * d_inner]
        bc = proj[..., 2 * d_inner:2 * d_inner + 2 * bs]
        conv_state = jnp.concatenate([xp, bc], axis=-1)
        return out, {"h": h_last, "conv": conv_state.astype(cfg.cdtype)}

    h, states = lax.scan(dense._remat(body, cfg), h, params["layers"])
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h[:, -1:], compute_dtype=cfg.cdtype)
    return (constrain(logits, "batch", None, "vocab"),
            {"layers": states, "pos": jnp.asarray(S, jnp.int32)})


def prefill_chunk(params: Params, batch: dict, cfg: ModelConfig, *,
                  state: Params):
    """Continue a chunked prefill from a cache-shaped ``state``.

    ``state`` is exactly what :func:`prefill` (or a previous
    ``prefill_chunk``) returned — per-layer ``{"h", "conv"}`` plus the
    token cursor — so the final chunk's state *is* the prefill cache. The
    per-layer dict seeds both the SSD recurrence (``h``) and the depthwise
    conv history (``conv``), making the chunked scan bit-identical to one
    long scan when the engine aligns chunk boundaries to ``cfg.ssd_chunk``
    (see ``docs/slo-scheduling.md``).
    """
    h = embed(params["embed"], batch["tokens"], compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", "seq", "embed")
    S = h.shape[1]

    def body(carry, xs):
        layer, st = xs
        out, h_last = _layer_fwd(layer, carry, cfg=cfg, initial_state=st)
        # conv state: last (d_conv - 1) conv inputs *overall* — recompute
        # this chunk's tail and splice it behind the carried history so
        # chunks shorter than d_conv - 1 stay exact.
        hn = rms_norm(layer["norm"], carry)[:, -(cfg.d_conv - 1):]
        proj = hn.astype(cfg.cdtype) @ layer["mixer"]["in_proj"] \
            .astype(cfg.cdtype)
        d_inner = cfg.d_inner
        bs = cfg.n_groups * cfg.d_state
        xp = proj[..., d_inner:2 * d_inner]
        bc = proj[..., 2 * d_inner:2 * d_inner + 2 * bs]
        tail = jnp.concatenate([xp, bc], axis=-1).astype(st["conv"].dtype)
        conv_state = jnp.concatenate([st["conv"], tail],
                                     axis=1)[:, -(cfg.d_conv - 1):]
        return out, {"h": h_last, "conv": conv_state}

    h, states = lax.scan(dense._remat(body, cfg), h,
                         (params["layers"], state["layers"]))
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h[:, -1:], compute_dtype=cfg.cdtype)
    return (constrain(logits, "batch", None, "vocab"),
            {"layers": states,
             "pos": state["pos"] + jnp.asarray(S, jnp.int32)})


def _index(tree: Params, i) -> Params:
    """Entry ``i`` of every leaf of a stacked ``(L, ...)`` tree, read in
    place (a dynamic index, not a slice of the stack)."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _decode_layer(layer: Params, states: Params, i, h, *,
                  cfg: ModelConfig):
    """Decode layer ``i`` with its weights ``layer``: read its state from
    the carried ``(L, ...)`` state by index and write the new state back
    there, which a donated cache updates in place. Returns ``(h, states)``.
    """
    hn = rms_norm(layer["norm"], h)
    y, new_state = mamba2_decode(
        layer["mixer"], hn, _index(states, i), d_state=cfg.d_state,
        headdim=cfg.headdim, n_groups=cfg.n_groups, expand=cfg.expand,
        compute_dtype=cfg.cdtype)
    states = jax.tree.map(
        lambda a, s: lax.dynamic_update_index_in_dim(a, s, i, 0),
        states, new_state)
    return h + constrain(y, "batch", None, "embed"), states


def decode_step(params: Params, cache: Params, tokens, cfg: ModelConfig):
    h = embed(params["embed"], tokens, compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", None, "embed")
    h, new_layers = lax.fori_loop(
        0, cfg.n_layers,
        lambda i, c: _decode_layer(_index(params["layers"], i), c[1], i, c[0],
                                   cfg=cfg),
        (h, cache["layers"]))
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h, compute_dtype=cfg.cdtype)
    return (constrain(logits, "batch", None, "vocab"),
            {"layers": new_layers, "pos": cache["pos"] + 1})


def verify_step(params: Params, cache: Params, tokens, cfg: ModelConfig):
    """Score ``tokens (B, T)`` via T scanned decode steps with per-step
    state snapshots — the recurrent state cannot be cursor-rewound, so the
    commit restores the snapshot at each slot's accepted length (see
    :mod:`repro.models.verify_common`)."""
    return verify_common.scan_verify(
        lambda p, c, t: decode_step(p, c, t, cfg), params, cache, tokens,
        state_keys=("layers",))


def commit_verified(cache: Params, keep, aux, cfg: ModelConfig) -> Params:
    del cfg
    return verify_common.scan_commit(cache, keep, aux)
