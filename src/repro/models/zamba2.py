"""Zamba2-style hybrid: Mamba-2 backbone + one *shared* attention block.

Structure (arXiv:2411.15242, simplified as documented in
docs/architecture.md):
``n_layers`` Mamba-2 blocks; after every ``attn_every`` of them the single
shared (attention + SwiGLU) block is applied, with small *per-application*
input norms (stand-in for Zamba2's per-invocation LoRA). Weight sharing
keeps parameter count at 1.2B-class while giving the hybrid periodic global
mixing.

The shared block's KV caches (one per application point) are the only
sequence-length-proportional state — they, not the SSM states, dominate the
long_500k memory roofline term.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.layers import attention as attn_lib
from repro.layers.common import Params, init_rms_norm, rms_norm
from repro.layers.embedding import embed, init_embedding, unembed
from repro.layers.mlp import init_swiglu, swiglu
from repro.layers.ssd import init_ssm_state
from repro.models import mamba2 as mamba_lm
from repro.models import transformer as dense
from repro.models import verify_common
from repro.parallel import constrain

__all__ = ["init_params", "forward", "init_cache", "init_paged_cache",
           "prefill", "prefill_chunk", "decode_step", "paged_decode_step",
           "verify_step", "paged_verify_step", "commit_verified",
           "n_applications"]


#: Static-auditor registration (:mod:`repro.analysis.targets`): the serve
#: callables this family module exposes, its KV stack key (None = no KV),
#: and whether the paged layout / suffix prefill apply. The auditor
#: enumerates targets from this table, so a family module that grows a new
#: serve entry point must declare it here to be covered by CI.
SERVE_AUDIT = {
    "phases": ("prefill", "decode", "verify", "commit"),
    "paged": True,
    "kv_key": "kv",
    "suffix_prefill": False,
    "prefill_chunk": True,
}


def n_applications(cfg: ModelConfig) -> int:
    return cfg.n_layers // cfg.attn_every


def _grouped(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(n_apps, per_group, tail) — layers split into uniform groups + tail."""
    n_apps = n_applications(cfg)
    per_group = cfg.attn_every
    tail = cfg.n_layers - n_apps * per_group
    return n_apps, per_group, tail


def init_params(rng, cfg: ModelConfig) -> Params:
    ke, kl, ka, km, kn = jax.random.split(rng, 5)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: mamba_lm._init_layer(k, cfg))(layer_keys)
    n_apps = n_applications(cfg)
    app_norm_keys = jax.random.split(kn, n_apps)
    app_norms = jax.vmap(
        lambda k: {"attn": init_rms_norm(cfg.d_model, cfg.pdtype),
                   "mlp": init_rms_norm(cfg.d_model, cfg.pdtype)}
    )(app_norm_keys)
    return {
        "embed": init_embedding(ke, cfg.vocab, cfg.d_model,
                                tie=cfg.tie_embeddings, dtype=cfg.pdtype),
        "layers": layers,
        "shared_attn": attn_lib.init_attention(
            ka, d_model=cfg.d_model, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            dtype=cfg.pdtype),
        "shared_mlp": init_swiglu(km, cfg.d_model, cfg.d_ff, cfg.pdtype),
        "app_norms": app_norms,
        "final_norm": init_rms_norm(cfg.d_model, cfg.pdtype),
    }


def _split_layers(params: Params, cfg: ModelConfig):
    """Stacked (L, ...) mamba params → ((n_apps, per_group, ...), tail)."""
    n_apps, per_group, tail = _grouped(cfg)
    head = jax.tree.map(
        lambda a: a[: n_apps * per_group].reshape(
            (n_apps, per_group) + a.shape[1:]), params["layers"])
    tail_p = jax.tree.map(lambda a: a[n_apps * per_group:], params["layers"]) \
        if tail else None
    return head, tail_p


def _shared_block(params: Params, app_norm: Params, h, *, cfg: ModelConfig,
                  positions):
    hn = rms_norm(app_norm["attn"], h)
    a = attn_lib.attention_forward(
        params["shared_attn"], hn, positions=positions, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim, causal=True,
        rope_theta=cfg.rope_theta, q_chunk=cfg.q_chunk,
        kv_chunk=cfg.kv_chunk, impl=cfg.attn_impl, compute_dtype=cfg.cdtype,
        context_parallel=cfg.attn_cp, strategy=cfg.moa_for("attention"))
    h = h + constrain(a, "batch", "seq", "embed")
    hn = rms_norm(app_norm["mlp"], h)
    m = swiglu(params["shared_mlp"], hn, strategy=cfg.moa_for("mlp"),
               compute_dtype=cfg.cdtype)
    return h + constrain(m, "batch", "seq", "embed")


def forward(params: Params, batch: dict, cfg: ModelConfig):
    h = embed(params["embed"], batch["tokens"], compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", "seq", "embed")
    positions = jnp.arange(h.shape[1])
    head, tail_p = _split_layers(params, cfg)

    def mamba_body(carry, layer):
        out, _ = mamba_lm._layer_fwd(layer, carry, cfg=cfg)
        return out, None

    def group_body(carry, xs):
        group_layers, app_norm = xs
        out, _ = lax.scan(dense._remat(mamba_body, cfg), carry, group_layers)
        out = _shared_block(params, app_norm, out, cfg=cfg,
                            positions=positions)
        return out, None

    h, _ = lax.scan(group_body, h, (head, params["app_norms"]))
    if tail_p is not None:
        h, _ = lax.scan(dense._remat(mamba_body, cfg), h, tail_p)
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h, compute_dtype=cfg.cdtype)
    return constrain(logits, "batch", "seq", "vocab")


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    n_apps = n_applications(cfg)
    ssm_one = init_ssm_state(batch, d_model=cfg.d_model, d_state=cfg.d_state,
                             headdim=cfg.headdim, n_groups=cfg.n_groups,
                             d_conv=cfg.d_conv, expand=cfg.expand)
    kv_one = attn_lib.init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                    cfg.head_dim, dtype=cfg.cdtype)
    return {
        "ssm": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape), ssm_one),
        "kv": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_apps,) + a.shape), kv_one),
        "pos": jnp.zeros((), jnp.int32),
    }


def init_paged_cache(cfg: ModelConfig, n_slots: int, n_phys_blocks: int,
                     block_size: int, max_blocks: int) -> Params:
    """Paged hybrid state: the shared block's KV (the only sequence-
    proportional state) moves into a physical page pool per application
    point; the SSM states stay dense per slot — they are O(1) in sequence
    length, so paging them would buy nothing."""
    n_apps = n_applications(cfg)
    ssm_one = init_ssm_state(n_slots, d_model=cfg.d_model,
                             d_state=cfg.d_state, headdim=cfg.headdim,
                             n_groups=cfg.n_groups, d_conv=cfg.d_conv,
                             expand=cfg.expand)
    kv_one = attn_lib.init_kv_pool(n_phys_blocks, block_size,
                                   cfg.n_kv_heads, cfg.head_dim,
                                   dtype=cfg.cdtype)
    return {
        "ssm": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape),
            ssm_one),
        "kv": jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_apps,) + a.shape), kv_one),
        "block_tables": jnp.zeros((n_slots, max_blocks), jnp.int32),
        "pos": jnp.zeros((n_slots,), jnp.int32),
    }


def prefill(params: Params, batch: dict, cfg: ModelConfig, *, max_len: int):
    """Prefill both the SSM states and the shared-block KV caches.

    Implemented as the forward pass with explicit state capture per group.
    """
    from repro.layers.rope import apply_rope

    h = embed(params["embed"], batch["tokens"], compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", "seq", "embed")
    S = h.shape[1]
    positions = jnp.arange(S)
    head, tail_p = _split_layers(params, cfg)

    def mamba_body(carry, layer):
        out, h_last = mamba_lm._layer_fwd(layer, carry, cfg=cfg)
        hn = rms_norm(layer["norm"], carry)[:, -(cfg.d_conv - 1):]
        proj = hn.astype(cfg.cdtype) @ layer["mixer"]["in_proj"] \
            .astype(cfg.cdtype)
        d_inner = cfg.d_inner
        bs = cfg.n_groups * cfg.d_state
        conv_state = jnp.concatenate(
            [proj[..., d_inner:2 * d_inner],
             proj[..., 2 * d_inner:2 * d_inner + 2 * bs]], axis=-1)
        return out, {"h": h_last, "conv": conv_state.astype(cfg.cdtype)}

    def group_body(carry, xs):
        group_layers, app_norm = xs
        out, ssm_states = lax.scan(dense._remat(mamba_body, cfg), carry,
                                   group_layers)
        # shared block with KV capture
        hn = rms_norm(app_norm["attn"], out)
        attn_strategy = cfg.moa_for("attention")
        q, k, v = attn_lib._project_qkv(
            params["shared_attn"], hn, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            compute_dtype=cfg.cdtype, strategy=attn_strategy)
        q = apply_rope(q, positions, theta=cfg.rope_theta)
        k = apply_rope(k, positions, theta=cfg.rope_theta)
        o = attn_lib.flash_attention(q, k, v, causal=True,
                                     q_chunk=cfg.q_chunk,
                                     kv_chunk=cfg.kv_chunk)
        B = o.shape[0]
        o = o.reshape(B, S, cfg.n_heads * cfg.head_dim)
        out = out + attn_lib._moa_dot(
            o, params["shared_attn"]["wo"].astype(cfg.cdtype),
            strategy=attn_strategy, compute_dtype=cfg.cdtype)
        hn = rms_norm(app_norm["mlp"], out)
        out = out + swiglu(params["shared_mlp"], hn,
                           strategy=cfg.moa_for("mlp"),
                           compute_dtype=cfg.cdtype)
        pad = max_len - S
        kv = attn_lib._constrain_cache(
            {"k": jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0))),
             "v": jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))})
        return out, (ssm_states, kv)

    h, (ssm_head, kv_layers) = lax.scan(group_body, h,
                                        (head, params["app_norms"]))
    # ssm_head: (n_apps, per_group, ...) → flatten to (n_apps*per_group, ...)
    ssm_states = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), ssm_head)
    if tail_p is not None:
        h, ssm_tail = lax.scan(dense._remat(mamba_body, cfg), h, tail_p)
        ssm_states = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), ssm_states, ssm_tail)
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h[:, -1:], compute_dtype=cfg.cdtype)
    cache = {"ssm": ssm_states, "kv": kv_layers,
             "pos": jnp.asarray(S, jnp.int32)}
    return constrain(logits, "batch", None, "vocab"), cache


def prefill_chunk(params: Params, batch: dict, cfg: ModelConfig, *,
                  state: Params, prefix_kv: Params):
    """Continue a chunked prefill from carried SSM state + cached prefix KV.

    ``state`` is the ``{"ssm", "pos"}`` portion of what :func:`prefill`
    (or a previous ``prefill_chunk``) produced — per-layer ``{"h", "conv"}``
    seeding both the SSD recurrence and the depthwise conv history.
    ``prefix_kv`` holds the shared block's already-computed prefix K/V,
    ``{"k", "v"}: (n_apps, 1, P, Hk, D)`` in compute dtype; this chunk's
    queries attend over ``concat(prefix, chunk)`` with explicit positions,
    exactly like :func:`repro.models.transformer.prefill_suffix`.

    Returns ``(logits, {"ssm", "kv", "pos"})`` where ``kv`` is the chunk's
    *suffix-only* K/V ``(n_apps, B, S, Hk, D)`` (unpadded — the engine
    accumulates it or scatters it into pool pages) and ``ssm``/``pos`` are
    the carried state advanced through this chunk. Bit-identical to the
    same positions of a one-shot :func:`prefill` when chunk boundaries
    align to ``cfg.ssd_chunk`` (see ``docs/slo-scheduling.md``).
    """
    from repro.layers.rope import apply_rope

    h = embed(params["embed"], batch["tokens"], compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", "seq", "embed")
    S = h.shape[1]
    P = prefix_kv["k"].shape[2]
    positions_q = P + jnp.arange(S)
    positions_kv = jnp.arange(P + S)
    n_apps, per_group, tail = _grouped(cfg)
    head, tail_p = _split_layers(params, cfg)
    head_states = jax.tree.map(
        lambda a: a[: n_apps * per_group].reshape(
            (n_apps, per_group) + a.shape[1:]), state["ssm"])
    tail_states = jax.tree.map(lambda a: a[n_apps * per_group:],
                               state["ssm"]) if tail else None

    def mamba_body(carry, xs):
        layer, st = xs
        out, h_last = mamba_lm._layer_fwd(layer, carry, cfg=cfg,
                                          initial_state=st)
        # conv state: last (d_conv - 1) conv inputs overall — splice this
        # chunk's recomputed tail behind the carried history so chunks
        # shorter than d_conv - 1 stay exact.
        hn = rms_norm(layer["norm"], carry)[:, -(cfg.d_conv - 1):]
        proj = hn.astype(cfg.cdtype) @ layer["mixer"]["in_proj"] \
            .astype(cfg.cdtype)
        d_inner = cfg.d_inner
        bs = cfg.n_groups * cfg.d_state
        tail_in = jnp.concatenate(
            [proj[..., d_inner:2 * d_inner],
             proj[..., 2 * d_inner:2 * d_inner + 2 * bs]],
            axis=-1).astype(st["conv"].dtype)
        conv_state = jnp.concatenate([st["conv"], tail_in],
                                     axis=1)[:, -(cfg.d_conv - 1):]
        return out, {"h": h_last, "conv": conv_state}

    def group_body(carry, xs):
        group_layers, group_states, app_norm, pre = xs
        out, ssm_states = lax.scan(dense._remat(mamba_body, cfg), carry,
                                   (group_layers, group_states))
        hn = rms_norm(app_norm["attn"], out)
        attn_strategy = cfg.moa_for("attention")
        q, k, v = attn_lib._project_qkv(
            params["shared_attn"], hn, n_heads=cfg.n_heads,
            n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
            compute_dtype=cfg.cdtype, strategy=attn_strategy)
        q = apply_rope(q, positions_q, theta=cfg.rope_theta)
        k = apply_rope(k, positions_q, theta=cfg.rope_theta)
        k_full = jnp.concatenate([pre["k"].astype(cfg.cdtype), k], axis=1)
        v_full = jnp.concatenate([pre["v"].astype(cfg.cdtype), v], axis=1)
        o = attn_lib.full_attention(q, k_full, v_full, causal=True,
                                    positions_q=positions_q,
                                    positions_kv=positions_kv)
        B = o.shape[0]
        o = o.reshape(B, S, cfg.n_heads * cfg.head_dim)
        out = out + attn_lib._moa_dot(
            o, params["shared_attn"]["wo"].astype(cfg.cdtype),
            strategy=attn_strategy, compute_dtype=cfg.cdtype)
        hn = rms_norm(app_norm["mlp"], out)
        out = out + swiglu(params["shared_mlp"], hn,
                           strategy=cfg.moa_for("mlp"),
                           compute_dtype=cfg.cdtype)
        return out, (ssm_states, {"k": k, "v": v})

    h, (ssm_head, kv_layers) = lax.scan(
        group_body, h, (head, head_states, params["app_norms"], prefix_kv))
    ssm_states = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), ssm_head)
    if tail_p is not None:
        h, ssm_tail = lax.scan(dense._remat(mamba_body, cfg), h,
                               (tail_p, tail_states))
        ssm_states = jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), ssm_states,
            ssm_tail)
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h[:, -1:], compute_dtype=cfg.cdtype)
    cache = {"ssm": ssm_states, "kv": kv_layers,
             "pos": state["pos"] + jnp.asarray(S, jnp.int32)}
    return constrain(logits, "batch", None, "vocab"), cache


def _decode_layers(params: Params, cache: Params, h, *, cfg: ModelConfig,
                   attend):
    """The decode step's layer loop, shared by :func:`decode_step` and
    :func:`paged_decode_step`: for each application point ``g``, Mamba-2
    layers ``g*attn_every .. (g+1)*attn_every - 1`` then the shared block,
    then the ``n_layers % attn_every`` tail layers.

    Weights are read from the stacked ``(L, ...)`` parameters by layer
    index; the SSM state ``cache["ssm"]`` and the KV stack ``cache["kv"]``
    ride whole in the loop carry and each layer writes its new state back
    by index, so a donated cache is updated in place: nothing is sliced
    into groups or restacked. ``attend(hn, kv, g) -> (a, kv)`` is the
    shared block's attention at application ``g`` over the carried stack.
    Every loop has a static trip count. The tail loop indexes a slice of
    just its own layers' weights: XLA hoists a loop's f32->bf16 weight
    cast out of it over the whole array the loop indexes, so indexing the
    full stack there would cast all ``n_layers`` a second time. Returns
    ``(h, ssm, kv)``.
    """
    n_apps, per_group, tail = _grouped(cfg)
    n_head = n_apps * per_group

    def mamba_layers(weights, w0, i0):
        """Loop body: step ``j`` runs layer ``i0 + j``, weights
        ``weights[w0 + j]``."""
        def body(j, carry):
            h, ssm, kv = carry
            h, ssm = mamba_lm._decode_layer(
                mamba_lm._index(weights, w0 + j), ssm, i0 + j, h, cfg=cfg)
            return h, ssm, kv
        return body

    def group(g, carry):
        first = g * per_group
        h, ssm, kv = lax.fori_loop(
            0, per_group, mamba_layers(params["layers"], first, first), carry)
        app_norm = mamba_lm._index(params["app_norms"], g)
        hn = rms_norm(app_norm["attn"], h)
        a, kv = attend(hn, kv, g)
        h = h + constrain(a, "batch", None, "embed")
        hn = rms_norm(app_norm["mlp"], h)
        m = swiglu(params["shared_mlp"], hn, strategy=cfg.moa_for("mlp"),
                   compute_dtype=cfg.cdtype)
        return h + constrain(m, "batch", None, "embed"), ssm, kv

    carry = lax.fori_loop(0, n_apps, group, (h, cache["ssm"], cache["kv"]))
    if tail:
        tail_layers = jax.tree.map(lambda a: a[n_head:], params["layers"])
        carry = lax.fori_loop(0, tail, mamba_layers(tail_layers, 0, n_head),
                              carry)
    return carry


def _decode_logits(params: Params, h, cfg: ModelConfig):
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h, compute_dtype=cfg.cdtype)
    return constrain(logits, "batch", None, "vocab")


def decode_step(params: Params, cache: Params, tokens, cfg: ModelConfig):
    pos = cache["pos"]
    h = embed(params["embed"], tokens, compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", None, "embed")

    def attend(hn, kv, g):
        a, kv_g = attn_lib.attention_decode(
            params["shared_attn"], hn, mamba_lm._index(kv, g), pos,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            compute_dtype=cfg.cdtype, strategy=cfg.moa_for("attention"))
        return a, jax.tree.map(
            lambda a, n: lax.dynamic_update_index_in_dim(a, n, g, 0),
            kv, kv_g)

    h, ssm, kv = _decode_layers(params, cache, h, cfg=cfg, attend=attend)
    return (_decode_logits(params, h, cfg),
            {"ssm": ssm, "kv": kv, "pos": pos + 1})


def paged_decode_step(params: Params, cache: Params, tokens,
                      cfg: ModelConfig, *, live_blocks=None):
    """Paged decode step: identical to :func:`decode_step` except the
    shared attention block reads/writes its KV through per-slot block
    tables (bounded to ``live_blocks``, dispatched per
    ``cfg.attn_backend``); the new token's K/V goes straight into the
    stacked pool at ``(application, block, offset)``. The dense per-slot
    SSM recurrence is untouched."""
    pos, tables = cache["pos"], cache["block_tables"]
    h = embed(params["embed"], tokens, compute_dtype=cfg.cdtype)
    h = constrain(h, "batch", None, "embed")

    def attend(hn, pools, g):
        return attn_lib.attention_decode_paged(
            params["shared_attn"], hn, pools, tables, pos,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
            compute_dtype=cfg.cdtype, strategy=cfg.moa_for("attention"),
            backend=cfg.attn_backend, live_blocks=live_blocks, app=g)

    h, ssm, kv = _decode_layers(params, cache, h, cfg=cfg, attend=attend)
    return (_decode_logits(params, h, cfg),
            {"ssm": ssm, "kv": kv, "block_tables": tables, "pos": pos + 1})


# ---------------------------------------------------------------------------
# Speculative verify (docs/spec-decode.md)
# ---------------------------------------------------------------------------
# The hybrid's KV caches are position-addressed (cursor rewind suffices),
# but the Mamba-2 states are recurrent — verify is a scan of the family's
# own decode step with per-step SSM snapshots, and the commit restores
# each slot's snapshot at its accepted length.


def verify_step(params: Params, cache: Params, tokens, cfg: ModelConfig):
    """Score ``tokens (B, T)`` via T scanned decode steps; bit-identical
    to sequential decode by construction. Returns ``(logits, cache, aux)``
    — ``aux`` holds the stacked SSM snapshots for
    :func:`commit_verified`."""
    return verify_common.scan_verify(
        lambda p, c, t: decode_step(p, c, t, cfg), params, cache, tokens,
        state_keys=("ssm",))


def paged_verify_step(params: Params, cache: Params, tokens,
                      cfg: ModelConfig, *, live_blocks=None):
    """Paged twin of :func:`verify_step`: the scanned step is
    :func:`paged_decode_step`, so tentative KV writes route through the
    block tables (slot-private pages — the engine's admission margin).
    ``live_blocks`` must already include the T-token verify window — every
    scanned step reuses the same static bound."""
    return verify_common.scan_verify(
        lambda p, c, t: paged_decode_step(p, c, t, cfg,
                                          live_blocks=live_blocks),
        params, cache, tokens, state_keys=("ssm",))


def commit_verified(cache: Params, keep, aux, cfg: ModelConfig) -> Params:
    del cfg
    return verify_common.scan_commit(cache, keep, aux)
