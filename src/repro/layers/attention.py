"""GQA attention: chunked (flash-style) softmax, full-softmax, and decode.

The chunked path is the paper's §3.1 *done right on TPU*: the softmax·V
contraction over the KV axis is a multi-operand reduction with up to 524 288
operands (long_500k). Instead of materializing the (Sq × Skv) score matrix
(the "adder tree" — maximal working set), KV blocks stream through a
``lax.scan`` carrying a running (max, denominator, accumulator) triple in
f32 — a serialized MOA whose "serializer" is the hard-wired HBM→VMEM
pipeline. ``kv_chunk`` is the cluster size ``n_c``.

Layouts: q ``(B, Sq, H, D)``, k/v ``(B, Skv, Hk, D)``; GQA groups
``G = H // Hk`` are kept as a separate axis so the ``model``-axis sharding
of Hk stays even.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.layers.common import Params, dense_init
from repro.layers.numerics import NEG_INF, kv_scale_zeros, online_softmax_init
from repro.layers.rope import apply_rope
from repro.parallel import active_context, constrain
from repro.parallel.sharding import _drop_indivisible, constraint_spec
from repro.tracing import ATTENTION, layer_scope

__all__ = [
    "init_attention", "attention_forward", "attention_decode",
    "attention_decode_paged", "attention_verify", "attention_verify_paged",
    "flash_attention", "full_attention", "init_kv_cache", "init_kv_pool",
    "gather_paged_kv", "resolve_attn_backend",
]

_NEG_INF = NEG_INF  # canonical sentinel lives in layers/numerics.py

#: valid ``attn_backend`` values (mirrors ``moa/backends.py``'s two
#: substrates: a pure-jnp reference and the Pallas kernels)
ATTN_BACKENDS = ("jnp", "pallas")


def resolve_attn_backend(backend: str = "auto") -> str:
    """Resolve the paged-attention backend knob.

    Mirrors ``MOAStrategy.resolve_backend()``: ``"auto"`` selects the fused
    Pallas block-table kernels on TPU and the gather-based jnp reference
    elsewhere (where the kernels would only run in interpret mode — the
    correctness path, not a fast one). Explicit ``"pallas"`` on CPU still
    works via interpret mode, which is how the parity suite exercises the
    kernel schedule on CI.
    """
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if backend not in ATTN_BACKENDS:
        raise ValueError(f"unknown attn backend {backend!r}; expected "
                         f"'auto' or one of {ATTN_BACKENDS}")
    return backend


def init_attention(rng, *, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, qkv_bias: bool = False,
                   dtype=jnp.float32) -> Params:
    kq, kk, kv, ko = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(kq, (d_model, n_heads * head_dim), dtype, fan_in=d_model),
        "wk": dense_init(kk, (d_model, n_kv_heads * head_dim), dtype, fan_in=d_model),
        "wv": dense_init(kv, (d_model, n_kv_heads * head_dim), dtype, fan_in=d_model),
        "wo": dense_init(ko, (n_heads * head_dim, d_model), dtype,
                         fan_in=n_heads * head_dim),
    }
    if qkv_bias:  # qwen1.5 style
        p["bq"] = jnp.zeros((n_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((n_kv_heads * head_dim,), dtype)
    return p


@layer_scope(ATTENTION)
def full_attention(q, k, v, *, causal: bool, positions_q=None, positions_kv=None,
                   kv_len=None):
    """One-shot attention (the spatial "adder tree"): materializes scores.

    Kept as the ``tree`` MOA strategy baseline and for tiny smoke shapes;
    the memory roofline term it produces is the §Perf before/after foil.

    ``kv_len`` limits which cache positions are attended: a scalar applies
    to the whole batch, a ``(B,)`` vector gives per-sequence valid lengths
    (continuous-batching decode, where slots sit at different positions).
    ``positions_q`` may be ``(Sq,)`` (shared) or ``(B, Sq)`` — per-sequence
    query positions, the speculative-verify case where every slot scores
    its draft window starting at its own cursor.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hk, _ = k.shape
    G = H // Hk
    qg = q.reshape(B, Sq, Hk, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    if positions_q is None:
        positions_q = jnp.arange(Sq)
    if positions_kv is None:
        positions_kv = jnp.arange(Skv)
    pq = positions_q if jnp.ndim(positions_q) == 2 else positions_q[None]
    mask = jnp.ones((pq.shape[0], Sq, Skv), bool)       # (B | 1, Sq, Skv)
    if causal:
        mask &= positions_kv[None, None, :] <= pq[:, :, None]
    if kv_len is not None:
        if jnp.ndim(kv_len) == 0:
            mask &= positions_kv[None, None, :] < kv_len
        else:
            mask &= positions_kv[None, None, :] < kv_len[:, None, None]
    mask = mask[:, None, None]                          # (B|1, 1, 1, Sq, Skv)
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


@layer_scope(ATTENTION)
def flash_attention(q, k, v, *, causal: bool = True, q_chunk: int = 256,
                    kv_chunk: int = 512, kv_len=None):
    """Chunked-softmax attention (serialized MOA over the KV axis).

    Works for any (Sq, Skv); sequences are padded up to chunk multiples and
    padded KV positions are masked. f32 running statistics.
    """
    B, Sq, H, D = q.shape
    _, Skv, Hk, _ = k.shape
    G = H // Hk
    scale = D ** -0.5
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    pad_q = -Sq % q_chunk
    pad_k = -Skv % kv_chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq_p, Skv_p = q.shape[1], k.shape[1]
    nq, nk = Sq_p // q_chunk, Skv_p // kv_chunk
    kv_valid = jnp.asarray(Skv if kv_len is None else kv_len, jnp.int32)

    qg = (q.astype(jnp.float32) * scale).reshape(B, nq, q_chunk, Hk, G, D)
    qg = jnp.moveaxis(qg, 1, 0)                      # (nq, B, qc, Hk, G, D)
    kb = jnp.moveaxis(k.reshape(B, nk, kv_chunk, Hk, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, kv_chunk, Hk, D), 1, 0)

    def outer(_, xs):
        qi, q_blk = xs
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def inner(carry, inner_xs):
            m, l, acc = carry
            kj, k_blk, v_blk = inner_xs
            kv_pos = kj * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk,
                           k_blk.astype(jnp.float32))
            mask = kv_pos[None, :] < kv_valid
            if causal:
                mask &= kv_pos[None, :] <= q_pos[:, None]
            s = jnp.where(mask[None, None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = lax.scan(inner,
                                  online_softmax_init((B, Hk, G, q_chunk), D),
                                  (jnp.arange(nk), kb, vb))
        o_blk = acc / jnp.maximum(l, 1e-30)[..., None]   # (B,Hk,G,qc,D)
        return None, jnp.moveaxis(o_blk, 3, 1)           # (B,qc,Hk,G,D)

    _, o_blocks = lax.scan(outer, None, (jnp.arange(nq), qg))
    o = jnp.moveaxis(o_blocks, 0, 1).reshape(B, Sq_p, H, D)
    return o[:, :Sq].astype(q.dtype)


@layer_scope(ATTENTION)
def _moa_dot(x, w, *, strategy, compute_dtype):
    """Dense projection routed through the MOA engine (scope-aware).

    The d_model contraction of every attention projection is itself an MOA;
    delegates to :func:`repro.layers.linear.project` so strategy dispatch
    (and the f32-accumulating fallback) lives in exactly one place.
    """
    from repro.layers.linear import project

    return project({"w": w}, x, strategy=strategy,
                   compute_dtype=compute_dtype)


@layer_scope(ATTENTION)
def _project_qkv(params: Params, x, *, n_heads, n_kv_heads, head_dim,
                 compute_dtype, strategy=None):
    B, S, _ = x.shape
    x = x.astype(compute_dtype)

    def dot(w):
        return _moa_dot(x, w.astype(compute_dtype), strategy=strategy,
                        compute_dtype=compute_dtype)

    q = dot(params["wq"])
    k = dot(params["wk"])
    v = dot(params["wv"])
    if "bq" in params:
        q = q + params["bq"].astype(compute_dtype)
        k = k + params["bk"].astype(compute_dtype)
        v = v + params["bv"].astype(compute_dtype)
    q = q.reshape(B, S, n_heads, head_dim)
    k = k.reshape(B, S, n_kv_heads, head_dim)
    v = v.reshape(B, S, n_kv_heads, head_dim)
    return q, k, v


@layer_scope(ATTENTION)
def attention_forward(params: Params, x, *, positions, n_heads: int,
                      n_kv_heads: int, head_dim: int, causal: bool = True,
                      rope_theta: float = 10000.0, use_rope: bool = True,
                      q_chunk: int = 256, kv_chunk: int = 512,
                      impl: str = "flash", compute_dtype=jnp.bfloat16,
                      context_parallel: bool = False, strategy=None):
    """Self-attention over ``x: (B, S, d_model)``.

    ``context_parallel``: constrain Q to a model-axis-sharded *sequence*
    layout (Ulysses-style). Heads stay unsharded; GSPMD inserts the layout
    all-to-all (each device moves only its activation shard) in place of
    the Megatron attn-out all-reduce (which moves the full activation
    twice) — the §Perf collective lever for attention-heavy cells.
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(params, x, n_heads=n_heads, n_kv_heads=n_kv_heads,
                           head_dim=head_dim, compute_dtype=compute_dtype,
                           strategy=strategy)
    if use_rope:
        q = apply_rope(q, positions, theta=rope_theta)
        k = apply_rope(k, positions, theta=rope_theta)
    if context_parallel:
        q = constrain(q, "batch", "seq_cp", None, None)
        k = constrain(k, "batch", "seq_cp", None, None)
        v = constrain(v, "batch", "seq_cp", None, None)
    if impl == "flash":
        o = flash_attention(q, k, v, causal=causal, q_chunk=q_chunk,
                            kv_chunk=kv_chunk)
    else:
        o = full_attention(q, k, v, causal=causal)
    o = o.reshape(B, S, n_heads * head_dim)
    return _moa_dot(o, params["wo"].astype(compute_dtype),
                    strategy=strategy, compute_dtype=compute_dtype)


def _constrain_cache(cache: Params) -> Params:
    """Pin a dense ``(batch, seq, heads, dim)`` KV cache's layout under an
    active sharding context (no-op otherwise): slots on the data axis, KV
    heads on the model axis. Scatter updates route through this so the
    donated cache buffer's sharding never drifts between decode steps
    (docs/sharded-serving.md)."""
    out = dict(cache)
    for key in ("k", "v"):
        out[key] = constrain(out[key], "batch", "kv_seq",
                             "kv_heads_cache", "head_dim")
    for key in ("k_scale", "v_scale"):
        if key in out:
            out[key] = constrain(out[key], "batch", "scale_seq",
                                 "kv_heads_cache")
    return out


def _constrain_pool(pool: Params, *, stacked: bool = False) -> Params:
    """Paged twin of :func:`_constrain_cache`: the physical block axis is
    shared across slots (replicated — block tables are logical), only the
    head dimension shards. ``stacked`` pools carry a leading (replicated)
    application-point axis."""
    lead = (None,) * (3 if stacked else 2)
    out = dict(pool)
    for key in ("k", "v"):
        out[key] = constrain(out[key], *lead, "kv_heads_cache", "head_dim")
    for key in ("k_scale", "v_scale"):
        if key in out:
            out[key] = constrain(out[key], *lead, "kv_heads_cache")
    return out


def init_kv_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16) -> Params:
    """KV cache; ``dtype=int8`` stores quantized K/V with per-(pos, head)
    f32 scales — halves the decode-time HBM stream (the memory-roofline
    lever for decode shapes; see docs/paged-kv.md on cache memory)."""
    cache = {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
    }
    if dtype == jnp.int8:
        cache["k_scale"] = kv_scale_zeros((batch, max_len, n_kv_heads))
        cache["v_scale"] = kv_scale_zeros((batch, max_len, n_kv_heads))
    return _constrain_cache(cache)


def init_kv_pool(n_phys_blocks: int, block_size: int, n_kv_heads: int,
                 head_dim: int, dtype=jnp.bfloat16) -> Params:
    """Paged KV pool: one shared set of physical pages instead of a dense
    per-slot region. Same leaf set as :func:`init_kv_cache` with the
    sequence axis factored into ``(n_phys_blocks, block_size)``; physical
    block 0 is the engine's write-trash page (see
    :mod:`repro.serve.kv_pool`). The head dimension is constrained so a
    mesh-backed engine materializes the pool model-axis-sharded from the
    start."""
    pool = {
        "k": jnp.zeros((n_phys_blocks, block_size, n_kv_heads, head_dim),
                       dtype),
        "v": jnp.zeros((n_phys_blocks, block_size, n_kv_heads, head_dim),
                       dtype),
    }
    if dtype == jnp.int8:
        pool["k_scale"] = kv_scale_zeros((n_phys_blocks, block_size,
                                          n_kv_heads))
        pool["v_scale"] = kv_scale_zeros((n_phys_blocks, block_size,
                                          n_kv_heads))
    return _constrain_pool(pool)


@layer_scope(ATTENTION)
def quantize_kv(x):
    """Per-(batch, pos, head) symmetric int8 quantization of K or V."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


@layer_scope(ATTENTION)
def attention_decode(params: Params, x, cache: Params, pos, *, n_heads: int,
                     n_kv_heads: int, head_dim: int,
                     rope_theta: float = 10000.0, use_rope: bool = True,
                     compute_dtype=jnp.bfloat16,
                     strategy=None) -> Tuple[jax.Array, Params]:
    """One decode step: ``x (B, 1, d)`` against a KV cache at position ``pos``.

    The softmax over the cache is the *decode-time MOA* — a single-operand
    append followed by a 32k–524k-operand reduction. Under SP the cache's
    sequence axis is sharded and XLA's partial reductions realize the
    split-K (parallel-MOA) combine.
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(
        params, x, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        compute_dtype=compute_dtype, strategy=strategy)
    pos_arr = jnp.full((B, 1), pos) if jnp.ndim(pos) == 0 else pos[:, None]
    if use_rope:
        q = apply_rope(q, pos_arr, theta=rope_theta)
        k_new = apply_rope(k_new, pos_arr, theta=rope_theta)

    quantized = "k_scale" in cache

    def write(buf, new):
        if jnp.ndim(pos) == 0:
            return lax.dynamic_update_slice_in_dim(
                buf, new.astype(buf.dtype), pos, axis=1)
        return _scatter_per_batch(buf, new, pos)

    new_cache = dict(cache)
    if quantized:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_cache["k"] = write(cache["k"], kq)
        new_cache["v"] = write(cache["v"], vq)
        new_cache["k_scale"] = write(cache["k_scale"], ks)
        new_cache["v_scale"] = write(cache["v_scale"], vs)
        new_cache = _constrain_cache(new_cache)
        k_cache = dequantize_kv(new_cache["k"], new_cache["k_scale"],
                                compute_dtype)
        v_cache = dequantize_kv(new_cache["v"], new_cache["v_scale"],
                                compute_dtype)
    else:
        new_cache["k"] = write(cache["k"], k_new)
        new_cache["v"] = write(cache["v"], v_new)
        new_cache = _constrain_cache(new_cache)
        k_cache, v_cache = new_cache["k"], new_cache["v"]

    kv_len = pos + 1
    o = full_attention(q, k_cache, v_cache, causal=False, kv_len=kv_len)
    o = o.reshape(B, 1, n_heads * head_dim)
    y = _moa_dot(o, params["wo"].astype(compute_dtype),
                 strategy=strategy, compute_dtype=compute_dtype)
    return y, new_cache


def _scatter_per_batch(cache, new, pos):
    """Per-sequence cache write when positions differ across the batch."""
    B = cache.shape[0]
    idx = pos.astype(jnp.int32)
    return cache.at[jnp.arange(B), idx].set(new[:, 0].astype(cache.dtype))


def _verify_positions(pos, batch: int, n_tokens: int):
    """Per-slot query positions ``(B, T)`` for a T-token verify window
    starting at each slot's cursor (scalar ``pos`` broadcasts)."""
    start = jnp.full((batch,), pos) if jnp.ndim(pos) == 0 else pos
    return start.astype(jnp.int32)[:, None] + jnp.arange(n_tokens)[None, :]


@layer_scope(ATTENTION)
def attention_verify(params: Params, x, cache: Params, pos, *, n_heads: int,
                     n_kv_heads: int, head_dim: int,
                     rope_theta: float = 10000.0, use_rope: bool = True,
                     compute_dtype=jnp.bfloat16,
                     strategy=None) -> Tuple[jax.Array, Params]:
    """Speculative verify: score ``T`` tokens per slot in one call.

    ``x (B, T, d)`` holds the pending token followed by the draft window;
    slot ``b``'s tokens sit at positions ``pos[b] .. pos[b]+T-1``. All T
    K/V entries are written (tentatively — the engine's commit/rewind
    decides how many survive via the ``pos`` cursor; rows past the cursor
    are causally masked garbage exactly like freed-slot rows), and each
    query attends the cache causally at its own per-slot position, so the
    per-position math is identical to T sequential
    :func:`attention_decode` calls (tests/test_spec_decode.py parity).
    """
    B, T, _ = x.shape
    q, k_new, v_new = _project_qkv(
        params, x, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        compute_dtype=compute_dtype, strategy=strategy)
    pos_q = _verify_positions(pos, B, T)                 # (B, T)
    if use_rope:
        q = apply_rope(q, pos_q, theta=rope_theta)
        k_new = apply_rope(k_new, pos_q, theta=rope_theta)

    b_idx = jnp.arange(B)[:, None]

    def write(buf, new):
        # out-of-range rows (a slot near max_len) drop, never clamp
        return buf.at[b_idx, pos_q].set(new.astype(buf.dtype),
                                        mode="drop")

    new_cache = dict(cache)
    if "k_scale" in cache:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_cache["k"] = write(cache["k"], kq)
        new_cache["v"] = write(cache["v"], vq)
        new_cache["k_scale"] = write(cache["k_scale"], ks)
        new_cache["v_scale"] = write(cache["v_scale"], vs)
        new_cache = _constrain_cache(new_cache)
        k_cache = dequantize_kv(new_cache["k"], new_cache["k_scale"],
                                compute_dtype)
        v_cache = dequantize_kv(new_cache["v"], new_cache["v_scale"],
                                compute_dtype)
    else:
        new_cache["k"] = write(cache["k"], k_new)
        new_cache["v"] = write(cache["v"], v_new)
        new_cache = _constrain_cache(new_cache)
        k_cache, v_cache = new_cache["k"], new_cache["v"]

    o = full_attention(q, k_cache, v_cache, causal=True, positions_q=pos_q)
    o = o.reshape(B, T, n_heads * head_dim)
    y = _moa_dot(o, params["wo"].astype(compute_dtype),
                 strategy=strategy, compute_dtype=compute_dtype)
    return y, new_cache


@layer_scope(ATTENTION)
def attention_verify_paged(params: Params, x, pool: Params, block_tables,
                           pos, *, n_heads: int, n_kv_heads: int,
                           head_dim: int, rope_theta: float = 10000.0,
                           use_rope: bool = True,
                           compute_dtype=jnp.bfloat16,
                           strategy=None, backend: str = "jnp",
                           live_blocks: Optional[int] = None,
                           ) -> Tuple[jax.Array, Params]:
    """Paged twin of :func:`attention_verify`.

    The T tentative K/V entries scatter to pages
    ``block_tables[b, (pos+i) // bs]``. The engine's admission reserves a
    ``k``-token margin of private pages past every request's worst-case
    length, so speculative writes only ever land on pages owned by the
    writing slot (or the trash page, for logical blocks past the table) —
    a rejected position is rolled back by rewinding ``pos`` alone and the
    page row is simply overwritten when decode reaches it again.

    ``backend`` / ``live_blocks`` behave as in
    :func:`attention_decode_paged`; the pallas path is the paged
    flash-**prefill** kernel instance (T-token contiguous window per slot),
    which is also what the bucketed suffix-prefill path runs. Callers must
    size ``live_blocks`` to cover ``max(pos) + T`` positions, not just the
    cursors.
    """
    B, T, _ = x.shape
    bs = pool["k"].shape[1]
    q, k_new, v_new = _project_qkv(
        params, x, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        compute_dtype=compute_dtype, strategy=strategy)
    pos_q = _verify_positions(pos, B, T)                 # (B, T)
    if use_rope:
        q = apply_rope(q, pos_q, theta=rope_theta)
        k_new = apply_rope(k_new, pos_q, theta=rope_theta)

    b_idx = jnp.arange(B)[:, None]
    logical = pos_q // bs
    n_logical = block_tables.shape[1]
    blk = block_tables[b_idx, jnp.minimum(logical, n_logical - 1)]
    # positions past the table (idle slots sitting at high cursors) go to
    # physical block 0 — the engine's write-trash page
    blk = jnp.where(logical < n_logical, blk, 0)         # (B, T)
    off = pos_q % bs

    def write(pool_leaf, new):
        return pool_leaf.at[blk, off].set(new.astype(pool_leaf.dtype))

    new_pool = dict(pool)
    if "k_scale" in pool:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_pool["k"] = write(pool["k"], kq)
        new_pool["v"] = write(pool["v"], vq)
        new_pool["k_scale"] = write(pool["k_scale"], ks)
        new_pool["v_scale"] = write(pool["v_scale"], vs)
    else:
        new_pool["k"] = write(pool["k"], k_new)
        new_pool["v"] = write(pool["v"], v_new)
    new_pool = _constrain_pool(new_pool)

    if resolve_attn_backend(backend) == "pallas":
        o = _paged_attention_fused(q, new_pool, block_tables, pos_q[:, 0],
                                   compute_dtype=compute_dtype,
                                   live_blocks=live_blocks)
    else:
        k_cache, v_cache = gather_paged_kv(new_pool, block_tables,
                                           compute_dtype,
                                           live_blocks=live_blocks)
        o = full_attention(q, k_cache, v_cache, causal=True,
                           positions_q=pos_q)
    o = o.reshape(B, T, n_heads * head_dim)
    y = _moa_dot(o, params["wo"].astype(compute_dtype),
                 strategy=strategy, compute_dtype=compute_dtype)
    return y, new_pool


# ---------------------------------------------------------------------------
# paged decode path (gather-based; see docs/paged-kv.md)
# ---------------------------------------------------------------------------


def gather_paged_kv(pool: Params, block_tables, dtype=jnp.bfloat16,
                    *, live_blocks: Optional[int] = None):
    """Materialize each sequence's logical KV view from the shared pool.

    ``pool`` leaves are ``(n_phys_blocks, block_size, ...)``;
    ``block_tables`` is ``(B, max_blocks)`` int32 logical→physical. Returns
    dense ``(B, n_blk·block_size, Hk, D)`` K and V (dequantized for an
    int8 pool). With ``block_size`` dividing ``max_len`` the gathered view
    has *exactly* the dense cache's shape, and every attended position
    holds the same value — the paged read is bit-identical by construction
    (unattended garbage is masked to ``_NEG_INF`` before the softmax either
    way).

    ``live_blocks`` (static) truncates the gather to the batch's high-water
    logical block — pages past *every* slot's cursor were fully masked, so
    not streaming them is float-bit-identical (a masked score contributes
    an exact f32 zero to the softmax and never holds the row max) while
    cutting the gathered HBM traffic from ``max_blocks`` to the live depth.
    """
    if live_blocks is not None:
        block_tables = block_tables[:, :live_blocks]

    def flat(name):
        x = pool[name][block_tables]         # (B, n_blk, bs, ...)
        return x.reshape((x.shape[0], -1) + x.shape[3:])

    k, v = flat("k"), flat("v")
    if "k_scale" in pool:
        k = dequantize_kv(k, flat("k_scale"), dtype)
        v = dequantize_kv(v, flat("v_scale"), dtype)
    # the gathered logical view carries the dense-slot layout: slots over
    # data, heads over model (the score reduction then never reshards)
    k = constrain(k, "batch", "kv_seq", "kv_heads_cache", "head_dim")
    v = constrain(v, "batch", "kv_seq", "kv_heads_cache", "head_dim")
    return k, v


def _paged_attention_fused(q, pool: Params, block_tables, start, *,
                           compute_dtype=jnp.bfloat16,
                           live_blocks: Optional[int] = None):
    """Route the paged score reduction through the fused Pallas kernel.

    ``q: (B, T, H, D)`` queries at positions ``start[b] .. start[b]+T-1``.
    The kernel walks the (optionally high-water-truncated) block tables
    inside the grid and dequantizes int8 pools in-register — the dense
    gathered view of :func:`gather_paged_kv` never exists.
    ``compute_dtype`` is the dtype the gather path would materialize that
    view in; the kernel rounds its dequantized values through it so the
    two backends agree bit-for-bit on every attended KV entry.

    Under an active multi-device mesh the call is partitioned by hand, since
    GSPMD cannot split a Mosaic kernel: a ``shard_map`` gives each device
    its slots (the ``batch`` axis) and its KV heads (``kv_heads_cache``,
    with their query groups), as the sharding rules place them. Each slot's
    queries attend only that slot's pages, and each KV head only its own
    query group, so the partitioned call needs no communication.
    """
    from repro.kernels import ops as kernel_ops

    if live_blocks is not None:
        block_tables = block_tables[:, :live_blocks]
    scales = tuple(pool[k] for k in ("k_scale", "v_scale") if k in pool)

    def call(q, k, v, tables, start, *scales):
        k_scale, v_scale = scales or (None, None)
        return kernel_ops.paged_attention(
            q, k, v, tables, start, k_scale=k_scale, v_scale=v_scale,
            dequant_dtype=compute_dtype)

    args = (q, pool["k"], pool["v"], block_tables, start) + scales
    mesh, _ = active_context()
    if mesh is None or mesh.size == 1:
        return call(*args)
    slots, heads = _drop_indivisible(
        (q.shape[0], pool["k"].shape[2]),
        constraint_spec(("batch", "kv_heads_cache"), mesh=mesh), mesh)
    q_spec = P(slots, None, heads, None)
    pool_spec = P(None, None, heads, None)
    in_specs = (q_spec, pool_spec, pool_spec, P(slots, None), P(slots)) \
        + (P(None, None, heads),) * len(scales)
    return jax.shard_map(call, mesh=mesh, in_specs=in_specs,
                         out_specs=q_spec, check_vma=False)(*args)


@layer_scope(ATTENTION)
def attention_decode_paged(params: Params, x, pool: Params, block_tables,
                           pos, *, n_heads: int, n_kv_heads: int,
                           head_dim: int, rope_theta: float = 10000.0,
                           use_rope: bool = True,
                           compute_dtype=jnp.bfloat16,
                           strategy=None, backend: str = "jnp",
                           live_blocks: Optional[int] = None, app=None,
                           ) -> Tuple[jax.Array, Params]:
    """One decode step against a *paged* KV pool.

    Identical math to :func:`attention_decode` — same projections, same
    rope, same masked full-softmax reduction — with the cache read/write
    factored through per-slot block tables: the new token's K/V scatters to
    physical page ``block_tables[b, pos // bs]`` offset ``pos % bs``, and
    the score reduction runs over the gathered logical view. The engine
    guarantees writes only ever land on unshared pages (copy-on-write
    happens host-side before the first divergent write), so slots at
    heterogeneous depths share physical prefix pages safely.

    ``backend`` picks the score-reduction substrate (resolved via
    :func:`resolve_attn_backend`): ``"jnp"`` gathers the dense logical view
    (reference), ``"pallas"`` runs the fused block-table kernel — greedy
    tokens are bit-identical, floats agree to online-softmax reassociation.
    ``live_blocks`` (static) bounds both paths to the batch's high-water
    logical block.

    With ``app`` (a traced application-point index) ``pool`` is a stack of
    pools, leaves ``(n_apps, n_phys_blocks, block_size, ...)``: the new
    K/V is written into the stack at ``(app, block, offset)`` — in place
    when the stack is a donated loop carry — the score reduction reads
    pool ``app``, and the whole stack is returned.
    """
    B = x.shape[0]
    bs = pool["k"].shape[-3]
    q, k_new, v_new = _project_qkv(
        params, x, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
        compute_dtype=compute_dtype, strategy=strategy)
    pos = pos[:, None] if jnp.ndim(pos) == 1 else jnp.full((B, 1), pos)
    if use_rope:
        q = apply_rope(q, pos, theta=rope_theta)
        k_new = apply_rope(k_new, pos, theta=rope_theta)

    cur = pos[:, 0]
    blk = block_tables[jnp.arange(B), cur // bs]
    off = cur % bs

    at = (blk, off) if app is None else (app, blk, off)

    def write(leaf, new):
        return leaf.at[at].set(new[:, 0].astype(leaf.dtype))

    new_pool = dict(pool)
    if "k_scale" in pool:
        kq, ks = quantize_kv(k_new)
        vq, vs = quantize_kv(v_new)
        new_pool["k"] = write(pool["k"], kq)
        new_pool["v"] = write(pool["v"], vq)
        new_pool["k_scale"] = write(pool["k_scale"], ks)
        new_pool["v_scale"] = write(pool["v_scale"], vs)
    else:
        new_pool["k"] = write(pool["k"], k_new)
        new_pool["v"] = write(pool["v"], v_new)
    new_pool = _constrain_pool(new_pool, stacked=app is not None)
    view = new_pool if app is None else _constrain_pool(jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, app, keepdims=False),
        new_pool))

    if resolve_attn_backend(backend) == "pallas":
        o = _paged_attention_fused(q, view, block_tables, cur,
                                   compute_dtype=compute_dtype,
                                   live_blocks=live_blocks)
    else:
        k_cache, v_cache = gather_paged_kv(view, block_tables,
                                           compute_dtype,
                                           live_blocks=live_blocks)
        o = full_attention(q, k_cache, v_cache, causal=False, kv_len=cur + 1)
    o = o.reshape(B, 1, n_heads * head_dim)
    y = _moa_dot(o, params["wo"].astype(compute_dtype),
                 strategy=strategy, compute_dtype=compute_dtype)
    return y, new_pool
