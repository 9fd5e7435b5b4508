"""Pallas TPU kernel: paged flash-attention over block-table KV pools.

The serving hot path's gather-based reference (`layers/attention.py:
gather_paged_kv`) materializes a dense ``(B, max_blocks·block_size, Hk, D)``
KV view on every decode step — the full padded cache streams through HBM
regardless of how deep each sequence actually is. This kernel walks each
sequence's **block table inside the grid** instead: the table row and the
per-slot first-query position are scalar-prefetched (SMEM), and the K/V
BlockSpec index maps translate logical KV block ``j`` to its physical page
``tables[b, j]`` on the fly. Pages past a slot's cursor are redirected to
physical page 0 (the engine's write-trash page); Pallas elides the re-fetch
when consecutive grid steps map to the same block, so dead pages cost
neither bandwidth nor compute (the compute body is ``pl.when``-guarded).

Softmax·V is scheduled exactly like ``flash_attention.py`` — the paper's
serialized MOA with a renormalizable (m, l, acc) carry (m, l in VMEM
scratch, acc in the resident output block) across the sequential trailing
grid dimension — so per-slot depth masking falls out of the causal mask: a
fully-dead page contributes an exact f32 zero and never perturbs the
running max.

For **int8 pools** the per-(pos, head) ``k_scale``/``v_scale`` leaves ride
along as two more paged inputs and dequantization happens in-register on
the VMEM tile — the bf16/f32 KV view the jnp path materializes in HBM never
exists here (the reconfigurable-MOA move: pick the accumulation path per
operand width at the kernel boundary).

Grid: ``(B, n_blocks)`` with the page walk sequential. Each step brings
in one page of *all* KV heads, a ``(block_size, Hk, head_dim)`` tile whose
two minor dims are the pool's own, so Mosaic's (8, 128) tiling accepts it
at every width (a one-head ``(block_size, 1, head_dim)`` block does not).
The per-step VMEM working set is that K and V tile, the ``Hk × T·G ×
head_dim`` query/accumulator tiles and the ``Hk × T·G × block_size`` score
tile (f32) — independent of table width. Queries enter as ``(B, Hk, T·G,
D)`` so each GQA group stays packed next to the head it shares KV with,
and the body contracts all heads in one batched ``einsum``.

One kernel covers both serve phases: decode is the ``T = 1`` instance
(``start`` = each slot's cursor) and the bucketed/chunked suffix-prefill /
speculative-verify path is the ``T = window`` instance (queries are always
a contiguous window starting at the cursor, so positions never need to be
shipped — only the ``(B,)`` start vector).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_pallas", "paged_flash_decode",
           "paged_flash_prefill"]

_NEG_INF = -1e30


def _paged_kernel(tables_ref, start_ref, q_ref, k_ref, v_ref, *rest,
                  block_size, group, sm_scale, quantized, dequant_dtype):
    if quantized:
        k_scale_ref, v_scale_ref, o_ref, m_ref, l_ref = rest
    else:
        o_ref, m_ref, l_ref = rest
    del tables_ref  # consumed by the BlockSpec index maps
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_rows = q_ref.shape[2]                  # T·G query rows per KV head

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    start = start_ref[b]
    # logical block j holds KV positions [j·bs, j·bs + bs); the deepest
    # query sits at start + T - 1, so later blocks are fully causal-masked
    live = j * block_size <= start + n_rows // group - 1

    @pl.when(live)
    def _fold():
        q = q_ref[0].astype(jnp.float32) * sm_scale          # (Hk, T·G, D)
        k = k_ref[0].astype(jnp.float32)                     # (bs, Hk, D)
        v = v_ref[0].astype(jnp.float32)
        if quantized:
            # round through the gather path's materialization dtype so the
            # in-register dequant sees the exact values gather_paged_kv
            # would have written to HBM — greedy parity needs the logits to
            # differ only by online-softmax reassociation
            k = (k * k_scale_ref[0][:, :, None]) \
                .astype(dequant_dtype).astype(jnp.float32)
            v = (v * v_scale_ref[0][:, :, None]) \
                .astype(dequant_dtype).astype(jnp.float32)
        s = jnp.einsum("hrd,khd->hrk", q, k,
                       preferred_element_type=jnp.float32)   # (Hk, T·G, bs)

        # row r is query t = r // G at position start + t; the causal test
        # kv_pos <= start + r // G is (kv_pos - start)·G <= r in integers
        shape = (n_rows, block_size)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        kv_pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32,
                                                           shape, 1)
        mask = (kv_pos - start) * group <= row
        s = jnp.where(mask[None], s, _NEG_INF)

        m_prev = m_ref[...]                                  # (Hk, T·G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        o_ref[0] = o_ref[0] * corr + jnp.einsum(
            "hrk,khd->hrd", p, v, preferred_element_type=jnp.float32)

    # the last page may be dead for shallow slots, so normalization reads
    # the carried (acc, l) from the refs rather than registers
    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = o_ref[0] / jnp.maximum(l_ref[...], 1e-30)


def paged_attention_pallas(q, k_pool, v_pool, block_tables, start, *,
                           k_scale=None, v_scale=None,
                           dequant_dtype=jnp.bfloat16,
                           interpret: bool = False):
    """q: (B, T, H, D); pools: (n_phys, bs, Hk, D); tables: (B, n_blocks)
    int32; start: (B,) first query position per slot → (B, T, H, D).

    ``T`` is static (1 for decode, the window for suffix-prefill/verify);
    slot ``b``'s queries sit at positions ``start[b] .. start[b]+T-1`` and
    attend the pool causally at those positions. ``k_scale``/``v_scale``
    (``(n_phys, bs, Hk)`` f32) switch on the fused int8 dequant path;
    ``dequant_dtype`` is the dtype the gather reference materializes its
    dequantized view in (the in-register values round through it so both
    paths see bit-equal KV). Callers bound the page walk by slicing
    ``block_tables`` to the live high-water width before the call.
    """
    B, T, H, D = q.shape
    n_phys, bs, Hk, _ = k_pool.shape
    G = H // Hk
    n_blocks = block_tables.shape[1]
    sm_scale = D ** -0.5
    quantized = k_scale is not None

    # (B, Hk, T·G, D): each KV head's T·G query rows, t-major
    qg = jnp.moveaxis(q.reshape(B, T, Hk, G, D), 1, 2) \
        .reshape(B, Hk, T * G, D)
    tables = block_tables.astype(jnp.int32)
    start = start.astype(jnp.int32)

    def phys(b, j, tables_ref, start_ref):
        # dead pages all redirect to the trash page so the pipeline fetches
        # it once and elides every repeat
        live = j * bs <= start_ref[b] + T - 1
        return jnp.where(live, tables_ref[b, j], 0)

    def q_map(b, j, tables_ref, start_ref):
        return (b, 0, 0, 0)

    def kv_map(b, j, tables_ref, start_ref):
        return (phys(b, j, tables_ref, start_ref), 0, 0, 0)

    def scale_map(b, j, tables_ref, start_ref):
        return (phys(b, j, tables_ref, start_ref), 0, 0)

    # one page of every KV head per step: the block's two minor dims are
    # the pool's whole (Hk, D), which Mosaic's tiling accepts at any width
    in_specs = [
        pl.BlockSpec((1, Hk, T * G, D), q_map),
        pl.BlockSpec((1, bs, Hk, D), kv_map),
        pl.BlockSpec((1, bs, Hk, D), kv_map),
    ]
    inputs = [qg, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, Hk), scale_map),
                     pl.BlockSpec((1, bs, Hk), scale_map)]
        inputs += [k_scale, v_scale]

    out = pl.pallas_call(
        functools.partial(_paged_kernel, block_size=bs, group=G,
                          sm_scale=sm_scale, quantized=quantized,
                          dequant_dtype=dequant_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_blocks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, Hk, T * G, D), q_map),
            scratch_shapes=[pltpu.VMEM((Hk, T * G, 1), jnp.float32),
                            pltpu.VMEM((Hk, T * G, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hk, T * G, D), jnp.float32),
        interpret=interpret,
    )(tables, start, *inputs)
    out = jnp.moveaxis(out.reshape(B, Hk, T, G, D), 2, 1)
    return out.reshape(B, T, H, D).astype(q.dtype)


def paged_flash_decode(q, k_pool, v_pool, block_tables, pos, *,
                       k_scale=None, v_scale=None,
                       dequant_dtype=jnp.bfloat16, interpret: bool = False):
    """Decode instance: one query per slot at its cursor ``pos (B,)``."""
    if q.shape[1] != 1:
        raise ValueError(f"decode expects T=1 queries, got {q.shape}")
    return paged_attention_pallas(q, k_pool, v_pool, block_tables, pos,
                                  k_scale=k_scale, v_scale=v_scale,
                                  dequant_dtype=dequant_dtype,
                                  interpret=interpret)


def paged_flash_prefill(q, k_pool, v_pool, block_tables, start, *,
                        k_scale=None, v_scale=None,
                        dequant_dtype=jnp.bfloat16, interpret: bool = False):
    """Suffix-prefill / verify instance: a T-token contiguous window per
    slot starting at ``start (B,)`` (the slot's cursor)."""
    return paged_attention_pallas(q, k_pool, v_pool, block_tables, start,
                                  k_scale=k_scale, v_scale=v_scale,
                                  dequant_dtype=dequant_dtype,
                                  interpret=interpret)
