"""Pallas TPU kernel: K-blocked matmul with a serialized-MOA contraction.

The contraction (K) dimension of ``(m, k) @ (k, n)`` is the MOA of every
dense layer. This kernel schedules it the way the paper's §3.1 *wanted* to
— time-multiplexed into an accumulator — on hardware where that actually
wins because serializer (DMA) and accumulator (MXU f32) are hard-wired:

  grid = (m_blocks, n_blocks, k_blocks); the trailing K dimension is
  sequential on TPU, each step issuing one ``block_m × block_k`` ×
  ``block_k × block_n`` MXU contraction accumulated into the f32 output
  block held in VMEM.

Variants:
  * float (f32/bf16 in, f32 accumulate — the MXU's hard-wired behaviour);
  * int8 (int8 in, int32 accumulate — the paper's 8-bit operand regime);
  * int8 + LOA accumulator (``approx_bits > 0``): every fold of a K-block
    partial into the accumulator goes through the Lower-part-OR adder —
    the §3.2 approximate MOA, measurably *not cheaper* (see
    benchmarks/fig5_loa.py): the LOA fold costs ~6 VPU ops where the exact
    fold is a single hard add the MXU gives away for free.

Block sizes default to MXU-aligned (multiples of 128 on the matmul dims).
VMEM per step holds the double-buffered input tiles, their f32 up-casts and
the f32 output block. The TPU v5e compiler gives a kernel a 16 MiB scoped
VMEM limit by default: the serve default in bf16 (256 × 2048 × 256 blocks)
compiles under it, while 512 × 4096 × 512 asks for 18 MiB and is refused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["dot_moa_pallas"]


def _loa_combine(x, y, *, approx_bits: int):
    if approx_bits == 0:
        return x + y
    l = approx_bits
    mask_l = jnp.int32((1 << l) - 1)
    low = (x & mask_l) | (y & mask_l)
    cin = ((x >> (l - 1)) & (y >> (l - 1))) & jnp.int32(1)
    high = (x >> l) + (y >> l) + cin
    return (high << l) | low


def _dot_moa_kernel(a_ref, b_ref, o_ref, *, accum_dtype, approx_bits):
    k = pl.program_id(2)
    partial = jnp.dot(
        a_ref[...].astype(accum_dtype),
        b_ref[...].astype(accum_dtype),
        preferred_element_type=accum_dtype,
    )

    @pl.when(k == 0)
    def _init():
        o_ref[...] = partial.astype(o_ref.dtype)

    @pl.when(k != 0)
    def _accum():
        if approx_bits > 0:
            o_ref[...] = _loa_combine(
                o_ref[...], partial.astype(o_ref.dtype), approx_bits=approx_bits
            )
        else:
            o_ref[...] += partial.astype(o_ref.dtype)


def dot_moa_pallas(a: jax.Array, b: jax.Array, *, block_m: int = 256,
                   block_n: int = 256, block_k: int = 512,
                   approx_bits: int = 0, out_dtype=None,
                   interpret: bool = False) -> jax.Array:
    """``a @ b`` with serialized-MOA contraction.

    Args:
      a: ``(m, k)``; b: ``(k, n)``. Floats accumulate in f32, ints in int32.
      block_k: the cluster size ``n_c`` — how many operands fold per
        sequential step.
      approx_bits: LOA ``l`` for the accumulator folds (int paths only).
    """
    (m, k), (k2, n) = a.shape, b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    is_int = jnp.issubdtype(a.dtype, jnp.integer)
    if approx_bits and not is_int:
        raise TypeError("LOA accumulation requires integer operands")
    accum_dtype = jnp.int32 if is_int else jnp.float32
    out_dtype = out_dtype or (jnp.int32 if is_int else a.dtype)

    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    pad_m, pad_n, pad_k = -m % block_m, -n % block_n, -k % block_k
    if approx_bits and pad_k:
        # Zero-padding inserts exact-zero folds into the approximate
        # accumulator chain, which would change LOA semantics vs the oracle.
        raise ValueError(f"k={k} must be a multiple of block_k={block_k} for LOA")
    if pad_m or pad_k:
        a = jnp.pad(a, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        b = jnp.pad(b, ((0, pad_k), (0, pad_n)))
    m_p, k_p = a.shape
    _, n_p = b.shape

    grid = (m_p // block_m, n_p // block_n, k_p // block_k)
    out = pl.pallas_call(
        functools.partial(
            _dot_moa_kernel, accum_dtype=accum_dtype, approx_bits=approx_bits
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m_p, n_p), accum_dtype),
        interpret=interpret,
    )(a, b)
    return out[:m, :n].astype(out_dtype)
