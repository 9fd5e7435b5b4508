"""Compile the main serve-path Pallas kernels for a described TPU v5e.

Interpret mode (what the rest of the suite runs on CPU) evaluates kernel
bodies in Python and accepts block shapes Mosaic refuses. Here each kernel
is compiled by the TPU compiler for a ``v5e:2x2`` topology that is described
but not attached, at real widths: zamba2-1.2b's shared attention block
(Hk = 32, D = 64) and a GQA layout as in yi-34b (Hk = 8, D = 128, G = 7) for
the paged kernel, and zamba2-1.2b's shared-block projections for
``dot_moa``. Nothing runs, so these tests say nothing about results or
times; they catch what the chip's compiler would reject.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dot_moa import dot_moa_pallas
from repro.kernels.paged_attention import paged_attention_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "kernel did not lower to Mosaic"
    return compiled


#: (Hk, G, D): zamba2-1.2b's shared block (32 heads, kv=32, head_dim 64)
#: and yi-34b's GQA layout (56 heads over 8 kv heads, head_dim 128)
WIDTHS = {"zamba2": (32, 1, 64), "gqa": (8, 7, 128)}


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("T", [1, 64], ids=["decode", "prefill64"])
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_paged_attention_compiles(one_chip, no_compile_cache, width, T, kv):
    """4 slots x 128 logical pages of 16 tokens over a 513-page pool."""
    Hk, G, D = WIDTHS[width]
    B, bs, n_blocks = 4, 16, 128
    n_phys = B * n_blocks + 1
    pool_dtype = jnp.int8 if kv == "int8" else jnp.bfloat16
    shapes = [((B, T, Hk * G, D), jnp.bfloat16),
              ((n_phys, bs, Hk, D), pool_dtype),
              ((n_phys, bs, Hk, D), pool_dtype),
              ((B, n_blocks), jnp.int32),
              ((B,), jnp.int32)]
    if kv == "int8":
        shapes += [((n_phys, bs, Hk), jnp.float32)] * 2

        def fn(q, k, v, tables, start, ks, vs):
            return paged_attention_pallas(q, k, v, tables, start,
                                          k_scale=ks, v_scale=vs,
                                          interpret=False)
    else:
        fn = functools.partial(paged_attention_pallas, interpret=False)
    _compile(fn, one_chip, *shapes)


#: the (k, n) of every contraction zamba2-1.2b routes through the MOA
#: strategy: shared-block q/k/v/o (2048 x 2048), MLP gate/up (2048 x 8192)
#: and MLP down (8192 x 2048); m = 8 is a decode batch, 2048 a prefill
DOT_SHAPES = {"qkvo": (2048, 2048), "mlp_up": (2048, 8192),
              "mlp_down": (8192, 2048)}


@pytest.mark.parametrize("m", [8, 2048])
@pytest.mark.parametrize("proj", sorted(DOT_SHAPES))
def test_dot_moa_compiles(one_chip, no_compile_cache, proj, m):
    """bf16 operands with the serve default's cluster size (block_k 2048,
    the ``serial?chunk=4096`` strategy capped by the Pallas backend)."""
    k, n = DOT_SHAPES[proj]
    fn = functools.partial(dot_moa_pallas, block_k=2048, interpret=False)
    _compile(fn, one_chip, ((m, k), jnp.bfloat16), ((k, n), jnp.bfloat16))


@pytest.mark.parametrize("mesh_shape", [(1, 4), (2, 2)],
                         ids=["1x4", "2x2"])
def test_paged_attention_compiles_partitioned(topo, no_compile_cache,
                                              monkeypatch, mesh_shape):
    """The serve path's paged attention under a (data, model) mesh of the
    described 2x2 with the tensor-parallel rules: each device's kernel gets
    its slots and its share of zamba2-1.2b's 32 KV heads."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.kernels import ops
    from repro.layers import attention as attn_lib
    from repro.parallel import DEFAULT_RULES, activate

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(mesh_shape),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    Hk, G, D = WIDTHS["zamba2"]
    B, bs, n_blocks = 4, 16, 35
    n_phys = B * n_blocks + 1

    def fn(q, k, v, tables, start):
        with activate(mesh, DEFAULT_RULES):
            return attn_lib._paged_attention_fused(
                q, {"k": k, "v": v}, tables, start)

    hlo = _compile(fn, NamedSharding(mesh, PartitionSpec()),
                   ((B, 1, Hk * G, D), jnp.bfloat16),
                   ((n_phys, bs, Hk, D), jnp.bfloat16),
                   ((n_phys, bs, Hk, D), jnp.bfloat16),
                   ((B, n_blocks), jnp.int32), ((B,), jnp.int32)).as_text()
    data, model = mesh_shape
    # the kernel's per-device operands: B / data slots, Hk / model heads
    assert f"bf16[{B // data},{Hk // model},1,{D}]" in hlo
    assert f"bf16[{n_phys},{bs},{Hk // model},{D}]" in hlo
