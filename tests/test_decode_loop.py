"""The SSM and hybrid decode steps' layer loop.

``mamba2.decode_step`` and ``zamba2.decode_step`` / ``paged_decode_step``
read each layer's weights from the stacked ``(L, ...)`` parameters by index
and carry the whole recurrent state and KV stack through the loop, writing
each layer's new state back in place. Two properties hold them to that:

* the loop does the arithmetic of a plain Python ``for`` over the layers
  with static indexing, so logits and every cache leaf match it exactly;
* compiled with the cache donated, the step needs temporaries well under
  the cache's own size: no whole copy of the state or the pool is made.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, smoke_config
from repro.layers import attention as attn_lib
from repro.layers.common import rms_norm
from repro.layers.embedding import embed, unembed
from repro.layers.mlp import swiglu
from repro.layers.ssd import mamba2_decode
from repro.models.api import build_model

B = 4
N_PAGES, PAGE, MAX_BLOCKS = 40, 8, 8


def _cfg(arch, n_layers, attn_every=0, **kw):
    cfg = smoke_config(get_config(arch))
    return dataclasses.replace(cfg, n_layers=n_layers, attn_every=attn_every,
                               **kw)


def _randomize(tree, key):
    """Every floating leaf drawn from N(0, 1) in its own dtype."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return treedef.unflatten([
        jax.random.normal(k, a.shape, jnp.float32).astype(a.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a
        for k, a in zip(keys, leaves)])


def _cache(model, layout):
    if layout == "paged":
        cache = model.init_paged_cache(B, N_PAGES, PAGE, MAX_BLOCKS)
    else:
        cache = model.init_cache(B, N_PAGES * PAGE)
    cache = _randomize(cache, jax.random.PRNGKey(3))
    cache["pos"] = jnp.array([5, 17, 0, 40], jnp.int32)
    if layout == "paged":
        # distinct pages per slot, so no two slots write one page
        pages = jax.random.permutation(jax.random.PRNGKey(5), N_PAGES - 1)
        cache["block_tables"] = (pages[:B * MAX_BLOCKS] + 1).reshape(
            B, MAX_BLOCKS).astype(jnp.int32)
    return cache


def _plain_decode(params, cache, tokens, cfg, layout):
    """The reference: one Python ``for`` over the layers, static indices."""
    pick = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731
    ssm_key = "ssm" if cfg.family == "hybrid" else "layers"
    h = embed(params["embed"], tokens, compute_dtype=cfg.cdtype)
    states, kvs = [], []
    for i in range(cfg.n_layers):
        layer = pick(params["layers"], i)
        y, state = mamba2_decode(
            layer["mixer"], rms_norm(layer["norm"], h),
            pick(cache[ssm_key], i), d_state=cfg.d_state,
            headdim=cfg.headdim, n_groups=cfg.n_groups, expand=cfg.expand,
            compute_dtype=cfg.cdtype)
        h = h + y
        states.append(state)
        g, last = divmod(i + 1, cfg.attn_every or cfg.n_layers + 1)
        if cfg.attn_every and last == 0:
            norms = pick(params["app_norms"], g - 1)
            hn = rms_norm(norms["attn"], h)
            kw = dict(n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                      head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                      compute_dtype=cfg.cdtype,
                      strategy=cfg.moa_for("attention"))
            if layout == "paged":
                a, kv = attn_lib.attention_decode_paged(
                    params["shared_attn"], hn, pick(cache["kv"], g - 1),
                    cache["block_tables"], cache["pos"], backend="jnp",
                    live_blocks=MAX_BLOCKS, **kw)
            else:
                a, kv = attn_lib.attention_decode(
                    params["shared_attn"], hn, pick(cache["kv"], g - 1),
                    cache["pos"], **kw)
            h = h + a
            kvs.append(kv)
            h = h + swiglu(params["shared_mlp"],
                           rms_norm(norms["mlp"], h),
                           strategy=cfg.moa_for("mlp"),
                           compute_dtype=cfg.cdtype)
    h = rms_norm(params["final_norm"], h)
    logits = unembed(params["embed"], h, compute_dtype=cfg.cdtype)
    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)  # noqa
    new = dict(cache, pos=cache["pos"] + 1)
    new[ssm_key] = stack(states)
    if kvs:
        new["kv"] = stack(kvs)
    return logits, new


def _exact(fn, *args):
    """``fn(*args)`` compiled so that every op rounds to its own dtype:
    with excess precision allowed, XLA may keep a fused chain of bf16 ops
    in f32, and where it fuses differs between a loop and its unrolling."""
    return jax.jit(fn).lower(*args).compile(
        {"xla_allow_excess_precision": False})(*args)


def _step(model, layout):
    if layout == "paged":
        return lambda p, c, t: model.paged_decode_step(
            p, c, t, live_blocks=MAX_BLOCKS)
    return model.decode_step


CASES = [
    ("zamba2-1.2b", "dense", 6, 3),
    ("zamba2-1.2b", "dense", 8, 3),
    ("zamba2-1.2b", "paged", 6, 3),
    ("zamba2-1.2b", "paged", 8, 3),
    ("mamba2-370m", "dense", 5, 0),
]


@pytest.mark.parametrize(
    "arch,layout,n_layers,attn_every", CASES,
    ids=[f"{a.split('-')[0]}-{lay}-L{n}" + (f"-every{e}" if e else "")
         for a, lay, n, e in CASES])
def test_decode_matches_plain_layer_loop(arch, layout, n_layers, attn_every):
    cfg = _cfg(arch, n_layers, attn_every, attn_backend="jnp")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    cache = _cache(model, layout)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, 1), 0, cfg.vocab)

    want_logits, want = _exact(
        lambda p, c, t: _plain_decode(p, c, t, cfg, layout),
        params, cache, tokens)
    got_logits, got = _exact(_step(model, layout), params, cache, tokens)

    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(want_logits))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


#: zamba2's own depth and grouping (38 layers, the shared block after every
#: 6, two tail layers) and mamba2's depth, at smoke widths with 32 slots, so
#: that the recurrent state outweighs the weights. The paged case computes
#: (and pools its KV) in f32: the CPU backend scatters into a bf16 array
#: through an f32 copy of the whole array, which a TPU does not
GUARD = [
    ("zamba2-1.2b", "dense", 38, 6, "bfloat16"),
    ("zamba2-1.2b", "paged", 38, 6, "float32"),
    ("mamba2-370m", "dense", 48, 0, "bfloat16"),
]


@pytest.mark.parametrize("arch,layout,n_layers,attn_every,dtype", GUARD,
                         ids=[f"{a.split('-')[0]}-{lay}"
                              for a, lay, *_ in GUARD])
def test_decode_updates_donated_cache_in_place(arch, layout, n_layers,
                                               attn_every, dtype):
    slots = 32
    cfg = _cfg(arch, n_layers, attn_every, attn_backend="jnp",
               compute_dtype=dtype)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if layout == "paged":
        cache = jax.eval_shape(lambda: model.init_paged_cache(
            slots, 512, 16, 8))
        step = lambda p, c, t: model.paged_decode_step(  # noqa: E731
            p, c, t, live_blocks=2)
    else:
        cache = jax.eval_shape(lambda: model.init_cache(slots, 128))
        cache["pos"] = jax.ShapeDtypeStruct((slots,), jnp.int32)
        step = model.decode_step
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, tokens).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < cache_bytes / 4, (temp, cache_bytes)
