"""Fused paged-attention kernels vs the gathered jnp reference path.

The acceptance bar (ISSUE 8): with ``attn_backend="pallas"`` the fused
block-table flash kernels (decode T=1 and suffix-prefill/verify T=window)
produce greedy tokens **bit-identical** to the gathered ``jnp`` reference
across the dense / MoE / hybrid families, f32 / bf16 / int8 pools, uneven
per-slot depths, shared-prefix CoW tables, and a (data, model) host mesh.
On CPU the kernels run in Pallas interpret mode (``kernels/ops.py``), so
this suite exercises the real kernel bodies in CI without a TPU.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, smoke_config
from repro.kernels import ops
from repro.models.api import build_model
from repro.serve import (OracleDrafter, Request, ServeEngine,
                         shared_prefix_workload)


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


#: cfg overrides selecting the KV pool element type (the pool inherits
#: ``compute_dtype`` unless the quantized-cache knob overrides it)
POOLS = {
    "bf16": {},
    "f32": dict(compute_dtype="float32"),
    "int8": dict(kv_cache_dtype="int8"),
}


def _built(arch, rng, **cfg_updates):
    cfg = smoke_config(get_config(arch))
    if cfg_updates:
        cfg = dataclasses.replace(cfg, **cfg_updates)
    model = build_model(cfg)
    return cfg, model, model.init(rng)


def _pair(model, params, *, n_slots, max_len, block_size=8, n_blocks=None,
          drafter=False):
    def eng(backend):
        d = OracleDrafter(2) if drafter else None
        return ServeEngine(model, params, n_slots=n_slots, max_len=max_len,
                           paged=True, block_size=block_size,
                           n_blocks=n_blocks, drafter=d,
                           attn_backend=backend, clock=lambda: 0.0)
    return eng("jnp"), eng("pallas")


def _ragged_requests(rng, vocab, lens, gens):
    reqs = []
    for i, (n, g) in enumerate(zip(lens, gens)):
        toks = jax.random.randint(jax.random.fold_in(rng, i), (n,), 0, vocab)
        reqs.append(Request(uid=i, max_new_tokens=g,
                            prompt=tuple(int(t) for t in np.asarray(toks))))
    return reqs


def _assert_same_tokens(ref, got):
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a.tokens, b.tokens)


# ---------------------------------------------------------------------------
# kernel-level: fused walk vs an explicit gather reference
# ---------------------------------------------------------------------------


def _paged_ref(q, k_pool, v_pool, tables, start):
    """Dead-simple per-(slot, position, head) reference: gather the live
    pages, causal softmax in f64-free f32, GQA head sharing."""
    q, k_pool, v_pool = (np.asarray(x, np.float32)
                         for x in (q, k_pool, v_pool))
    tables, start = np.asarray(tables), np.asarray(start)
    B, T, H, D = q.shape
    _, bs, Hk, _ = k_pool.shape
    G = H // Hk
    out = np.zeros_like(q)
    for b in range(B):
        n_tok = int(start[b]) + T
        n_live = (n_tok - 1) // bs + 1
        k = k_pool[tables[b, :n_live]].reshape(-1, Hk, D)
        v = v_pool[tables[b, :n_live]].reshape(-1, Hk, D)
        for t in range(T):
            hi = int(start[b]) + t + 1          # causal horizon
            for h in range(H):
                s = k[:hi, h // G] @ q[b, t, h] * D ** -0.5
                w = np.exp(s - s.max())
                out[b, t, h] = (w / w.sum()) @ v[:hi, h // G]
    return out


class TestKernelVsGather:
    def _pool_problem(self, rng, *, B=3, T=1, Hk=2, G=2, D=16, bs=8,
                      n_blocks=4):
        """Uneven depths; dead table entries poisoned with a garbage page
        full of NaNs — the kernel must never let them into the math."""
        kq, kk, kv = jax.random.split(rng, 3)
        n_phys = B * n_blocks + 2
        q = jax.random.normal(kq, (B, T, Hk * G, D), jnp.float32)
        k_pool = jax.random.normal(kk, (n_phys, bs, Hk, D), jnp.float32)
        v_pool = jax.random.normal(kv, (n_phys, bs, Hk, D), jnp.float32)
        poison = n_phys - 1
        k_pool = k_pool.at[poison].set(jnp.nan)
        v_pool = v_pool.at[poison].set(jnp.nan)
        start = jnp.asarray([0, 5, n_blocks * bs - T], jnp.int32)
        tables = np.full((B, n_blocks), poison, np.int32)
        for b in range(B):
            n_live = (int(start[b]) + T - 1) // bs + 1
            tables[b, :n_live] = 1 + b * n_blocks + np.arange(n_live)
        return q, k_pool, v_pool, jnp.asarray(tables), start

    @pytest.mark.parametrize("T", [1, 4])
    def test_matches_gather_reference(self, rng, T):
        q, k_pool, v_pool, tables, start = self._pool_problem(rng, T=T)
        got = ops.paged_attention(q, k_pool, v_pool, tables, start)
        want = _paged_ref(q, k_pool, v_pool, tables, start)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=1e-5, atol=1e-5)

    def test_int8_dequant_in_register(self, rng):
        q, k_pool, v_pool, tables, start = self._pool_problem(rng, T=2)
        n_phys, bs, Hk, D = k_pool.shape
        ks, vs = jax.random.split(jax.random.fold_in(rng, 7))
        k_i8 = jax.random.randint(ks, k_pool.shape, -127, 128, jnp.int32)
        v_i8 = jax.random.randint(vs, v_pool.shape, -127, 128, jnp.int32)
        k_scale = jax.random.uniform(ks, (n_phys, bs, Hk), jnp.float32,
                                     0.01, 0.1)
        v_scale = jax.random.uniform(vs, (n_phys, bs, Hk), jnp.float32,
                                     0.01, 0.1)
        # dequant_dtype=f32 keeps the in-register rounding off so the
        # dense f32 reference is exact; the engine passes compute_dtype
        # (bf16) there to match the gather path bit-for-bit instead
        got = ops.paged_attention(q, k_i8.astype(jnp.int8),
                                  v_i8.astype(jnp.int8), tables, start,
                                  k_scale=k_scale, v_scale=v_scale,
                                  dequant_dtype=jnp.float32)
        want = _paged_ref(q, k_i8 * k_scale[..., None],
                          v_i8 * v_scale[..., None], tables, start)
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=1e-5, atol=1e-5)
        rounded = ops.paged_attention(q, k_i8.astype(jnp.int8),
                                      v_i8.astype(jnp.int8), tables, start,
                                      k_scale=k_scale, v_scale=v_scale)
        bf16 = lambda x: np.asarray(jnp.asarray(x).astype(jnp.bfloat16),
                                    np.float32)
        want_bf16 = _paged_ref(q, bf16(k_i8 * k_scale[..., None]),
                               bf16(v_i8 * v_scale[..., None]), tables, start)
        np.testing.assert_allclose(np.asarray(rounded, np.float32),
                                   want_bf16, rtol=1e-5, atol=1e-5)

    def test_table_width_invariance(self, rng):
        """Appending dead columns (the high-water bucket padding) must not
        change a single output bit — that is what makes the engine's
        power-of-two bucketing safe."""
        q, k_pool, v_pool, tables, start = self._pool_problem(rng, T=1)
        narrow = ops.paged_attention(q, k_pool, v_pool, tables, start)
        wide_tables = jnp.concatenate(
            [tables, jnp.full((tables.shape[0], 3), int(tables[0, -1]),
                              jnp.int32)], axis=1)
        wide = ops.paged_attention(q, k_pool, v_pool, wide_tables, start)
        np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))


# ---------------------------------------------------------------------------
# engine-level: families x pools, bit-identical greedy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["llama3-8b", "moonshot-v1-16b-a3b",
                                  "zamba2-1.2b"])
@pytest.mark.parametrize("pool", ["bf16", "f32", "int8"])
def test_fused_matches_gather_greedy(rng, arch, pool):
    """4 requests into 2 slots (slot reuse mid-flight), prompts off the
    block boundary, staggered generation lengths."""
    if arch == "zamba2-1.2b" and pool == "int8":
        pytest.skip("hybrid KV pool follows compute_dtype; no int8 variant")
    cfg, model, params = _built(arch, rng, **POOLS[pool])
    reqs = lambda: _ragged_requests(rng, cfg.vocab, [13, 9, 21, 5],
                                    [5, 7, 3, 6])
    jnp_eng, pl_eng = _pair(model, params, n_slots=2, max_len=32)
    ref, ref_report = jnp_eng.run(reqs())
    got, report = pl_eng.run(reqs())
    _assert_same_tokens(ref, got)
    assert ref_report["paged"]["attn_backend"] == "jnp"
    assert report["paged"]["attn_backend"] == "pallas"
    pl_eng._pool.check()
    assert pl_eng._pool.in_use == 0


def test_uneven_depths_cross_buckets(rng):
    """A deep sequence (several pages) beside near-empty ones: the
    live-block high-water bucket grows mid-run and both backends retrace
    per bucket — tokens must stay bit-identical throughout."""
    cfg, model, params = _built("llama3-8b", rng)
    reqs = lambda: _ragged_requests(rng, cfg.vocab, [37, 3, 18],
                                    [11, 4, 9])
    jnp_eng, pl_eng = _pair(model, params, n_slots=3, max_len=64)
    ref, _ = jnp_eng.run(reqs())
    got, report = pl_eng.run(reqs())
    _assert_same_tokens(ref, got)
    # the deep slot forces more than one bucket over the run
    steps = report["paged"]
    assert steps["fused_kv_bytes"] < steps["gathered_kv_bytes"]


def test_shared_prefix_cow_tables(rng):
    """Prefix hits + CoW spares produce non-contiguous physical tables;
    the fused walk must follow them exactly."""
    cfg, model, params = _built("llama3-8b", rng)
    reqs = lambda: shared_prefix_workload(
        n_requests=6, vocab=cfg.vocab, rate_rps=100.0, n_prefixes=2,
        prefix_len=16, suffix_len_range=(1, 6), gen_len_range=(3, 6),
        seed=7)
    jnp_eng, pl_eng = _pair(model, params, n_slots=3, max_len=64)
    ref, _ = jnp_eng.run(reqs())
    got, report = pl_eng.run(reqs())
    _assert_same_tokens(ref, got)
    assert report["paged"]["prefix_hits"] > 0
    pl_eng._pool.check()


def test_identical_prompts_cow_match(rng):
    """MoE full-prefill family: identical non-block-aligned prompts share
    the partial tail page and each follower CoWs it on its first write —
    the fused walk must read through the repointed table entries."""
    cfg, model, params = _built("moonshot-v1-16b-a3b", rng)
    p = tuple(int(t) for t in
              np.asarray(jax.random.randint(rng, (12,), 0, cfg.vocab)))
    reqs = lambda: [Request(uid=i, prompt=p, max_new_tokens=6,
                            arrival_s=0.1 * i) for i in range(3)]
    jnp_eng, pl_eng = _pair(model, params, n_slots=3, max_len=32)
    ref, _ = jnp_eng.run(reqs())
    got, report = pl_eng.run(reqs())
    _assert_same_tokens(ref, got)
    assert report["paged"]["cow_count"] >= 2


def test_spec_decode_verify_kernel(rng):
    """Speculative decoding drives the T=window verify instance of the
    kernel; accepted tokens must match the gathered path exactly."""
    cfg, model, params = _built("llama3-8b", rng)
    reqs = lambda: _ragged_requests(rng, cfg.vocab, [9, 14], [8, 6])
    jnp_eng, pl_eng = _pair(model, params, n_slots=2, max_len=48,
                            drafter=True)
    ref, _ = jnp_eng.run(reqs())
    got, report = pl_eng.run(reqs())
    _assert_same_tokens(ref, got)
    assert report["spec"]["verify_ticks"] > 0


def test_fused_bytes_never_exceed_gathered(rng):
    """The structural invariant the serving-v6 schema enforces on
    records, checked at the source: at every step the fused walk reads at
    most what the gather materializes."""
    cfg, model, params = _built("llama3-8b", rng)
    reqs = _ragged_requests(rng, cfg.vocab, [13, 9, 21, 5], [5, 7, 3, 6])
    _, pl_eng = _pair(model, params, n_slots=2, max_len=32)
    _, report = pl_eng.run(reqs)
    pg = report["paged"]
    assert pg["fused_kv_bytes"] <= pg["gathered_kv_bytes"]
    assert pl_eng._kv_step_log, "no per-step byte log recorded"
    for g, f in pl_eng._kv_step_log:
        assert f <= g


# ---------------------------------------------------------------------------
# mesh subprocess: (data=2, model=4) host devices, jnp vs pallas
# ---------------------------------------------------------------------------

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "src")
import numpy as np
import jax
from repro.configs.registry import ARCHS, smoke_config
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.serve import OracleDrafter, ServeEngine, poisson_workload

cfg = smoke_config(ARCHS[sys.argv[1]])
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = make_mesh((2, 4))
out = {"parity": {}}


def workload():
    return poisson_workload(n_requests=4, vocab=cfg.vocab, rate_rps=100.0,
                            prompt_len_range=(4, 10), gen_len_range=(2, 6),
                            seed=0)


for spec in (False, True):
    runs = []
    for backend in ("jnp", "pallas"):
        drafter = OracleDrafter(2) if spec else None
        eng = ServeEngine(model, params, n_slots=2, max_len=32, paged=True,
                          block_size=8, drafter=drafter, mesh=mesh,
                          attn_backend=backend)
        results, report = eng.run(workload(), warmup=True)
        runs.append([[int(t) for t in r.tokens] for r in results])
    out["parity"]["spec=%s" % spec] = runs[0] == runs[1]
print(json.dumps(out))
"""


@pytest.mark.slow
def test_mesh_fused_parity():
    """Fused kernel under GSPMD on a (data=2, model=4) host mesh: greedy
    tokens match the gathered backend for plain and speculative decode."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT, "llama3-8b"],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    for combo, ok in result["parity"].items():
        assert ok, f"{combo}: fused tokens diverged from gathered on mesh"


_TP_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "src")
import jax
from repro.configs.registry import ARCHS, smoke_config
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.moa import resolve
from repro.parallel import DEFAULT_RULES, activate
from repro.serve import ServeEngine, poisson_workload

cfg = smoke_config(ARCHS["zamba2-1.2b"])
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = make_mesh((2, 4))
out = {"kv_heads": cfg.n_kv_heads}
runs = []
for backend in ("jnp", "pallas"):
    eng = ServeEngine(model, params, n_slots=2, max_len=32, paged=True,
                      block_size=8, mesh=mesh, rules=DEFAULT_RULES,
                      attn_backend=backend)
    out["pool_spec"] = str(eng.cache["kv"]["k"].sharding.spec)
    results, _ = eng.run(poisson_workload(
        n_requests=4, vocab=cfg.vocab, rate_rps=100.0,
        prompt_len_range=(4, 10), gen_len_range=(2, 6), seed=0), warmup=True)
    runs.append([[int(t) for t in r.tokens] for r in results])
out["parity"] = runs[0] == runs[1]
with activate(mesh, DEFAULT_RULES):
    out["moa_auto"] = resolve("serial?chunk=32").resolve_backend()
    try:
        resolve("serial?backend=pallas&chunk=32").resolve_backend()
        out["moa_pallas_refused"] = False
    except ValueError:
        out["moa_pallas_refused"] = True
print(json.dumps(out))
"""


@pytest.mark.slow
def test_mesh_fused_parity_tensor_parallel():
    """The fused kernel partitioned over a (data=2, model=4) host mesh with
    the tensor-parallel rules: slots over ``data``, the hybrid's KV heads
    over ``model`` (each device attends its own heads); greedy tokens match
    the gathered backend. Under the mesh the MOA strategies resolve
    ``auto`` to their jnp paths and refuse an explicit ``pallas``."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _TP_SUBPROCESS_SCRIPT],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["kv_heads"] % 4 == 0
    assert "'model'" in result["pool_spec"], result["pool_spec"]
    assert result["parity"], "fused tokens diverged from gathered on mesh"
    assert result["moa_auto"] == "jnp"
    assert result["moa_pallas_refused"]
