"""Sharded serving: mesh-backed ServeEngine parity + sharding specs.

The paper's core lesson — a mapping that looks right on paper must be
validated on the actual device topology — applied to the serve stack: the
subprocess tests force 8 XLA host-platform devices (device count locks at
first backend init, so this cannot run in the test process), build a
``(data=2, model=4)`` mesh, and require **bit-identical greedy tokens**
between the single-device and sharded engines across dense / MoE / hybrid
families, dense-slot and paged KV layouts, plain and speculative decode.

In-process tests cover the pure pieces: the family rules table
(``serve_rules_for``), the cache-sharding inference
(``serve_cache_shardings``), and the CLI mesh-spec parser.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh, parse_mesh
from repro.parallel import (DEFAULT_RULES, serve_cache_shardings,
                            serve_rules_for)

# ---------------------------------------------------------------------------
# pure logic (no extra devices needed)
# ---------------------------------------------------------------------------


class TestServeRules:
    def test_attention_families_keep_tp(self):
        for family in ("dense", "moe"):
            rules = serve_rules_for(family)
            assert rules.lookup("heads") == "model"
            assert rules.lookup("ff") == "model"
            assert rules.lookup("experts") == "model"
            assert rules.lookup("kv_heads_cache") == "model"

    def test_recurrent_families_replicate_model_axis(self):
        """Split contractions feed the recurrence and compound rounding —
        ssm/hybrid serve data-parallel with the model axis idle."""
        for family in ("ssm", "hybrid"):
            rules = serve_rules_for(family)
            for name in ("heads", "kv_heads", "kv_heads_cache", "ff",
                         "experts", "vocab", "ssm_inner", "ssm_heads"):
                assert rules.lookup(name) is None, (family, name)
            # slots still shard over the data axis
            assert rules.lookup("batch") == ("pod", "data")

    def test_base_rules_not_mutated(self):
        serve_rules_for("hybrid")
        assert DEFAULT_RULES.lookup("heads") == "model"


class TestCacheShardings:
    def _mesh(self):
        return make_mesh((1, 1))

    def test_dense_slot_layout(self):
        mesh = self._mesh()
        cache = jax.eval_shape(lambda: {
            "layers": {
                "k": jax.ShapeDtypeStruct((2, 4, 32, 2, 16), "bfloat16"),
                "v": jax.ShapeDtypeStruct((2, 4, 32, 2, 16), "bfloat16"),
            },
            "pos": jax.ShapeDtypeStruct((4,), "int32"),
        })
        sh = serve_cache_shardings(cache, mesh, DEFAULT_RULES)
        assert sh["layers"]["k"].spec == P(None, "data", None,
                                           "model", None)
        assert sh["pos"].spec == P("data")

    def test_paged_pool_blocks_replicate(self):
        """Physical pages are shared across slots: the block axis must not
        shard (block tables are logical, host-side) — only heads do."""
        mesh = self._mesh()
        cache = jax.eval_shape(lambda: {
            "layers": {
                "k": jax.ShapeDtypeStruct((2, 17, 8, 2, 16), "bfloat16"),
                "v": jax.ShapeDtypeStruct((2, 17, 8, 2, 16), "bfloat16"),
            },
            "block_tables": jax.ShapeDtypeStruct((4, 4), "int32"),
            "pos": jax.ShapeDtypeStruct((4,), "int32"),
        })
        sh = serve_cache_shardings(cache, mesh, DEFAULT_RULES, paged=True)
        assert sh["layers"]["k"].spec == P(None, None, None, "model", None)
        assert sh["block_tables"].spec == P("data", None)

    def test_indivisible_dims_replicate(self):
        """A dim the mesh axis does not divide (3 slots over data=2, GQA
        kv=1 over model=4) replicates instead of erroring."""
        from repro.parallel.sharding import _drop_indivisible

        class _Mesh:                      # duck-typed 2x4 topology
            axis_names = ("data", "model")

            class devices:
                shape = (2, 4)

        assert _drop_indivisible((3, 32), P("data", "model"), _Mesh) \
            == P(None, "model")
        assert _drop_indivisible((4, 6), P("data", "model"), _Mesh) \
            == P("data", None)

    def test_ssm_state_stays_per_slot(self):
        mesh = self._mesh()
        cache = jax.eval_shape(lambda: {
            "ssm": {
                "h": jax.ShapeDtypeStruct((3, 4, 8, 16, 16), "float32"),
                "conv": jax.ShapeDtypeStruct((3, 4, 3, 160), "float32"),
            },
            "pos": jax.ShapeDtypeStruct((4,), "int32"),
        })
        rules = serve_rules_for("hybrid")
        sh = serve_cache_shardings(cache, mesh, rules)
        assert sh["ssm"]["h"].spec == P(None, "data", None, None, None)
        assert sh["ssm"]["conv"].spec == P(None, "data", None, None)


class TestParseMesh:
    def test_two_and_three_axis(self):
        assert parse_mesh("2x4") == (2, 4)
        assert parse_mesh("2X4") == (2, 4)
        assert parse_mesh("2x2x2") == (2, 2, 2)

    @pytest.mark.parametrize("bad", ["", "8", "2x0", "axb", "1x2x3x4"])
    def test_rejects_bad_specs(self, bad):
        with pytest.raises(ValueError):
            parse_mesh(bad)


def test_make_mesh_axes_accept_sharding_constraints():
    """Meshes carry Auto axes: the model code places arrays with
    ``with_sharding_constraint``, which refuses Explicit axes (the
    default of ``jax.make_mesh``)."""
    from jax.sharding import AxisType, NamedSharding

    mesh = make_mesh((1, 1))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
    x = jax.jit(lambda a: jax.lax.with_sharding_constraint(
        a * 2, NamedSharding(mesh, P("data", "model"))))(jnp.ones((2, 2)))
    assert float(x.sum()) == 8.0


# ---------------------------------------------------------------------------
# 8-device subprocess: parity matrix + spec assertions + no-transfer check
# ---------------------------------------------------------------------------

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
sys.path.insert(0, "src")
import numpy as np
import jax
import jax.numpy as jnp
from repro.configs.registry import ARCHS, smoke_config
from repro.launch.mesh import make_mesh
from repro.models.api import build_model
from repro.serve import OracleDrafter, ServeEngine, poisson_workload

arch = sys.argv[1]
cfg = smoke_config(ARCHS[arch])
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = make_mesh((2, 4))
out = {"family": cfg.family, "parity": {}}


def workload():
    return poisson_workload(n_requests=4, vocab=cfg.vocab, rate_rps=100.0,
                            prompt_len_range=(4, 10), gen_len_range=(2, 6),
                            seed=0)


def tokens(results):
    return [[int(t) for t in r.tokens] for r in results]


pageable = model.cache_spec().pageable
for paged in (False, True):
    if paged and not pageable:
        continue
    for spec in (False, True):
        kw = dict(n_slots=2, max_len=32)
        if paged:
            kw.update(paged=True, block_size=8)
        runs = []
        for m in (None, mesh):
            drafter = OracleDrafter(2) if spec else None
            eng = ServeEngine(model, params, **kw, drafter=drafter, mesh=m)
            results, report = eng.run(workload(), warmup=True)
            runs.append(tokens(results))
        out["parity"]["paged=%s,spec=%s" % (paged, spec)] = runs[0] == runs[1]

eng = ServeEngine(model, params, n_slots=2, max_len=32, mesh=mesh)


def spec_of(leaf):
    return [list(e) if isinstance(e, tuple) else e
            for e in leaf.sharding.spec]


if cfg.family in ("dense", "moe"):
    out["wq_spec"] = spec_of(eng.params["layers"]["attn"]["wq"])
if cfg.family == "dense":
    out["w_gate_spec"] = spec_of(eng.params["layers"]["mlp"]["w_gate"])
if cfg.family == "moe":
    out["moe_gate_spec"] = spec_of(eng.params["layers"]["moe"]["w_gate"])
    out["cache_k_spec"] = spec_of(eng.cache["layers"]["k"])
if cfg.family == "hybrid":
    out["shared_wq_spec"] = spec_of(eng.params["shared_attn"]["wq"])

# no-transfer check: one decode tick leaves every (donated) cache leaf's
# sharding unchanged — nothing reshards at the jit boundary
before = jax.tree.map(lambda a: str(a.sharding), eng.cache)
_, eng.cache = eng._decode(eng.params, eng.cache,
                           jnp.zeros((2, 1), jnp.int32))
after = jax.tree.map(lambda a: str(a.sharding), eng.cache)
out["decode_sharding_stable"] = bool(jax.tree.all(
    jax.tree.map(lambda a, b: a == b, before, after)))

# regression: a second mesh engine whose slot count the data axis does not
# divide (3 over data=2 -> replicated slot axis) bakes different sharding
# specs — it must not reuse the 2-slot engine's cached jit
eng3 = ServeEngine(model, params, n_slots=3, max_len=32, mesh=mesh)
_, eng3.cache = eng3._decode(eng3.params, eng3.cache,
                             jnp.zeros((3, 1), jnp.int32))
out["mixed_slot_layouts_ok"] = True
print(json.dumps(out))
"""


def _run_subprocess(arch):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT, arch],
        capture_output=True, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["llama3-8b", "moonshot-v1-16b-a3b",
                                  "zamba2-1.2b"])
def test_sharded_greedy_parity_matrix(arch):
    """Greedy decode on a (data=2, model=4) host mesh is bit-identical to
    single-device for every cache layout x decode mode of the family, the
    cache never reshards across a decode step, and the params land with
    the documented specs (TP for attention families, replicated for the
    recurrent hybrid)."""
    result = _run_subprocess(arch)
    assert result["parity"], "no parity combos ran"
    for combo, ok in result["parity"].items():
        assert ok, f"{arch} {combo}: sharded tokens diverged"
    assert result["decode_sharding_stable"]
    assert result["mixed_slot_layouts_ok"]

    def flat(spec):
        return [a for e in spec if e is not None
                for a in (e if isinstance(e, list) else [e])]

    if result["family"] == "dense":
        assert "model" in flat(result["wq_spec"])      # heads -> model
        assert "model" in flat(result["w_gate_spec"])  # ff -> model
    if result["family"] == "moe":
        assert "model" in flat(result["moe_gate_spec"])  # experts -> model
        assert "model" in flat(result["cache_k_spec"])   # kv head sharding
    if result["family"] == "hybrid":
        assert flat(result["shared_wq_spec"]) == []    # fully replicated
