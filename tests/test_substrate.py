"""Substrate tests: data determinism, optimizer, compression, checkpoints,
fault-tolerance runtime."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis package")
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLMData, host_shard
from repro.optim import (AdamWConfig, adamw_init, adamw_update,
                         compress_int8, compressed_gradients, cosine_schedule,
                         decompress_int8, init_error_feedback)
from repro.runtime import (FailureInjector, HeartbeatMonitor,
                           SimulatedFailure, Supervisor, plan_mesh_shape)


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------

class TestData:
    def test_step_indexing_deterministic(self):
        d = SyntheticLMData(vocab=100, seq_len=16, global_batch=4, seed=3)
        b1 = d.batch_for_step(7)
        b2 = d.batch_for_step(7)
        np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                      np.asarray(b2["tokens"]))

    def test_steps_differ(self):
        d = SyntheticLMData(vocab=100, seq_len=16, global_batch=4)
        assert not np.array_equal(np.asarray(d.batch_for_step(0)["tokens"]),
                                  np.asarray(d.batch_for_step(1)["tokens"]))

    def test_labels_are_shifted_tokens(self):
        d = SyntheticLMData(vocab=100, seq_len=16, global_batch=2)
        b = d.batch_for_step(0)
        np.testing.assert_array_equal(np.asarray(b["tokens"][:, 1:]),
                                      np.asarray(b["labels"][:, :-1]))

    def test_host_shard_partitions(self):
        d = SyntheticLMData(vocab=100, seq_len=8, global_batch=8)
        b = d.batch_for_step(0)
        parts = [host_shard(b, h, 4)["tokens"] for h in range(4)]
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p) for p in parts]),
            np.asarray(b["tokens"]))

    def test_bigram_learnable_structure(self):
        """noise=0 ⇒ next token is a deterministic function of prev."""
        d = SyntheticLMData(vocab=97, seq_len=32, global_batch=4, noise=0.0)
        t = np.asarray(d.batch_for_step(0)["tokens"])
        a = 2 * (d.seed % 1000) + 1
        c = (d.seed * 7919 + 13) % d.vocab
        np.testing.assert_array_equal(t[:, 1:], (t[:, :-1] * a + c) % 97)


# ---------------------------------------------------------------------------
# optimizer + schedules
# ---------------------------------------------------------------------------

class TestAdamW:
    def test_converges_on_quadratic(self):
        params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
        opt = adamw_init(params)
        cfg = AdamWConfig(weight_decay=0.0, clip_norm=None)
        for step in range(200):
            g = jax.grad(lambda p: jnp.sum((p["w"] - 1.0) ** 2))(params)
            params, opt, _ = adamw_update(g, opt, params, lr=0.05, config=cfg)
        np.testing.assert_allclose(np.asarray(params["w"]), 1.0, atol=1e-2)

    def test_clip_bounds_update(self):
        params = {"w": jnp.zeros((3,))}
        opt = adamw_init(params)
        g = {"w": jnp.asarray([1e6, -1e6, 1e6])}
        _, _, metrics = adamw_update(g, opt, params, lr=1e-3,
                                     config=AdamWConfig(clip_norm=1.0))
        assert float(metrics["grad_norm"]) > 1e5  # reported pre-clip

    def test_cosine_schedule_shape(self):
        lrs = [float(cosine_schedule(s, peak_lr=1.0, warmup_steps=10,
                                     total_steps=100)) for s in range(100)]
        assert lrs[0] < lrs[9] <= 1.0
        assert abs(max(lrs) - 1.0) < 0.01
        assert lrs[-1] < 0.2


class TestCompression:
    def test_roundtrip_error_bounded(self, rng):
        x = jax.random.normal(rng, (1000,))
        q, s = compress_int8(x)
        err = np.abs(np.asarray(decompress_int8(q, s) - x))
        assert err.max() <= float(s) * 0.5 + 1e-6

    @given(seed=st.integers(0, 50))
    @settings(max_examples=10, deadline=None)
    def test_error_feedback_accumulates_residual(self, seed):
        """Σ_t deq_t ≈ Σ_t g_t: residue is carried, not lost."""
        rng = np.random.default_rng(seed)
        g_true = {"w": jnp.asarray(rng.standard_normal(64), jnp.float32)}
        err = init_error_feedback(g_true)
        total_deq = np.zeros(64)
        T = 50
        for _ in range(T):
            deq, err = compressed_gradients(g_true, err)
            total_deq += np.asarray(deq["w"])
        drift = np.abs(total_deq - T * np.asarray(g_true["w"])).max()
        # leftover residue is at most one quantization step
        assert drift <= float(np.abs(np.asarray(g_true["w"])).max() / 127) + 1e-4

    def test_compression_changes_single_step(self, rng):
        g = {"w": jax.random.normal(rng, (64,))}
        err = init_error_feedback(g)
        deq, _ = compressed_gradients(g, err)
        assert not np.allclose(np.asarray(deq["w"]), np.asarray(g["w"]))


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

class TestCheckpoint:
    def _tree(self, k=0):
        return {"params": {"w": jnp.arange(6, dtype=jnp.float32) + k,
                           "b": jnp.ones((2,), jnp.bfloat16) * k},
                "step": jnp.asarray(k, jnp.int32)}

    def test_roundtrip(self, tmp_path):
        m = CheckpointManager(str(tmp_path))
        m.save(3, self._tree(3), metadata={"loss": 1.5})
        restored, meta = m.restore(jax.eval_shape(lambda: self._tree()))
        assert meta["loss"] == 1.5
        np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                      np.asarray(self._tree(3)["params"]["w"]))
        assert restored["params"]["b"].dtype == jnp.bfloat16

    def test_latest_and_retention(self, tmp_path):
        m = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 5, 9, 12):
            m.save(s, self._tree(s))
        assert m.latest_step() == 12
        assert m.available_steps() == [9, 12]

    def test_async_save(self, tmp_path):
        m = CheckpointManager(str(tmp_path))
        m.save_async(4, self._tree(4))
        m.wait()
        assert m.latest_step() == 4

    def test_atomicity_no_partial_dirs(self, tmp_path):
        m = CheckpointManager(str(tmp_path))
        m.save(1, self._tree(1))
        names = os.listdir(tmp_path)
        assert names == ["step_1"]
        assert not any(n.endswith(".tmp") for n in names)

    def test_restore_with_target_sharding(self, tmp_path):
        """Elastic restore path: device_put onto explicit shardings."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_mesh

        m = CheckpointManager(str(tmp_path))
        m.save(0, self._tree(7))
        mesh = make_mesh((1,), ("data",))
        template = jax.eval_shape(lambda: self._tree())
        sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), template)
        restored, _ = m.restore(template, shardings=sh)
        assert restored["params"]["w"].sharding == NamedSharding(mesh, P())

    def test_sharded_save_restore(self, tmp_path):
        """Two 'hosts' each save half the leaves; restore merges."""
        t = self._tree(2)
        for sid in (0, 1):
            m = CheckpointManager(str(tmp_path), shard_id=sid, n_shards=2)
            m.save(5, t)
        m = CheckpointManager(str(tmp_path), n_shards=2)
        restored, _ = m.restore(jax.eval_shape(lambda: self._tree()))
        np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                      np.asarray(t["params"]["w"]))

    def test_missing_key_raises(self, tmp_path):
        m = CheckpointManager(str(tmp_path))
        m.save(0, {"a": jnp.ones(3)})
        with pytest.raises(KeyError):
            m.restore(jax.eval_shape(lambda: {"a": jnp.ones(3),
                                              "b": jnp.ones(2)}))


# ---------------------------------------------------------------------------
# runtime: heartbeats, failures, supervisor, elastic
# ---------------------------------------------------------------------------

class TestRuntime:
    def test_straggler_detected(self):
        mon = HeartbeatMonitor(n_workers=4, window=16)
        for step in range(8):
            for w in range(4):
                mon.beat(w, step, 0.1)
        report = mon.beat(2, 8, 1.0)  # 10× median
        assert report is not None and report.worker == 2

    def test_uniform_noise_no_false_positives(self):
        rng = np.random.default_rng(0)
        mon = HeartbeatMonitor(n_workers=4)
        for step in range(30):
            for w in range(4):
                mon.beat(w, step, 0.1 + 0.005 * rng.random())
        assert mon.reports == []

    def test_dead_worker_detection(self):
        mon = HeartbeatMonitor(n_workers=2)
        for step in range(10):
            mon.beat(0, step, 0.1)
        mon.beat(1, 2, 0.1)
        assert mon.dead_workers(current_step=9) == [1]

    def test_failure_injector_fires_once(self):
        inj = FailureInjector([3])
        inj.maybe_fail(2)
        with pytest.raises(SimulatedFailure):
            inj.maybe_fail(3)
        inj.maybe_fail(3)  # replaced node survives the same step

    def test_supervisor_restarts_and_completes(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        inj = FailureInjector([4, 7])
        log = []

        def train_fn(start, restored):
            state = restored if restored is not None else 0
            for step in range(start, 10):
                state += 1
                inj.maybe_fail(step)
                mgr.save(step, {"acc": jnp.asarray(state)})
                log.append(step)
            return state

        def restore_fn(step):
            t, _ = mgr.restore({"acc": jnp.asarray(0)}, step=step)
            return int(t["acc"])

        sup = Supervisor(mgr, max_restarts=3)
        res = sup.run(train_fn, restore_fn=restore_fn)
        assert res.completed and res.restarts == 2
        assert res.final_state == 10

    @given(n=st.integers(1, 600))
    @settings(max_examples=50, deadline=None)
    def test_plan_mesh_uses_all_devices(self, n):
        d, m = plan_mesh_shape(n, model_parallel=16)
        assert d * m == n
        assert m <= 16


# ---------------------------------------------------------------------------
# persistent compile cache of the entry points
# ---------------------------------------------------------------------------

class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_repo_dir_when_unset(self, monkeypatch, restore_cache_dir):
        from repro.launch.compile_cache import (REPO_CACHE_DIR,
                                                enable_compile_cache)

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        assert REPO_CACHE_DIR.name == ".jax_cache"
        assert (REPO_CACHE_DIR.parent / "src" / "repro").is_dir()

    def test_environment_wins(self, monkeypatch, tmp_path,
                              restore_cache_dir):
        from repro.launch.compile_cache import enable_compile_cache

        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
