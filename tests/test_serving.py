"""Continuous-batching serve engine: correctness, scheduling, metrics.

Engine runs use CPU smoke configs and (where determinism matters) a frozen
clock — engine time then advances only through idle fast-forwarding, so
admission order is fully reproducible.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeSpec
from repro.configs.registry import get_config, smoke_config
from repro.launch.costing import request_decode_cost
from repro.launch.serve import serve_batch
from repro.models.api import build_model
from repro.serve import (GREEDY, Request, Sampler, ServeEngine,
                         SlotScheduler, poisson_workload)


@pytest.fixture(scope="module")
def rng():
    return jax.random.PRNGKey(0)


def _built(arch, rng):
    cfg = smoke_config(get_config(arch))
    model = build_model(cfg)
    return cfg, model, model.init(rng)


def _requests_from(tokens, gen_lens, arrivals=None):
    """Requests over the rows of a (B, P) token array."""
    arrivals = arrivals or [0.0] * len(gen_lens)
    return [Request(uid=i, prompt=tuple(int(t) for t in np.asarray(row)),
                    max_new_tokens=g, arrival_s=a)
            for i, (row, g, a) in enumerate(zip(tokens, gen_lens, arrivals))]


# ---------------------------------------------------------------------------
# engine vs static path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["llama3-8b", "moonshot-v1-16b-a3b",
                                  "mamba2-370m", "zamba2-1.2b"])
def test_engine_matches_static_greedy(rng, arch):
    """Greedy engine output is bit-identical to the lockstep serve_batch
    path for identical prompts across all decode families (dense/MoE:
    padded-bucket prefill; SSM/hybrid: exact-length prefill)."""
    cfg, model, params = _built(arch, rng)
    B, P, G = 3, 16, 6
    prompts = model.make_batch(rng, ShapeSpec("s", P, B, "prefill"))
    ref, _ = serve_batch(model, params, prompts, gen_len=G, max_len=P + G + 1)
    engine = ServeEngine(model, params, n_slots=B, max_len=P + G + 1,
                         clock=lambda: 0.0)
    results, report = engine.run(
        _requests_from(prompts["tokens"], [G] * B))
    got = np.stack([r.tokens for r in results])
    np.testing.assert_array_equal(np.asarray(ref), got)
    assert report["n_requests"] == B


def test_padded_bucket_prefill_matches_exact(rng):
    """A prompt length off the bucket boundary (13 → bucket 16) must not
    change the greedy continuation: padded K/V rows are masked by the
    per-slot position and then overwritten by decode."""
    cfg, model, params = _built("llama3-8b", rng)
    P, G = 13, 5
    toks = np.asarray(jax.random.randint(rng, (2, P), 0, cfg.vocab), np.int32)
    ref, _ = serve_batch(model, params, {"tokens": toks}, gen_len=G,
                         max_len=64)
    engine = ServeEngine(model, params, n_slots=2, max_len=64,
                         clock=lambda: 0.0)
    results, _ = engine.run(_requests_from(toks, [G, G]))
    np.testing.assert_array_equal(np.asarray(ref),
                                  np.stack([r.tokens for r in results]))


# ---------------------------------------------------------------------------
# continuous batching: slot reuse, staggered arrivals, metrics
# ---------------------------------------------------------------------------


def test_oversubscribed_slots_reused_midflight(rng):
    """5 requests with different gen lengths into 2 slots: freed slots admit
    the queue mid-flight (prefill interleaved with ongoing decode) and every
    request completes with its requested token count."""
    cfg, model, params = _built("llama3-8b", rng)
    gen_lens = [2, 9, 4, 7, 3]
    toks = np.asarray(jax.random.randint(rng, (5, 8), 0, cfg.vocab), np.int32)
    engine = ServeEngine(model, params, n_slots=2, max_len=32,
                         clock=lambda: 0.0)
    results, report = engine.run(_requests_from(toks, gen_lens))
    assert [r.tokens.size for r in results] == gen_lens
    assert report["slot_reuse"] >= 3          # 5 admissions, 2 slots
    assert 0.0 < report["slot_occupancy"] <= 1.0
    # mid-flight: the longest request (uid 1, 9 tokens) must still be in
    # its slot when a later request is admitted into the other slot
    slots_by_uid = {r.uid: r.slot for r in results}
    assert any(slots_by_uid[u] != slots_by_uid[1] for u in (2, 3, 4))


def test_staggered_arrivals_and_metrics(rng):
    """Frozen clock: later arrivals are admitted via idle fast-forward;
    lifecycle timestamps are ordered and all metrics finite/non-negative."""
    cfg, model, params = _built("llama3-8b", rng)
    toks = np.asarray(jax.random.randint(rng, (4, 8), 0, cfg.vocab), np.int32)
    reqs = _requests_from(toks, [3, 5, 2, 4], arrivals=[0.0, 0.0, 5.0, 5.5])
    engine = ServeEngine(model, params, n_slots=2, max_len=32,
                         clock=lambda: 0.0)
    results, report = engine.run(reqs)
    assert len(results) == 4
    for r in results:
        m = r.metrics
        assert m.arrival_s <= m.admitted_s <= m.first_token_s <= m.finished_s
        assert m.ttft_s >= 0 and m.per_token_ms >= 0
        assert np.isfinite([m.ttft_s, m.per_token_ms, m.tok_per_s,
                            m.moa_flops]).all()
        assert m.moa_flops >= 0
    # the t=5.0/5.5 arrivals cannot have been admitted before t=5.0
    assert results[2].metrics.admitted_s >= 5.0
    assert results[3].metrics.admitted_s >= 5.5
    agg = report["ttft_ms"]
    assert np.isfinite([agg["mean"], agg["p50"], agg["p95"]]).all()
    assert report["tok_per_s"] >= 0 and report["moa_flops_total"] > 0


def test_eos_early_exit(rng):
    """A request whose eos_id equals a token the greedy path would emit
    stops there (EOS finish reason) and frees the slot early."""
    from repro.serve.request import FinishReason

    cfg, model, params = _built("llama3-8b", rng)
    toks = np.asarray(jax.random.randint(rng, (1, 8), 0, cfg.vocab), np.int32)
    engine = ServeEngine(model, params, n_slots=1, max_len=32,
                         clock=lambda: 0.0)
    full, _ = engine.run(_requests_from(toks, [6]))
    gen = full[0].tokens
    # the first token past the first that greedy decode has not emitted
    # before: the request must stop exactly there, not at an earlier copy
    stop = next(i for i in range(1, gen.size) if gen[i] not in gen[:i])
    eos = int(gen[stop])
    engine2 = ServeEngine(model, params, n_slots=1, max_len=32,
                          clock=lambda: 0.0)
    results, _ = engine2.run([Request(
        uid=0, prompt=tuple(int(t) for t in toks[0]), max_new_tokens=6,
        eos_id=eos)])
    assert results[0].finish_reason is FinishReason.EOS
    assert results[0].tokens.size == stop + 1
    np.testing.assert_array_equal(results[0].tokens, gen[:stop + 1])


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


def test_serve_batch_sampler(rng):
    """The static path's single sampler argument: temperature sampling runs
    (and needs an rng); greedy is the default."""
    cfg, model, params = _built("llama3-8b", rng)
    prompts = model.make_batch(rng, ShapeSpec("s", 8, 2, "prefill"))
    tokens, _ = serve_batch(model, params, prompts, gen_len=4, max_len=16,
                            sampler=Sampler(0.8), rng=rng)
    assert tokens.shape == (2, 4)
    assert bool(jnp.all((tokens >= 0) & (tokens < cfg.vocab)))
    with pytest.raises(ValueError, match="rng"):
        serve_batch(model, params, prompts, gen_len=2, max_len=16,
                    sampler=Sampler(0.8))


def test_sampler_greedy_is_argmax():
    logits = jnp.asarray([[0.1, 2.0, -1.0], [3.0, 0.0, 0.5]])
    np.testing.assert_array_equal(np.asarray(GREEDY(logits)), [1, 0])
    assert GREEDY.greedy and not Sampler(0.7).greedy


# ---------------------------------------------------------------------------
# scheduler + workload units
# ---------------------------------------------------------------------------


def test_scheduler_invariants():
    sched = SlotScheduler(2, max_len=32, buckets=(8, 16))
    with pytest.raises(ValueError, match="max_len"):
        sched.submit(Request(uid=0, prompt=(1,) * 30, max_new_tokens=8))
    with pytest.raises(ValueError, match="bucket"):
        sched.submit(Request(uid=1, prompt=(1,) * 20, max_new_tokens=2))
    assert sched.bucket_for(5) == 8 and sched.bucket_for(9) == 16
    # FIFO over arrived requests, ties by uid
    for uid, arr in [(3, 0.2), (1, 0.0), (2, 0.0)]:
        sched.submit(Request(uid=uid, prompt=(1, 2), max_new_tokens=2,
                             arrival_s=arr))
    admitted = sched.admit_ready(0.1)
    assert [r.uid for _, r in admitted] == [1, 2]
    assert not sched.admit_ready(0.1)         # both slots busy, uid 3 future
    slot = admitted[0][0]
    sched.release(slot)
    with pytest.raises(KeyError):
        sched.release(slot)                    # invariant 1: already free
    assert [r.uid for _, r in sched.admit_ready(0.3)] == [3]
    assert sched.slot_reuse_count() == 1
    assert sched.slot_reuse_count(start=len(sched.admission_log)) == 0


def test_scheduler_accepts_tied_submissions():
    """Identical (arrival, uid) pairs must not fall through to comparing
    Request objects in the pending heap."""
    sched = SlotScheduler(1, max_len=16)
    for _ in range(2):
        sched.submit(Request(uid=0, prompt=(1, 2), max_new_tokens=2))
    assert len(sched.admit_ready(0.0)) == 1     # one slot: FIFO, no error
    assert sched.has_pending


def test_default_buckets_cover_max_len():
    """A prompt that fits the cache must also fit a bucket: the default
    bucket set ends with max_len, so invariant 3 alone decides
    admissibility (regression: 20 tokens at max_len=32 was rejected when
    the largest power-of-two bucket was 16)."""
    from repro.serve.scheduler import default_buckets

    assert default_buckets(32) == (8, 16, 32)
    assert default_buckets(70) == (8, 16, 32, 64, 70)
    assert default_buckets(6) == (6,)
    sched = SlotScheduler(1, max_len=32)
    sched.submit(Request(uid=0, prompt=(1,) * 20, max_new_tokens=8))
    assert sched.bucket_for(20) == 32


def test_engine_rerun_resets_counters(rng):
    """A reused engine (second run()) must not inherit the first run's
    fast-forward offset, decode-step count, or occupancy sum."""
    cfg, model, params = _built("llama3-8b", rng)
    toks = np.asarray(jax.random.randint(rng, (2, 8), 0, cfg.vocab), np.int32)
    engine = ServeEngine(model, params, n_slots=2, max_len=32,
                         clock=lambda: 0.0)
    # first run fast-forwards 3 s to its only arrival
    engine.run([Request(uid=0, prompt=tuple(int(t) for t in toks[0]),
                        max_new_tokens=4, arrival_s=3.0)])
    results, report = engine.run(
        [Request(uid=1, prompt=tuple(int(t) for t in toks[1]),
                 max_new_tokens=4)])
    assert results[0].metrics.ttft_s < 3.0      # no stale 3 s offset
    assert report["decode_steps"] == 3          # this run only (4 - 1 ticks)
    assert report["slot_occupancy"] <= 1.0
    assert report["slot_reuse"] == 0            # one admission this run


def test_on_logits_observes_each_decode_tick(rng):
    """``run(on_logits=...)`` sees every decode tick's last-position logits
    before sampling (warmup excluded): each busy slot's greedy token is
    their argmax. A later run without the callback does not call it."""
    cfg, model, params = _built("llama3-8b", rng)
    toks = np.asarray(jax.random.randint(rng, (2, 8), 0, cfg.vocab), np.int32)
    engine = ServeEngine(model, params, n_slots=2, max_len=32,
                         clock=lambda: 0.0)
    seen = []
    results, report = engine.run(
        _requests_from(toks, [4, 4]), warmup=True,
        on_logits=lambda lg: seen.append(np.asarray(lg)))
    assert len(seen) == report["decode_steps"] == 3
    assert all(lg.shape == (2, cfg.vocab) for lg in seen)
    for r in results:
        assert [int(np.argmax(lg[r.slot])) for lg in seen] \
            == r.tokens[1:].tolist()
    engine.run(_requests_from(toks, [4, 4]))
    assert len(seen) == 3


def test_padded_prefill_support_gates():
    """Padding is only claimed where it is exact: dense yes, SSM/hybrid/VLM
    no, MoE only in the dropless capacity regime."""
    assert build_model(smoke_config(get_config("llama3-8b"))) \
        .supports_padded_prefill
    for arch in ("mamba2-370m", "zamba2-1.2b", "llava-next-34b"):
        assert not build_model(smoke_config(get_config(arch))) \
            .supports_padded_prefill
    assert build_model(smoke_config(get_config("moonshot-v1-16b-a3b"))) \
        .supports_padded_prefill        # capacity_factor=8 >= 8/2
    assert not build_model(get_config("moonshot-v1-16b-a3b")) \
        .supports_padded_prefill        # base: 1.25 < 64/6


def test_poisson_workload_deterministic():
    a = poisson_workload(n_requests=6, vocab=97, rate_rps=10.0, seed=3)
    b = poisson_workload(n_requests=6, vocab=97, rate_rps=10.0, seed=3)
    assert a == b
    arr = [r.arrival_s for r in a]
    assert arr == sorted(arr) and arr[0] > 0
    assert all(0 <= t < 97 for r in a for t in r.prompt)
    assert {r.uid for r in a} == set(range(6))


def test_request_decode_cost_prices_strategy():
    """launch/costing routes serve metrics: the LOA strategy's ~6x per-add
    penalty must show up in the priced decode work."""
    cfg = smoke_config(get_config("llama3-8b"))
    exact = request_decode_cost(cfg, prompt_tokens=8, new_tokens=6)
    loa = request_decode_cost(
        dataclasses.replace(cfg, moa="loa?approx_bits=4&width=8"),
        prompt_tokens=8, new_tokens=6)
    assert exact > 0
    assert loa > exact
    assert request_decode_cost(cfg, prompt_tokens=8, new_tokens=1) == 0.0


# ---------------------------------------------------------------------------
# compilation cache + warmup (engine-level, docs/serving.md)
# ---------------------------------------------------------------------------


def test_compile_cache_shared_across_engines(rng):
    """Two engines on the same (model, layout) share every jitted callable
    — the second engine triggers no recompilation (regression: the
    per-instance ``jax.jit`` in ``__init__`` made benchmarks that build
    dense + paged + spec engines pay triple compile)."""
    from repro.serve.engine import _cache_size, _clear_compile_cache

    cfg, model, params = _built("llama3-8b", rng)
    toks = np.asarray(jax.random.randint(rng, (2, 6), 0, cfg.vocab),
                      np.int32)
    _clear_compile_cache()     # self-contained regardless of test order
    e1 = ServeEngine(model, params, n_slots=2, max_len=32,
                     clock=lambda: 0.0)
    r1, _ = e1.run(_requests_from(toks, [4, 4]))
    size_after_first = _cache_size()
    assert size_after_first > 0
    e2 = ServeEngine(model, params, n_slots=2, max_len=32,
                     clock=lambda: 0.0)
    r2, _ = e2.run(_requests_from(toks, [4, 4]))
    assert _cache_size() == size_after_first, \
        "second engine on the same layout must reuse the jit cache"
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.tokens, b.tokens)
    # a different cache layout is a different key set (no false sharing)
    e3 = ServeEngine(model, params, n_slots=2, max_len=32, paged=True,
                     block_size=8, clock=lambda: 0.0)
    assert _cache_size() > size_after_first
    r3, _ = e3.run(_requests_from(toks, [4, 4]))
    for a, b in zip(r1, r3):
        np.testing.assert_array_equal(a.tokens, b.tokens)


@pytest.mark.parametrize("arch,paged,spec",
                         [("llama3-8b", False, False),
                          ("llama3-8b", True, False),
                          ("llama3-8b", False, True),
                          ("zamba2-1.2b", False, True)])
def test_warmup_tick_is_invisible_to_results(rng, arch, paged, spec):
    """``run(warmup=True)`` must produce bit-identical results to a cold
    run: the throwaway tick's writes land on trash pages / overwritten
    slot rows, and a spec warmup's keep=0 commit restores recurrent state
    from the pre-verify snapshot."""
    from repro.serve import OracleDrafter

    cfg, model, params = _built(arch, rng)
    toks = np.asarray(jax.random.randint(rng, (2, 6), 0, cfg.vocab),
                      np.int32)
    runs = []
    for warmup in (False, True):
        kw = dict(n_slots=2, max_len=32, clock=lambda: 0.0)
        if paged:
            kw.update(paged=True, block_size=8)
        drafter = OracleDrafter(2) if spec else None
        engine = ServeEngine(model, params, drafter=drafter, **kw)
        results, report = engine.run(_requests_from(toks, [5, 5]),
                                     warmup=warmup)
        assert report["compile_s"] >= 0.0
        runs.append(results)
    for a, b in zip(*runs):
        np.testing.assert_array_equal(a.tokens, b.tokens)


def test_warmup_reports_compile_time(rng):
    """With a cold jit cache the warmup tick's compile time lands in
    ``compile_s``, not ``wall_s`` (the serving-v1/v2/v3 skew bugfix)."""
    from repro.serve.engine import _clear_compile_cache

    cfg, model, params = _built("llama3-8b", rng)
    _clear_compile_cache()                 # force fresh jit objects
    toks = np.asarray(jax.random.randint(rng, (2, 6), 0, cfg.vocab),
                      np.int32)
    engine = ServeEngine(model, params, n_slots=2, max_len=32)
    _, report = engine.run(_requests_from(toks, [4, 4]), warmup=True)
    assert report["compile_s"] > 0.0
    # the decode tick itself is milliseconds; compilation is not
    assert report["compile_s"] > report["wall_s"] / 10


# ---------------------------------------------------------------------------
# tracing: executable names, layer scopes, host spans (repro.tracing)
# ---------------------------------------------------------------------------


def _host_spans(trace_dir):
    """``(name, start_ns, end_ns, args)`` of every ``serve.*`` host event
    in the profile written under ``trace_dir``, by start time."""
    import glob
    import os

    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            spans.extend((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                         for ev in line.events
                         if ev.name.startswith("serve."))
    return sorted(spans, key=lambda s: s[1])


def test_tick_phases_are_host_spans(rng, tmp_path):
    """Under ``jax.profiler`` a decoding tick records ``serve.tick`` with
    ``serve.decode``, ``serve.pull`` and ``serve.commit`` nested in that
    order, and an admission records ``serve.admit`` (carrying the uid and
    prompt length) with its first-token ``serve.pull`` inside."""
    from repro import tracing

    cfg, model, params = _built("mamba2-370m", rng)
    toks = np.asarray(jax.random.randint(rng, (2, 6), 0, cfg.vocab),
                      np.int32)
    engine = ServeEngine(model, params, n_slots=2, max_len=32,
                         clock=lambda: 0.0)
    engine.run(_requests_from(toks, [3, 3]), warmup=True)    # compile
    with jax.profiler.trace(str(tmp_path)):
        engine.run(_requests_from(toks, [3, 3]))
    spans = _host_spans(str(tmp_path))

    def inside(outer):
        return [s for s in spans if s is not outer
                and outer[1] <= s[1] and s[2] <= outer[2]]

    ticks = [s for s in spans if s[0] == tracing.TICK]
    assert ticks
    decoding = [t for t in ticks
                if any(s[0] == tracing.DECODE for s in inside(t))]
    assert decoding
    for t in decoding:
        phases = [s[0] for s in inside(t) if s[0] in (
            tracing.DECODE, tracing.PULL, tracing.COMMIT)]
        assert phases[-3:] == [tracing.DECODE, tracing.PULL, tracing.COMMIT]
    admits = [s for s in spans if s[0] == tracing.ADMIT]
    assert sorted(int(a[3]["uid"]) for a in admits) == [0, 1]
    assert {int(a[3]["prompt_len"]) for a in admits} == {6}
    for a in admits:
        assert [s[0] for s in inside(a)].count(tracing.PULL) == 1
    # every span sits inside a tick
    assert all(any(t[1] <= s[1] and s[2] <= t[2] for t in ticks)
               for s in spans)


@pytest.mark.parametrize("paged", [False, True])
def test_engine_callables_are_named_executables(rng, paged):
    """Every callable the engine jits lowers to the module
    ``jit_serve_<name>``: every live-block bucket of decode shares
    ``jit_serve_decode``."""
    cfg, model, params = _built("zamba2-1.2b" if paged else "mamba2-370m",
                                rng)
    kw = dict(paged=True, block_size=8) if paged else {}
    engine = ServeEngine(model, params, n_slots=2, max_len=32, **kw)
    toks = jnp.zeros((2, 1), jnp.int32)
    decodes = [engine._decode_for(hw) for hw in engine._hw_buckets()] \
        if paged else [engine._decode]
    for decode in decodes:
        text = decode.lower(engine.params, engine.cache, toks).as_text()
        assert "module @jit_serve_decode" in text
    logits = jnp.zeros((2, cfg.vocab), jnp.float32)
    text = engine._sample.lower(logits, jnp.zeros((2,)), jnp.ones((2,), bool),
                                jax.random.PRNGKey(0)).as_text()
    assert "module @jit_serve_sample" in text


@pytest.mark.parametrize("arch,scopes", [
    ("zamba2-1.2b", {"embed", "ssd", "attention", "mlp", "logits"}),
    ("mamba2-370m", {"embed", "ssd", "logits"})])
def test_decode_ops_carry_layer_scopes(rng, arch, scopes):
    """The compiled decode step's op metadata names the layer of each op:
    the family's layers all appear, and the layer loop's plumbing (the
    in-place write of each layer's recurrent state into the carried
    stack) sits in no layer."""
    import re

    from repro import tracing

    cfg, model, params = _built(arch, rng)
    paged = arch == "zamba2-1.2b"
    kw = dict(paged=True, block_size=8) if paged else {}
    engine = ServeEngine(model, params, n_slots=2, max_len=32, **kw)
    decode = engine._decode_for(1) if paged else engine._decode
    hlo = decode.lower(engine.params, engine.cache,
                       jnp.zeros((2, 1), jnp.int32)).compile().as_text()
    names = re.findall(r'op_name="(jit\(serve_decode\)/[^"]*)"', hlo)

    def layer(name):
        return next((p for p in name.split("/")
                     if p in tracing.LAYER_SCOPES), "")

    assert {layer(n) for n in names} - {""} == scopes
    plumbing = [n for n in names if "/while/body/" in n
                and n.endswith("/dynamic_update_slice")]
    assert plumbing and not any(layer(n) for n in plumbing)
