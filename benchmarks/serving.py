"""Serving benchmark: continuous-batching engine under a Poisson workload,
JSON results (the BENCH trajectory's machine-readable record).

Record schemas (all validated by ``scripts/check_bench_schema.py``):

* ``serving-v1`` (default): one engine run — run configuration,
  per-request records (TTFT ms, per-token latency ms, tok/s,
  strategy-priced MOA FLOPs) and the aggregate report.
* ``serving-v2`` (``--paged``): the same workload through **both** cache
  layouts — dense per-slot regions and the paged block pool — plus a
  comparison block (paged-vs-dense TTFT, prefix hits, resident KV bytes
  vs the dense reservation). ``--shared-prefix`` swaps in the
  system-prompt-style workload that actually exercises the prefix cache.
* ``serving-v3`` (``--spec-decode``): the same workload through plain
  decode and speculative decode at a **sweep of forced accept rates**
  (oracle drafter with independent per-token corruption) — the paper's
  "does the multiplexing gamble pay" question measured end-to-end, with
  the acceptance-aware cost-model prediction alongside each measured
  point (docs/spec-decode.md).

* ``serving-v4`` (``--mesh DxM``): the same workload through a
  single-device engine and a **mesh-sharded** engine (params
  tensor-parallel, KV cache sharded over slots and heads, per
  ``docs/sharded-serving.md``) — per-axis mesh shape, tok/s and TTFT side
  by side, plus a greedy token-parity bit (the sharded mapping validated
  on the actual device topology, the paper's core lesson). On CPU the
  mesh runs on XLA host-platform devices.

* ``serving-v5`` (``--slo``): the same **bursty, deadline-carrying**
  workload through a FIFO engine and an SLO engine (deadline-aware
  admission + preemptive spill/revive + chunked prefill,
  ``docs/slo-scheduling.md``), both on a deterministic
  :class:`~repro.serve.clock.StepClock` — p99 TTFT of the deadline
  cohort, attainment and goodput-under-SLO side by side, plus a greedy
  token-parity bit (preemption must not change any request's tokens).

* ``serving-v6`` (``--backends``): the same workload through two paged
  engines that differ only in the attention backend — ``jnp`` (gathered
  dense KV view, reference) vs ``pallas`` (fused block-table flash
  decode/verify, ``docs/kernels.md``) — tok/s and TTFT side by side, the
  per-step gathered-vs-fused attention HBM bytes (the traffic the fused
  kernel removes), and a ``greedy_tokens_match`` bit.

* ``serving-v7`` (``--replicas N``): the same greedy workload through a
  failure-free replica fleet and a **chaos** fleet — injected replica
  crashes (heartbeat-detected, requests requeued and re-prefilled
  elsewhere) plus a mid-run checkpoint save that triggers a rolling
  watcher-driven weight reload (``docs/fault-tolerance.md``) — goodput
  and requeue-latency cost of the failures, a ``greedy_tokens_match``
  bit against the failure-free baseline, and the zero-loss /
  zero-reload-drop counters CI gates on.

  PYTHONPATH=src python -m benchmarks.serving --smoke --json out.json
  PYTHONPATH=src python -m benchmarks.serving --smoke --paged \
      --shared-prefix --block-size 8 --json paged.json
  PYTHONPATH=src python -m benchmarks.serving --smoke --spec-decode \
      --spec-k 3 --json spec.json
  PYTHONPATH=src python -m benchmarks.serving --smoke --mesh 2x4 \
      --json sharded.json
  PYTHONPATH=src python -m benchmarks.serving --smoke --slo \
      --json slo.json
  PYTHONPATH=src python -m benchmarks.serving --smoke --backends \
      --block-size 8 --json backends.json
"""

from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from repro.configs.registry import get_config, smoke_config
from repro.launch.costing import spec_decode_cost
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import ensure_host_devices, make_mesh, parse_mesh
from repro.models.api import build_model
from repro.serve import (GREEDY, OracleDrafter, Sampler, ServeEngine,
                         StepClock, bursty_workload, poisson_workload,
                         shared_prefix_workload)


def _build(arch: str, smoke: bool):
    cfg = get_config(arch)
    if smoke:
        cfg = smoke_config(cfg)
    if cfg.family == "encoder":
        raise ValueError("encoder-only arch has no decode step")
    return cfg, build_model(cfg)


def _workload_factory(cfg, *, requests, rate_rps, shared_prefix, prefix_len,
                      n_prefixes, prompt_len_range, gen_len_range,
                      temperature, seed):
    sampler = Sampler(temperature) if temperature > 0 else GREEDY
    if shared_prefix:
        return lambda: shared_prefix_workload(
            n_requests=requests, vocab=cfg.vocab, rate_rps=rate_rps,
            n_prefixes=n_prefixes, prefix_len=prefix_len,
            suffix_len_range=(0, max(prompt_len_range[1] - prefix_len, 0)),
            gen_len_range=gen_len_range, sampler=sampler, seed=seed)
    return lambda: poisson_workload(
        n_requests=requests, vocab=cfg.vocab, rate_rps=rate_rps,
        prompt_len_range=prompt_len_range, gen_len_range=gen_len_range,
        sampler=sampler, seed=seed)


def run(*, arch: str = "llama3-8b", smoke: bool = True, requests: int = 8,
        rate_rps: float = 50.0, slots: int = 4, max_len: int = 96,
        prompt_len_range=(4, 24), gen_len_range=(2, 12),
        temperature: float = 0.0, seed: int = 0,
        warmup: bool = True, shared_prefix: bool = False,
        prefix_len: int = 16, n_prefixes: int = 2) -> dict:
    """One dense engine run; returns the ``serving-v1`` record.

    ``warmup`` replays the same workload once unmeasured first, so XLA
    compilation of each prefill bucket and the decode step lands outside
    the measured TTFT / per-token distributions; the measured run also
    executes the engine's warmup tick, so any residual compile time is
    reported as ``aggregate.compile_s`` instead of folding into
    ``wall_s``.
    """
    cfg, model = _build(arch, smoke)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    engine = ServeEngine(model, params, n_slots=slots, max_len=max_len,
                         rng=rng)
    make_workload = _workload_factory(
        cfg, requests=requests, rate_rps=rate_rps,
        shared_prefix=shared_prefix, prefix_len=prefix_len,
        n_prefixes=n_prefixes, prompt_len_range=prompt_len_range,
        gen_len_range=gen_len_range, temperature=temperature, seed=seed)
    if warmup:
        engine.run(make_workload())
    results, report = engine.run(make_workload(), warmup=warmup)
    return {
        "schema": "serving-v1",
        "config": {
            "arch": cfg.name, "family": cfg.family, "smoke": smoke,
            "moa": cfg.moa_strategy.spec, "n_slots": slots,
            "max_len": max_len, "requests": requests, "rate_rps": rate_rps,
            "prompt_len_range": list(prompt_len_range),
            "gen_len_range": list(gen_len_range),
            "temperature": temperature, "seed": seed, "warmup": warmup,
            "shared_prefix": shared_prefix,
        },
        "requests": [r.to_json() for r in results],
        "aggregate": report,
    }


def run_paged(*, arch: str = "llama3-8b", smoke: bool = True,
              requests: int = 8, rate_rps: float = 50.0, slots: int = 4,
              max_len: int = 96, block_size: int = 16, n_blocks: int = 0,
              prompt_len_range=(4, 24), gen_len_range=(2, 12),
              temperature: float = 0.0, seed: int = 0, warmup: bool = True,
              shared_prefix: bool = True, prefix_len: int = 16,
              n_prefixes: int = 2,
              attn_backend: str = None) -> dict:
    """Dense-vs-paged comparison on one workload; ``serving-v2`` record.

    Both engines serve the identical request stream (same seed) so the
    TTFT columns differ only through the cache layout: the paged engine's
    prefix-cache hits skip shared prefill compute (dense family), and its
    ``resident_kv_bytes`` prices pages in use instead of the
    ``n_slots x max_len`` reservation.
    """
    cfg, model = _build(arch, smoke)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    make_workload = _workload_factory(
        cfg, requests=requests, rate_rps=rate_rps,
        shared_prefix=shared_prefix, prefix_len=prefix_len,
        n_prefixes=n_prefixes, prompt_len_range=prompt_len_range,
        gen_len_range=gen_len_range, temperature=temperature, seed=seed)
    runs = {}
    for mode in ("dense", "paged"):
        engine = ServeEngine(
            model, params, n_slots=slots, max_len=max_len,
            paged=(mode == "paged"), block_size=block_size,
            n_blocks=n_blocks or None, rng=rng,
            attn_backend=attn_backend if mode == "paged" else None)
        if warmup:
            # paged: twice — the first replay warms the prefix trie, the
            # second compiles the suffix-prefill shapes that only occur
            # once admissions start hitting the warm trie
            for _ in range(2 if mode == "paged" else 1):
                engine.run(make_workload())
        results, report = engine.run(make_workload(), warmup=warmup)
        runs[mode] = {"requests": [r.to_json() for r in results],
                      "aggregate": report}
    paged_agg = runs["paged"]["aggregate"]
    comparison = {
        "ttft_p50_ms_dense": runs["dense"]["aggregate"]["ttft_ms"]["p50"],
        "ttft_p50_ms_paged": paged_agg["ttft_ms"]["p50"],
        "prefix_hits": paged_agg["paged"]["prefix_hits"],
        "prefix_hit_rate": paged_agg["paged"]["prefix_hit_rate"],
        "cached_prompt_tokens": sum(
            r["cached_prompt_tokens"] for r in runs["paged"]["requests"]),
        "resident_kv_bytes": paged_agg["paged"]["resident_kv_bytes"],
        "dense_equiv_kv_bytes": paged_agg["paged"]["dense_equiv_kv_bytes"],
    }
    return {
        "schema": "serving-v2",
        "config": {
            "arch": cfg.name, "family": cfg.family, "smoke": smoke,
            "moa": cfg.moa_strategy.spec, "n_slots": slots,
            "max_len": max_len, "block_size": block_size,
            "n_blocks": paged_agg["paged"]["n_blocks"],
            "requests": requests, "rate_rps": rate_rps,
            "prompt_len_range": list(prompt_len_range),
            "gen_len_range": list(gen_len_range),
            "temperature": temperature, "seed": seed, "warmup": warmup,
            "shared_prefix": shared_prefix, "prefix_len": prefix_len,
            "n_prefixes": n_prefixes,
        },
        "dense": runs["dense"],
        "paged": runs["paged"],
        "comparison": comparison,
    }


def _slot_norm_tokens_per_step(agg: dict) -> float:
    """Tick-emitted tokens per active-slot step (plain decode ≡ 1.0).

    Matches the spec report's normalization: each request's first token
    comes from its prefill, not a decode tick, so it is excluded.
    """
    slot_steps = agg["slot_occupancy"] * agg["decode_steps"] * agg["n_slots"]
    return (agg["total_new_tokens"] - agg["n_requests"]) \
        / max(slot_steps, 1e-9)


def run_spec(*, arch: str = "llama3-8b", smoke: bool = True,
             requests: int = 8, rate_rps: float = 50.0, slots: int = 4,
             max_len: int = 96, spec_k: int = 3,
             accept_probs=(1.0, 0.75, 0.5, 0.0),
             prompt_len_range=(4, 24), gen_len_range=(2, 12),
             temperature: float = 0.0, seed: int = 0,
             warmup: bool = True) -> dict:
    """Plain-vs-speculative comparison at a sweep of forced accept rates;
    ``serving-v3`` record.

    Every run serves the identical request stream. The oracle drafter
    proposes the target's own greedy continuation with each token
    independently corrupted at rate ``1 - accept_prob``, so the *measured*
    accept rate tracks the knob and ``tokens_per_step`` (slot-step
    normalized: plain decode ≡ 1.0) traces the payoff curve that the
    acceptance-aware estimator (:func:`repro.launch.costing
    .spec_decode_cost`) predicts — measured and predicted land side by
    side in ``comparison``, the paper's promising-on-paper vs
    synthesized-reality split.
    """
    cfg, model = _build(arch, smoke)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    make_workload = _workload_factory(
        cfg, requests=requests, rate_rps=rate_rps, shared_prefix=False,
        prefix_len=0, n_prefixes=1, prompt_len_range=prompt_len_range,
        gen_len_range=gen_len_range, temperature=temperature, seed=seed)

    engine = ServeEngine(model, params, n_slots=slots, max_len=max_len,
                         rng=rng)
    if warmup:
        engine.run(make_workload())
    plain_results, plain_report = engine.run(make_workload(),
                                             warmup=warmup)
    plain = {"requests": [r.to_json() for r in plain_results],
             "aggregate": plain_report}
    plain_tps = _slot_norm_tokens_per_step(plain_report)

    s_attn = float(sum(prompt_len_range) / 2 + sum(gen_len_range) / 2)
    spec_runs, curve = [], []
    for accept in accept_probs:
        engine = ServeEngine(
            model, params, n_slots=slots, max_len=max_len, rng=rng,
            drafter=OracleDrafter(spec_k, accept_prob=accept, seed=seed))
        if warmup:
            engine.run(make_workload())
        results, report = engine.run(make_workload(), warmup=warmup)
        spec_runs.append({"accept_prob": accept,
                          "requests": [r.to_json() for r in results],
                          "aggregate": report})
        predicted = spec_decode_cost(cfg, k=spec_k, accept_prob=accept,
                                     s_attn=s_attn, draft_cfg=cfg)
        sp = report["spec"]
        curve.append({
            "accept_prob": accept,
            "measured_accept_rate": sp["accept_rate"],
            "tokens_per_step": sp["tokens_per_step"],
            "speedup_vs_plain": sp["tokens_per_step"] / max(plain_tps, 1e-9),
            "predicted_tokens_per_step":
                predicted["expected_tokens_per_step"],
            "predicted_flops_overhead": predicted["flops_overhead"],
            "ttft_p50_ms": report["ttft_ms"]["p50"],
        })
    best = max(curve, key=lambda c: c["tokens_per_step"])
    return {
        "schema": "serving-v3",
        "config": {
            "arch": cfg.name, "family": cfg.family, "smoke": smoke,
            "moa": cfg.moa_strategy.spec, "n_slots": slots,
            "max_len": max_len, "requests": requests, "rate_rps": rate_rps,
            "prompt_len_range": list(prompt_len_range),
            "gen_len_range": list(gen_len_range),
            "temperature": temperature, "seed": seed, "warmup": warmup,
            "spec_k": spec_k, "accept_probs": list(accept_probs),
            "drafter": "oracle",
        },
        "plain": plain,
        "spec_runs": spec_runs,
        "comparison": {
            "tokens_per_step_plain": plain_tps,
            "ttft_p50_ms_plain": plain_report["ttft_ms"]["p50"],
            "curve": curve,
            "best_tokens_per_step": best["tokens_per_step"],
            "best_accept_prob": best["accept_prob"],
        },
    }


def run_sharded(*, arch: str = "llama3-8b", smoke: bool = True,
                requests: int = 8, rate_rps: float = 50.0, slots: int = 4,
                max_len: int = 96, mesh_shape=(2, 4),
                prompt_len_range=(4, 24), gen_len_range=(2, 12),
                temperature: float = 0.0, seed: int = 0,
                warmup: bool = True) -> dict:
    """Single-device vs mesh-sharded engine on one workload; ``serving-v4``.

    The sharded engine places the parameters tensor-parallel and the KV
    cache slot/head-sharded (``docs/sharded-serving.md``); both engines
    serve the identical request stream, so the comparison isolates the
    device mapping: tok/s and TTFT per topology, plus
    ``greedy_tokens_match`` — the bit-identical-output check that the
    paper's "validate the mapping on the device" lesson demands. The mesh
    must already be satisfiable by the visible devices (the CLI requests
    XLA host-platform devices before jax initializes).
    """
    cfg, model = _build(arch, smoke)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    make_workload = _workload_factory(
        cfg, requests=requests, rate_rps=rate_rps, shared_prefix=False,
        prefix_len=0, n_prefixes=1, prompt_len_range=prompt_len_range,
        gen_len_range=gen_len_range, temperature=temperature, seed=seed)
    mesh = make_mesh(tuple(mesh_shape))
    runs = {}
    for mode, m in (("single", None), ("sharded", mesh)):
        engine = ServeEngine(model, params, n_slots=slots, max_len=max_len,
                             rng=rng, mesh=m)
        if warmup:
            engine.run(make_workload())
        results, report = engine.run(make_workload(), warmup=warmup)
        runs[mode] = {"results": results,
                      "requests": [r.to_json() for r in results],
                      "aggregate": report}
    single_agg = runs["single"]["aggregate"]
    shard_agg = runs["sharded"]["aggregate"]
    tokens_match = all(
        np.array_equal(a.tokens, b.tokens)
        for a, b in zip(runs["single"]["results"],
                        runs["sharded"]["results"]))
    for mode in runs:
        del runs[mode]["results"]
    return {
        "schema": "serving-v4",
        "config": {
            "arch": cfg.name, "family": cfg.family, "smoke": smoke,
            "moa": cfg.moa_strategy.spec, "n_slots": slots,
            "max_len": max_len, "requests": requests, "rate_rps": rate_rps,
            "prompt_len_range": list(prompt_len_range),
            "gen_len_range": list(gen_len_range),
            "temperature": temperature, "seed": seed, "warmup": warmup,
            "mesh": {
                "shape": [int(s) for s in mesh.devices.shape],
                "axes": list(mesh.axis_names),
                "n_devices": int(mesh.devices.size),
            },
        },
        "single": runs["single"],
        "sharded": runs["sharded"],
        "comparison": {
            "greedy_tokens_match": bool(tokens_match),
            "tok_per_s_single": single_agg["tok_per_s"],
            "tok_per_s_sharded": shard_agg["tok_per_s"],
            "sharded_speedup": shard_agg["tok_per_s"]
                / max(single_agg["tok_per_s"], 1e-9),
            "ttft_p50_ms_single": single_agg["ttft_ms"]["p50"],
            "ttft_p50_ms_sharded": shard_agg["ttft_ms"]["p50"],
            "compile_s_single": single_agg["compile_s"],
            "compile_s_sharded": shard_agg["compile_s"],
        },
    }


def run_backends(*, arch: str = "llama3-8b", smoke: bool = True,
                 requests: int = 8, rate_rps: float = 50.0, slots: int = 4,
                 max_len: int = 96, block_size: int = 16, n_blocks: int = 0,
                 prompt_len_range=(4, 24), gen_len_range=(2, 12),
                 temperature: float = 0.0, seed: int = 0,
                 warmup: bool = True, shared_prefix: bool = False,
                 prefix_len: int = 16, n_prefixes: int = 2) -> dict:
    """Gather-vs-fused paged attention on one workload; ``serving-v6``.

    Both engines serve the identical request stream through the paged
    pool; they differ only in ``attn_backend`` — ``jnp`` streams the
    gathered (padded, high-water-bucketed) KV view, ``pallas`` walks the
    block table inside the fused flash kernel and touches only live
    pages. ``comparison.kv_bytes_per_step`` records both byte counts at
    every decode step (same cursors, so the fused column is <= the
    gathered one by construction — the bandwidth headroom the kernel
    converts into tok/s), and ``greedy_tokens_match`` asserts the two
    backends emit bit-identical greedy tokens. On CPU the pallas engine
    runs the kernels in interpret mode, so the token-parity bit is
    meaningful everywhere while the tok/s columns only are on TPU.
    """
    cfg, model = _build(arch, smoke)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    make_workload = _workload_factory(
        cfg, requests=requests, rate_rps=rate_rps,
        shared_prefix=shared_prefix, prefix_len=prefix_len,
        n_prefixes=n_prefixes, prompt_len_range=prompt_len_range,
        gen_len_range=gen_len_range, temperature=temperature, seed=seed)
    runs = {}
    logs = {}
    for backend in ("jnp", "pallas"):
        engine = ServeEngine(
            model, params, n_slots=slots, max_len=max_len, paged=True,
            block_size=block_size, n_blocks=n_blocks or None, rng=rng,
            attn_backend=backend)
        if warmup:
            for _ in range(2):
                engine.run(make_workload())
        results, report = engine.run(make_workload(), warmup=warmup)
        runs[backend] = {"results": results,
                         "requests": [r.to_json() for r in results],
                         "aggregate": report}
        logs[backend] = [[int(g), int(f)] for g, f in engine._kv_step_log]
    jnp_agg = runs["jnp"]["aggregate"]
    pallas_agg = runs["pallas"]["aggregate"]
    tokens_match = all(
        np.array_equal(a.tokens, b.tokens)
        for a, b in zip(runs["jnp"]["results"], runs["pallas"]["results"]))
    for backend in runs:
        del runs[backend]["results"]
    # same workload + parity => identical cursor streams; keep one log
    step_log = logs["jnp"]
    comparison = {
        "greedy_tokens_match": bool(tokens_match),
        "tok_per_s_jnp": jnp_agg["tok_per_s"],
        "tok_per_s_pallas": pallas_agg["tok_per_s"],
        "pallas_speedup": pallas_agg["tok_per_s"]
            / max(jnp_agg["tok_per_s"], 1e-9),
        "ttft_p50_ms_jnp": jnp_agg["ttft_ms"]["p50"],
        "ttft_p50_ms_pallas": pallas_agg["ttft_ms"]["p50"],
        "compile_s_jnp": jnp_agg["compile_s"],
        "compile_s_pallas": pallas_agg["compile_s"],
        "gathered_kv_bytes": jnp_agg["paged"]["gathered_kv_bytes"],
        "fused_kv_bytes": jnp_agg["paged"]["fused_kv_bytes"],
        "kv_bytes_per_step": step_log,
        "fused_le_gathered_every_step": bool(
            all(f <= g for g, f in step_log)),
        "kv_bytes_saved_frac": 1.0
            - jnp_agg["paged"]["fused_kv_bytes"]
            / max(jnp_agg["paged"]["gathered_kv_bytes"], 1),
    }
    return {
        "schema": "serving-v6",
        "config": {
            "arch": cfg.name, "family": cfg.family, "smoke": smoke,
            "moa": cfg.moa_strategy.spec, "n_slots": slots,
            "max_len": max_len, "block_size": block_size,
            "n_blocks": jnp_agg["paged"]["n_blocks"],
            "requests": requests, "rate_rps": rate_rps,
            "prompt_len_range": list(prompt_len_range),
            "gen_len_range": list(gen_len_range),
            "temperature": temperature, "seed": seed, "warmup": warmup,
            "shared_prefix": shared_prefix,
            "backends": ["jnp", "pallas"],
            "default_backend": jax.default_backend(),
        },
        "jnp": runs["jnp"],
        "pallas": runs["pallas"],
        "comparison": comparison,
    }


def run_slo(*, arch: str = "llama3-8b", smoke: bool = True,
            slots: int = 2, max_len: int = 96, n_long: int = 0,
            n_burst: int = 8, long_prompt_len: int = 24,
            long_gen_len: int = 40, burst_prompt_len: int = 8,
            burst_gen_len: int = 4, burst_at_s: float = 0.004,
            burst_deadline_s: float = 0.035, prefill_chunk: int = 16,
            clock_dt: float = 1e-3, seed: int = 0) -> dict:
    """FIFO-vs-SLO comparison on one bursty workload; ``serving-v5``.

    Long generations grab every slot, then a burst of short requests with
    tight TTFT deadlines lands behind them. Both engines run on a
    deterministic :class:`StepClock` (virtual time advances per engine
    clock read, so XLA compile time cannot skew any latency — no warmup
    replay needed and the record is exactly reproducible). FIFO queues
    the burst until a long decode finishes and blows the deadline cohort's
    p99 TTFT; the SLO engine preempts the longs (their first token is
    already banked), serves the burst, and revives them — same tokens for
    every request, very different tail latency. The SLO engine also
    prefills in ``prefill_chunk``-token chunks so a long admission never
    blocks a tick for more than one chunk.
    """
    cfg, model = _build(arch, smoke)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    n_long = n_long or slots
    make_workload = lambda: bursty_workload(  # noqa: E731
        vocab=cfg.vocab, n_long=n_long, n_burst=n_burst,
        long_prompt_len=long_prompt_len, long_gen_len=long_gen_len,
        burst_prompt_len=burst_prompt_len, burst_gen_len=burst_gen_len,
        burst_at_s=burst_at_s, burst_deadline_s=burst_deadline_s,
        seed=seed)
    runs = {}
    for policy in ("fifo", "slo"):
        engine = ServeEngine(
            model, params, n_slots=slots, max_len=max_len, rng=rng,
            clock=StepClock(dt=clock_dt), scheduling=policy,
            prefill_chunk_tokens=(prefill_chunk or None)
            if policy == "slo" else None)
        results, report = engine.run(make_workload())
        runs[policy] = {"results": results,
                        "requests": [r.to_json() for r in results],
                        "aggregate": report}
    tokens_match = all(
        np.array_equal(a.tokens, b.tokens)
        for a, b in zip(runs["fifo"]["results"], runs["slo"]["results"]))
    for policy in runs:
        del runs[policy]["results"]
    f, s = (runs[p]["aggregate"]["slo"] for p in ("fifo", "slo"))
    comparison = {
        "greedy_tokens_match": bool(tokens_match),
        "attainment_fifo": f["attainment"],
        "attainment_slo": s["attainment"],
        "deadline_ttft_p99_ms_fifo": f["deadline_ttft_ms"]["p99"],
        "deadline_ttft_p99_ms_slo": s["deadline_ttft_ms"]["p99"],
        "goodput_tok_per_s_fifo": f["goodput_tok_per_s"],
        "goodput_tok_per_s_slo": s["goodput_tok_per_s"],
        "preemptions": s["preemptions"],
        "spills": s["spills"],
        "revivals": s["revivals"],
        "prefill_chunk_count": s["prefill_chunk_count"],
        "slo_wins_p99": bool(s["deadline_ttft_ms"]["p99"]
                             < f["deadline_ttft_ms"]["p99"]),
        "slo_wins_goodput": bool(s["goodput_tok_per_s"]
                                 > f["goodput_tok_per_s"]),
    }
    return {
        "schema": "serving-v5",
        "config": {
            "arch": cfg.name, "family": cfg.family, "smoke": smoke,
            "moa": cfg.moa_strategy.spec, "n_slots": slots,
            "max_len": max_len, "n_long": n_long, "n_burst": n_burst,
            "long_prompt_len": long_prompt_len,
            "long_gen_len": long_gen_len,
            "burst_prompt_len": burst_prompt_len,
            "burst_gen_len": burst_gen_len, "burst_at_s": burst_at_s,
            "burst_deadline_s": burst_deadline_s,
            "prefill_chunk_tokens": prefill_chunk, "clock_dt": clock_dt,
            "seed": seed,
        },
        "fifo": runs["fifo"],
        "slo": runs["slo"],
        "comparison": comparison,
    }


def run_replicas(*, arch: str = "llama3-8b", smoke: bool = True,
                 n_replicas: int = 3, requests: int = 8,
                 rate_rps: float = 100.0, slots: int = 2, max_len: int = 96,
                 prompt_len_range=(4, 16), gen_len_range=(3, 8),
                 kill_schedule=((6, 1),), reload_at_step: int = 12,
                 miss_limit: int = 3, clock_dt: float = 1e-3,
                 seed: int = 0) -> dict:
    """Failure-free vs chaos replica-set serving; ``serving-v7`` record.

    Both fleets serve the identical greedy workload on a deterministic
    :class:`StepClock`. The chaos fleet additionally takes ``kill_schedule``
    — per-replica :class:`~repro.runtime.failures.FailureInjector` crashes
    at the given router steps (requests requeue after heartbeat detection
    and restart from their prompts elsewhere) — and, at
    ``reload_at_step``, a checkpoint save that the watcher turns into a
    rolling drain → swap → rejoin weight reload. ``comparison`` records
    the goodput cost of the chaos (the dead replica's partial decodes are
    wasted work), the requeue latency distribution, and the two proof
    bits CI gates on: ``greedy_tokens_match`` (every requeued request
    regenerated a bit-identical stream) and ``lost_requests == 0`` with
    ``reload_dropped == 0``.
    """
    import tempfile

    from repro.checkpoint import CheckpointManager, CheckpointWatcher
    from repro.runtime import FailureInjector
    from repro.serve import ReplicaSet

    cfg, model = _build(arch, smoke)
    rng = jax.random.PRNGKey(seed)
    params = model.init(rng)
    make_workload = _workload_factory(
        cfg, requests=requests, rate_rps=rate_rps, shared_prefix=False,
        prefix_len=0, n_prefixes=1, prompt_len_range=prompt_len_range,
        gen_len_range=gen_len_range, temperature=0.0, seed=seed)
    kills = {}
    for step, rid in kill_schedule:
        kills.setdefault(int(rid), []).append(int(step))

    def fleet(chaos: bool, tmpdir: str):
        clock = StepClock(dt=clock_dt)
        factory = lambda: ServeEngine(  # noqa: E731
            model, params, n_slots=slots, max_len=max_len, rng=rng,
            clock=clock)
        manager = watcher = None
        actions = {}
        if chaos and reload_at_step:
            manager = CheckpointManager(tmpdir)
            watcher = CheckpointWatcher(manager)
            actions[reload_at_step] = lambda _rs: manager.save(1, params)
        rs = ReplicaSet(
            factory, n_replicas=n_replicas, clock=clock,
            miss_limit=miss_limit,
            failure_injectors={rid: FailureInjector(steps)
                               for rid, steps in kills.items()}
            if chaos else None,
            watcher=watcher,
            load_params=(lambda step: manager.restore(params)[0])
            if watcher else None)
        results, report = rs.run(make_workload(), actions=actions)
        rs.check()
        return results, report

    with tempfile.TemporaryDirectory() as tmpdir:
        base_results, base_report = fleet(False, tmpdir)
        chaos_results, chaos_report = fleet(True, tmpdir)
    tokens_match = len(base_results) == len(chaos_results) and all(
        a.uid == b.uid and np.array_equal(a.tokens, b.tokens)
        for a, b in zip(base_results, chaos_results))

    def _run_record(results, report):
        return {
            "requests": [{"uid": r.uid,
                          "prompt_tokens": r.metrics.prompt_tokens,
                          "new_tokens": r.metrics.new_tokens,
                          "ttft_ms": 1e3 * r.metrics.ttft_s}
                         for r in results],
            "fleet": report,
        }

    return {
        "schema": "serving-v7",
        "config": {
            "arch": cfg.name, "family": cfg.family, "smoke": smoke,
            "moa": cfg.moa_strategy.spec, "n_replicas": n_replicas,
            "n_slots": slots, "max_len": max_len, "requests": requests,
            "rate_rps": rate_rps,
            "prompt_len_range": list(prompt_len_range),
            "gen_len_range": list(gen_len_range),
            "kill_schedule": [[int(s), int(r)] for s, r in kill_schedule],
            "reload_at_step": reload_at_step, "miss_limit": miss_limit,
            "clock_dt": clock_dt, "seed": seed,
        },
        "baseline": _run_record(base_results, base_report),
        "chaos": _run_record(chaos_results, chaos_report),
        "comparison": {
            "greedy_tokens_match": bool(tokens_match),
            "lost_requests": chaos_report["lost_requests"],
            "kills": chaos_report["kills"],
            "deaths_detected": chaos_report["deaths_detected"],
            "requeues": chaos_report["requeues"],
            "requeue_latency_ms": chaos_report["requeue_latency_ms"],
            "reloads_completed": chaos_report["reloads_completed"],
            "reload_dropped": chaos_report["reload_dropped"],
            "goodput_tok_per_s_baseline": base_report["tok_per_s"],
            "goodput_tok_per_s_chaos": chaos_report["tok_per_s"],
            "goodput_ratio": chaos_report["tok_per_s"]
                / max(base_report["tok_per_s"], 1e-9),
            "router_steps_baseline": base_report["router_steps"],
            "router_steps_chaos": chaos_report["router_steps"],
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Continuous-batching serving benchmark (JSON output)")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=50.0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="run the dense-vs-paged comparison (serving-v2)")
    ap.add_argument("--backends", action="store_true",
                    help="run the jnp-vs-pallas paged attention backend "
                         "comparison (serving-v6; see docs/kernels.md)")
    ap.add_argument("--attn-backend", default=None,
                    choices=("auto", "jnp", "pallas"),
                    help="[--paged] paged attention backend for the paged "
                         "engine (default: the model config's, usually "
                         "auto)")
    ap.add_argument("--mesh", default="",
                    help="run the single-vs-sharded comparison on a DxM "
                         "device mesh, e.g. 2x4 (serving-v4; see "
                         "docs/sharded-serving.md)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="run the plain-vs-speculative accept-rate sweep "
                         "(serving-v3; see docs/spec-decode.md)")
    ap.add_argument("--slo", action="store_true",
                    help="run the FIFO-vs-SLO bursty-deadline comparison "
                         "on a deterministic virtual clock (serving-v5; "
                         "see docs/slo-scheduling.md)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="run the failure-free-vs-chaos replica-set "
                         "comparison with N replicas (serving-v7; see "
                         "docs/fault-tolerance.md)")
    ap.add_argument("--kill", default="6:1",
                    help="[--replicas] chaos schedule STEP:REPLICA[,...] "
                         "of injected replica crashes")
    ap.add_argument("--reload-at", type=int, default=12,
                    help="[--replicas] router step of the mid-run "
                         "checkpoint save that triggers the rolling hot "
                         "reload (0 = no reload)")
    ap.add_argument("--burst", type=int, default=8,
                    help="[--slo] short tight-deadline requests in the "
                         "burst")
    ap.add_argument("--deadline", type=float, default=0.035,
                    help="[--slo] burst TTFT deadline, virtual seconds "
                         "after arrival")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="[--slo] SLO engine's prefill chunk tokens "
                         "(0 = one-shot)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="[--spec-decode] draft tokens per verify window")
    ap.add_argument("--accept-probs", default="1.0,0.75,0.5,0.0",
                    help="[--spec-decode] comma-separated forced accept "
                         "probabilities to sweep")
    ap.add_argument("--block-size", type=int, default=16,
                    help="[--paged] tokens per physical KV page")
    ap.add_argument("--blocks", type=int, default=0,
                    help="[--paged] pool pages (0 = dense equivalent)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared-prefix workload (system-prompt style)")
    ap.add_argument("--prefix-len", type=int, default=16,
                    help="[--shared-prefix] shared prefix tokens")
    ap.add_argument("--prefixes", type=int, default=2,
                    help="[--shared-prefix] distinct prefixes")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip the unmeasured warmup replay (metrics then "
                         "include XLA compile time)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write the JSON record here (default: stdout)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if sum(map(bool, (args.paged, args.spec_decode, args.mesh,
                      args.slo, args.backends, args.replicas))) > 1:
        raise SystemExit("--paged, --spec-decode, --mesh, --slo, "
                         "--backends and --replicas are separate "
                         "comparisons; run them as separate records")
    if args.attn_backend and not args.paged:
        raise SystemExit("--attn-backend selects the paged engine's "
                         "attention backend; it requires --paged "
                         "(--backends always runs both)")
    if (args.spec_decode or args.mesh) and args.shared_prefix:
        raise SystemExit("--spec-decode and --mesh use the plain Poisson "
                         "workload; --shared-prefix belongs to the --paged "
                         "comparison")
    if args.slo and args.shared_prefix:
        raise SystemExit("--slo uses the bursty deadline workload; "
                         "--shared-prefix belongs to the --paged "
                         "comparison")
    common = dict(arch=args.arch, smoke=args.smoke, requests=args.requests,
                  rate_rps=args.rate, slots=args.slots, max_len=args.max_len,
                  temperature=args.temperature, seed=args.seed,
                  warmup=not args.no_warmup)
    if args.replicas:
        kill_schedule = []
        for item in filter(None, (s.strip()
                                  for s in args.kill.split(","))):
            step_s, rid_s = item.split(":")
            kill_schedule.append((int(step_s), int(rid_s)))
        record = run_replicas(arch=args.arch, smoke=args.smoke,
                              n_replicas=args.replicas,
                              requests=args.requests, rate_rps=args.rate,
                              slots=args.slots, max_len=args.max_len,
                              kill_schedule=tuple(kill_schedule),
                              reload_at_step=args.reload_at,
                              seed=args.seed)
    elif args.slo:
        record = run_slo(arch=args.arch, smoke=args.smoke,
                         slots=args.slots, max_len=args.max_len,
                         n_burst=args.burst,
                         burst_deadline_s=args.deadline,
                         prefill_chunk=args.prefill_chunk, seed=args.seed)
    elif args.mesh:
        # must run before jax initializes its backends: XLA locks the
        # host-platform device count at first init
        shape = parse_mesh(args.mesh)
        ensure_host_devices(shape)
        record = run_sharded(mesh_shape=shape, **common)
    elif args.spec_decode:
        record = run_spec(spec_k=args.spec_k,
                          accept_probs=tuple(
                              float(a) for a in
                              args.accept_probs.split(",") if a),
                          **common)
    elif args.backends:
        record = run_backends(block_size=args.block_size,
                              n_blocks=args.blocks,
                              shared_prefix=args.shared_prefix,
                              prefix_len=args.prefix_len,
                              n_prefixes=args.prefixes, **common)
    elif args.paged:
        record = run_paged(block_size=args.block_size, n_blocks=args.blocks,
                           shared_prefix=args.shared_prefix,
                           prefix_len=args.prefix_len,
                           n_prefixes=args.prefixes,
                           attn_backend=args.attn_backend, **common)
    else:
        record = run(shared_prefix=args.shared_prefix,
                     prefix_len=args.prefix_len, n_prefixes=args.prefixes,
                     **common)
    text = json.dumps(record, indent=2)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
        if record["schema"] == "serving-v7":
            c = record["comparison"]
            print(f"[bench] wrote {args.json}: serving-v7, "
                  f"kills={c['kills']} requeues={c['requeues']} "
                  f"(latency p95={c['requeue_latency_ms']['p95']:.0f}ms), "
                  f"reloads={c['reloads_completed']} "
                  f"(dropped {c['reload_dropped']}), lost="
                  f"{c['lost_requests']}, goodput "
                  f"{c['goodput_tok_per_s_baseline']:.0f}->"
                  f"{c['goodput_tok_per_s_chaos']:.0f} tok/s, greedy "
                  f"tokens "
                  f"{'MATCH' if c['greedy_tokens_match'] else 'DIVERGE'}",
                  file=sys.stderr)
        elif record["schema"] == "serving-v5":
            c = record["comparison"]
            print(f"[bench] wrote {args.json}: serving-v5, deadline ttft "
                  f"p99 fifo={c['deadline_ttft_p99_ms_fifo']:.0f}ms "
                  f"slo={c['deadline_ttft_p99_ms_slo']:.0f}ms, attainment "
                  f"{c['attainment_fifo']:.2f}->{c['attainment_slo']:.2f}, "
                  f"goodput {c['goodput_tok_per_s_fifo']:.0f}->"
                  f"{c['goodput_tok_per_s_slo']:.0f} tok/s, "
                  f"preemptions={c['preemptions']}, greedy tokens "
                  f"{'MATCH' if c['greedy_tokens_match'] else 'DIVERGE'}",
                  file=sys.stderr)
        elif record["schema"] == "serving-v6":
            c = record["comparison"]
            print(f"[bench] wrote {args.json}: serving-v6, tok/s "
                  f"jnp={c['tok_per_s_jnp']:.1f} "
                  f"pallas={c['tok_per_s_pallas']:.1f}, kv bytes/run "
                  f"gathered={c['gathered_kv_bytes']:,}B "
                  f"fused={c['fused_kv_bytes']:,}B "
                  f"(saved {c['kv_bytes_saved_frac']:.0%}), greedy tokens "
                  f"{'MATCH' if c['greedy_tokens_match'] else 'DIVERGE'}",
                  file=sys.stderr)
        elif record["schema"] == "serving-v4":
            c = record["comparison"]
            m = record["config"]["mesh"]
            axes = "x".join(str(s) for s in m["shape"])
            print(f"[bench] wrote {args.json}: serving-v4, mesh {axes} "
                  f"({m['n_devices']} devices), tok/s "
                  f"single={c['tok_per_s_single']:.1f} "
                  f"sharded={c['tok_per_s_sharded']:.1f}, greedy tokens "
                  f"{'MATCH' if c['greedy_tokens_match'] else 'DIVERGE'}",
                  file=sys.stderr)
        elif record["schema"] == "serving-v3":
            c = record["comparison"]
            pts = ", ".join(
                f"a={p['accept_prob']:.2f}:{p['tokens_per_step']:.2f}"
                for p in c["curve"])
            print(f"[bench] wrote {args.json}: serving-v3, "
                  f"tok/step plain={c['tokens_per_step_plain']:.2f} "
                  f"spec[{pts}]", file=sys.stderr)
        elif record["schema"] == "serving-v2":
            c = record["comparison"]
            print(f"[bench] wrote {args.json}: serving-v2, "
                  f"ttft p50 dense={c['ttft_p50_ms_dense']:.0f}ms "
                  f"paged={c['ttft_p50_ms_paged']:.0f}ms, "
                  f"prefix hits={c['prefix_hits']}, "
                  f"resident={c['resident_kv_bytes']:,}B / "
                  f"dense {c['dense_equiv_kv_bytes']:,}B", file=sys.stderr)
        else:
            agg = record["aggregate"]
            print(f"[bench] wrote {args.json}: {agg['n_requests']} requests, "
                  f"{agg['tok_per_s']:.1f} tok/s, "
                  f"ttft p50={agg['ttft_ms']['p50']:.0f}ms, "
                  f"occupancy={agg['slot_occupancy']:.2f}", file=sys.stderr)
    else:
        print(text)


if __name__ == "__main__":
    main()
