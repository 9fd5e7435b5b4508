"""Serving CLI: continuous-batching engine (default) or static batch.

Thin front-end over :mod:`repro.serve`. The default mode drives the
:class:`~repro.serve.engine.ServeEngine` with a synthetic Poisson workload
(open-loop arrivals, mixed prompt/generation lengths) and prints per-request
and aggregate latency/throughput metrics; ``--static`` keeps the original
lockstep path (:func:`serve_batch`: one joint prefill, then batched greedy
or sampled decode — the fast path when all requests start together).

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --requests 8 --rate 50 --slots 4
  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
      --static --batch 4 --prompt-len 64 --gen-len 32 --temperature 0.8
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeSpec
from repro.configs.registry import get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import ensure_host_devices, make_mesh, parse_mesh
from repro.models.api import build_model
from repro.serve import (GREEDY, ReplicaSet, Sampler, ServeEngine, StepClock,
                         bursty_workload, poisson_workload, resolve_drafter)

__all__ = ["serve_batch", "main"]


def serve_batch(model, params, prompts: dict, *, gen_len: int,
                max_len: int, sampler: Sampler = GREEDY, rng=None):
    """Static-batch serving: joint prefill + ``gen_len`` lockstep decode
    steps with donated cache buffers.

    ``sampler`` is the single next-token policy for the whole batch
    (``rng`` required when it is not greedy). Returns ``(tokens, timings)``
    where ``tokens`` is ``(B, gen_len)`` int32 and timings are in seconds
    (``per_token_ms`` in milliseconds).
    """
    if not sampler.greedy and rng is None:
        raise ValueError("non-greedy sampler needs an rng key")
    prefill_fn = jax.jit(
        lambda p, b: model.prefill(p, b, max_len=max_len))
    decode_fn = jax.jit(model.decode_step, donate_argnums=(1,))

    t0 = time.monotonic()
    logits, cache = prefill_fn(params, prompts)
    logits.block_until_ready()
    t_prefill = time.monotonic() - t0

    B = logits.shape[0]
    out_tokens = []

    def next_tok(lg):
        nonlocal rng
        if sampler.greedy:
            return sampler(lg[:, -1])[:, None]
        rng, k = jax.random.split(rng)
        return sampler(lg[:, -1], k)[:, None]

    tok = next_tok(logits)
    t0 = time.monotonic()
    for _ in range(gen_len):
        out_tokens.append(tok)
        logits, cache = decode_fn(params, cache, tok)
        tok = next_tok(logits)
    tok.block_until_ready()
    t_decode = time.monotonic() - t0
    tokens = jnp.concatenate(out_tokens, axis=1)
    return tokens, {
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_per_s": B * gen_len / max(t_decode, 1e-9),
        "per_token_ms": 1e3 * t_decode / max(gen_len, 1),
    }


def _build(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode step "
                         "(assignment skip rule)")
    return cfg, build_model(cfg)


def _sampler(args) -> Sampler:
    return GREEDY if args.greedy else Sampler(args.temperature)


def _run_static(args):
    cfg, model = _build(args)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    shape = ShapeSpec("serve", args.prompt_len, args.batch, "prefill")
    prompts = model.make_batch(rng, shape)
    max_len = args.prompt_len + args.gen_len + 1
    tokens, stats = serve_batch(model, params, prompts,
                                gen_len=args.gen_len, max_len=max_len,
                                sampler=_sampler(args), rng=rng)
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen_len}")
    print(f"[serve] prefill={stats['prefill_s']*1e3:.0f}ms "
          f"decode={stats['per_token_ms']:.1f}ms/tok "
          f"throughput={stats['decode_tok_per_s']:.1f} tok/s")
    print(f"[serve] sample: {np.asarray(tokens[0, :16]).tolist()}")


def _run_engine(args):
    cfg, model = _build(args)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    spec_margin = args.spec_k if args.spec_decode else 0
    max_len = args.max_len \
        or (args.prompt_len + args.gen_len + spec_margin + 1) * 2
    if args.paged and max_len % args.block_size:
        max_len += args.block_size - max_len % args.block_size
    drafter = resolve_drafter(args.drafter, args.spec_k) \
        if args.spec_decode else None
    mesh = make_mesh(parse_mesh(args.mesh)) if args.mesh else None
    chunk = args.prefill_chunk or None
    if chunk is not None and args.paged and chunk % args.block_size:
        raise SystemExit(f"--prefill-chunk {chunk} must be a multiple of "
                         f"--block-size {args.block_size}")
    if args.attn_backend and not args.paged:
        raise SystemExit("--attn-backend selects the paged attention "
                         "backend; it requires --paged")
    engine = ServeEngine(model, params, n_slots=args.slots, max_len=max_len,
                         paged=args.paged, block_size=args.block_size,
                         n_blocks=args.blocks or None, rng=rng,
                         drafter=drafter, mesh=mesh,
                         prefill_chunk_tokens=chunk,
                         scheduling=args.scheduling,
                         attn_backend=args.attn_backend or None)
    if args.scheduling == "slo":
        requests = bursty_workload(
            vocab=cfg.vocab, n_long=args.slots,
            n_burst=max(args.requests - args.slots, 1),
            long_prompt_len=args.prompt_len, long_gen_len=args.gen_len,
            burst_prompt_len=max(args.prompt_len // 4, 1),
            burst_gen_len=max(args.gen_len // 4, 1),
            burst_deadline_s=args.deadline, sampler=_sampler(args),
            seed=args.seed)
    else:
        requests = poisson_workload(
            n_requests=args.requests, vocab=cfg.vocab, rate_rps=args.rate,
            prompt_len_range=(min(4, args.prompt_len), args.prompt_len),
            gen_len_range=(min(2, args.gen_len), args.gen_len),
            sampler=_sampler(args), seed=args.seed)
    results, report = engine.run(requests, warmup=not args.no_warmup)
    print(f"[serve] arch={cfg.name} slots={args.slots} max_len={max_len} "
          f"requests={args.requests} rate={args.rate}/s")
    if mesh is not None:
        axes = ", ".join(f"{a}={s}" for a, s in
                         zip(mesh.axis_names, mesh.devices.shape))
        print(f"[serve] mesh: ({axes}) over {mesh.devices.size} devices, "
              f"family rules for {cfg.family!r} (docs/sharded-serving.md)")
    for r in results:
        m = r.metrics
        print(f"[serve]   req {r.uid}: slot={r.slot} prompt={r.prompt_len} "
              f"gen={r.tokens.size} ttft={m.ttft_s*1e3:.0f}ms "
              f"{m.per_token_ms:.1f}ms/tok ({r.finish_reason.value})")
    print(f"[serve] aggregate: {report['tok_per_s']:.1f} tok/s, "
          f"ttft p50={report['ttft_ms']['p50']:.0f}ms "
          f"p95={report['ttft_ms']['p95']:.0f}ms, "
          f"occupancy={report['slot_occupancy']:.2f}, "
          f"slot_reuse={report['slot_reuse']}, "
          f"warmup compile={report['compile_s']*1e3:.0f}ms (kept out of "
          f"wall_s)")
    if args.spec_decode:
        sp = report["spec"]
        print(f"[serve] spec: drafter={args.drafter} k={sp['k']}, "
              f"{sp['tokens_per_step']:.2f} tokens/step "
              f"(plain decode = 1.00), accept rate "
              f"{sp['accept_rate']:.2f}, accepted hist "
              f"{sp['accepted_hist']}, draft steps {sp['draft_steps']}")
    if args.paged:
        pg = report["paged"]
        print(f"[serve] paged: {pg['n_blocks']}x{pg['block_size']}-token "
              f"blocks, backend={pg['attn_backend']}, "
              f"occupancy={pg['block_occupancy']:.2f}, "
              f"prefix hits={pg['prefix_hits']}/{pg['admissions']}, "
              f"cow={pg['cow_count']}, "
              f"resident={pg['resident_kv_bytes']:,}B "
              f"(dense equiv {pg['dense_equiv_kv_bytes']:,}B), "
              f"kv read/step gathered={pg['gathered_kv_bytes_per_step']:,.0f}B "
              f"fused={pg['fused_kv_bytes_per_step']:,.0f}B")
    if "slo" in report:
        sl = report["slo"]
        print(f"[serve] slo ({report['scheduling']}): attainment "
              f"{sl['deadline_met']}/{sl['deadline_requests']} "
              f"({sl['attainment']:.2f}), goodput "
              f"{sl['goodput_tok_per_s']:.1f} tok/s, deadline ttft "
              f"p99={sl['deadline_ttft_ms']['p99']:.0f}ms, "
              f"preemptions={sl['preemptions']} "
              f"(spills={sl['spills']}, revivals={sl['revivals']}), "
              f"chunked ticks={sl['prefill_chunk_count']}")


def _parse_kill_schedule(spec: str):
    """``"step:replica,step:replica"`` → {replica: [steps]}."""
    schedule = {}
    for item in filter(None, (s.strip() for s in spec.split(","))):
        try:
            step_s, rid_s = item.split(":")
            step, rid = int(step_s), int(rid_s)
        except ValueError:
            raise SystemExit(f"--kill: bad entry {item!r}; expected "
                             "STEP:REPLICA, e.g. 6:1")
        schedule.setdefault(rid, []).append(step)
    return schedule


def _run_replicas(args):
    """Replica-set serving on a deterministic StepClock: the chaos smoke.

    Kills from ``--kill`` are injected through per-replica
    FailureInjectors at the scheduled router steps; ``--reload-at`` saves
    the serving weights as a checkpoint mid-run so the watcher triggers a
    rolling drain → swap → rejoin. Exits non-zero if any request is lost,
    any reload drops an in-flight request, or (greedy) any token stream
    diverges from the failure-free fleet baseline.
    """
    import tempfile

    from repro.checkpoint import CheckpointManager, CheckpointWatcher
    from repro.runtime import FailureInjector

    if args.spec_decode or args.scheduling != "fifo" or args.mesh \
            or args.static:
        raise SystemExit("--replicas drives plain fifo engines tick-by-"
                         "tick; --spec-decode/--scheduling slo/--mesh/"
                         "--static are single-engine modes")
    cfg, model = _build(args)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init(rng)
    max_len = args.max_len or (args.prompt_len + args.gen_len + 1) * 2
    if args.paged and max_len % args.block_size:
        max_len += args.block_size - max_len % args.block_size
    sampler = _sampler(args)
    make_workload = lambda: poisson_workload(  # noqa: E731
        n_requests=args.requests, vocab=cfg.vocab, rate_rps=args.rate,
        prompt_len_range=(min(4, args.prompt_len), args.prompt_len),
        gen_len_range=(min(2, args.gen_len), args.gen_len),
        sampler=sampler, seed=args.seed)
    kills = _parse_kill_schedule(args.kill)
    for rid in kills:
        if not 0 <= rid < args.replicas:
            raise SystemExit(f"--kill: replica {rid} out of range "
                             f"(0..{args.replicas - 1})")

    def fleet(chaos: bool, tmpdir):
        clock = StepClock(dt=args.dt)
        factory = lambda: ServeEngine(  # noqa: E731
            model, params, n_slots=args.slots, max_len=max_len,
            paged=args.paged, block_size=args.block_size,
            n_blocks=args.blocks or None, rng=rng, clock=clock)
        manager = watcher = None
        actions = {}
        if chaos and args.reload_at:
            manager = CheckpointManager(tmpdir)
            watcher = CheckpointWatcher(manager)
            actions[args.reload_at] = \
                lambda _rs: manager.save(1, params)
        rs = ReplicaSet(
            factory, n_replicas=args.replicas, clock=clock,
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
        results, report = fleet(True, tmpdir)
    print(f"[serve] arch={cfg.name} replicas={args.replicas} "
          f"slots={args.slots}/replica max_len={max_len} "
          f"requests={args.requests} rate={args.rate}/s dt={args.dt}")
    print(f"[serve] chaos: kills={report['kills']} (schedule "
          f"{args.kill or 'none'}), deaths detected="
          f"{report['deaths_detected']}, requeues={report['requeues']}, "
          f"requeue latency p95="
          f"{report['requeue_latency_ms']['p95']:.0f}ms")
    print(f"[serve] reload: completed={report['reloads_completed']} "
          f"dropped={report['reload_dropped']} versions="
          f"{[r['param_version'] for r in report['replicas']]}")
    print(f"[serve] fleet: {report['completed']}/{report['requests']} "
          f"requests, {report['tok_per_s']:.1f} tok/s "
          f"(baseline {base_report['tok_per_s']:.1f}), router steps="
          f"{report['router_steps']}")
    failures = []
    if report["lost_requests"]:
        failures.append(f"{report['lost_requests']} requests lost")
    if report["reload_dropped"]:
        failures.append(f"reload dropped {report['reload_dropped']} "
                        "in-flight requests")
    if args.reload_at and not report["reloads_completed"]:
        failures.append("scheduled reload never completed")
    if sampler.greedy:
        diverged = [r.uid for r, b in zip(results, base_results)
                    if not np.array_equal(r.tokens, b.tokens)]
        if diverged:
            failures.append(f"greedy tokens diverged from failure-free "
                            f"baseline for uids {diverged}")
        else:
            print("[serve] greedy tokens bit-identical to failure-free "
                  "baseline")
    if failures:
        raise SystemExit("[serve] FAIL: " + "; ".join(failures))


def main():
    ap = argparse.ArgumentParser(
        description="Serve a registry arch: continuous batching (default) "
                    "or --static lockstep batch")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced CPU-runnable config")
    ap.add_argument("--static", action="store_true",
                    help="static-batch serve_batch path")
    ap.add_argument("--batch", type=int, default=4,
                    help="[static] batch size")
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="prompt tokens ([engine] upper bound of the range)")
    ap.add_argument("--gen-len", type=int, default=32,
                    help="generated tokens ([engine] upper bound)")
    ap.add_argument("--requests", type=int, default=8,
                    help="[engine] number of workload requests")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="[engine] Poisson arrival rate, requests/s")
    ap.add_argument("--slots", type=int, default=4,
                    help="[engine] decode slots (in-flight requests)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="[engine] per-slot context capacity, tokens")
    ap.add_argument("--paged", action="store_true",
                    help="[engine] paged KV-cache: shared block pool with "
                         "ref-counted prefix caching (docs/paged-kv.md)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="[engine --paged] tokens per physical KV page")
    ap.add_argument("--blocks", type=int, default=0,
                    help="[engine --paged] pool size in pages (0 = dense "
                         "equivalent slots*max_len/block_size)")
    ap.add_argument("--attn-backend", default="",
                    choices=("", "auto", "jnp", "pallas"),
                    help="[engine --paged] paged attention backend: jnp "
                         "(gathered KV view, reference), pallas (fused "
                         "block-table flash kernels, docs/kernels.md), or "
                         "auto (pallas on TPU). Default: the model "
                         "config's (auto)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="[engine] speculative decoding: draft k tokens "
                         "per tick, verify in one pass "
                         "(docs/spec-decode.md)")
    ap.add_argument("--drafter", default="ngram?n=3",
                    help="[engine --spec-decode] drafter spec: "
                         "ngram[?n=N] (prompt lookup) or "
                         "oracle[?accept=P] (target-as-drafter, forced "
                         "accept rate)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="[engine --spec-decode] draft tokens per verify "
                         "window")
    ap.add_argument("--mesh", default="",
                    help="[engine] run sharded on a DxM device mesh (e.g. "
                         "2x4): params tensor-parallel, KV cache sharded "
                         "over slots/heads (docs/sharded-serving.md). On "
                         "CPU the devices are XLA host-platform devices")
    ap.add_argument("--scheduling", choices=["fifo", "slo"], default="fifo",
                    help="[engine] admission policy: fifo (arrival order) "
                         "or slo (priority + earliest TTFT deadline, with "
                         "preemption; docs/slo-scheduling.md). slo swaps "
                         "the workload for a deadline-carrying bursty one")
    ap.add_argument("--deadline", type=float, default=0.25,
                    help="[engine --scheduling slo] burst requests' TTFT "
                         "deadline, seconds after arrival")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="[engine] split prompts longer than this into "
                         "fixed-budget prefill chunks interleaved with "
                         "decode ticks (0 = one-shot; see "
                         "repro.launch.costing.prefill_chunk_guidance)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="[engine] serve through a fault-tolerant "
                         "replica set of N engines on a deterministic "
                         "StepClock (docs/fault-tolerance.md); 0 = single "
                         "engine, -1 = plan from the visible device count "
                         "(repro.runtime.elastic.plan_replicas)")
    ap.add_argument("--kill", default="",
                    help="[--replicas] chaos schedule STEP:REPLICA[,...] — "
                         "each entry crashes that replica at that router "
                         "step via a FailureInjector; its requests requeue "
                         "after heartbeat detection")
    ap.add_argument("--reload-at", type=int, default=0,
                    help="[--replicas] router step at which to save the "
                         "weights as a checkpoint, triggering a rolling "
                         "watcher-driven hot reload (0 = no reload)")
    ap.add_argument("--dt", type=float, default=1e-3,
                    help="[--replicas] StepClock virtual seconds per "
                         "clock read")
    ap.add_argument("--no-warmup", action="store_true",
                    help="[engine] skip the unmeasured warmup tick "
                         "(first-call XLA compile time then lands in "
                         "wall_s instead of compile_s)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--greedy", action="store_true",
                    help="force greedy decode regardless of --temperature")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.mesh:
        # before any backend touch: XLA locks device count at first init
        ensure_host_devices(parse_mesh(args.mesh))
    dev = jax.devices()[0]
    print(f"[serve] platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={jax.device_count()}")
    if args.replicas == -1:
        from repro.runtime import plan_replicas
        args.replicas = plan_replicas(jax.device_count())
    if args.replicas:
        _run_replicas(args)
    elif args.static:
        _run_static(args)
    else:
        _run_engine(args)


if __name__ == "__main__":
    main()
