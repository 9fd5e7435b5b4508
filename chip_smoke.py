"""Bring-up smoke run of the serve path on a TPU: zamba2-1.2b, published widths.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # tensor-parallel (data=1, model=4) mesh
                                    # vs device 0 alone

Builds ``zamba2-1.2b`` from the registry at its published config (38 Mamba2
layers, d_model 2048, shared attention+MLP block every 6 layers, vocab
32000) with seeded random weights, and serves a burst of 8 requests from
``poisson_workload`` through the continuous-batching ``ServeEngine`` over a
paged KV pool. On the chip the paged-attention kernel, the ``dot_moa``
projections and the SSD layers all run compiled.

Correctness is checked on the chip, in this process, against a second
engine over the same requests:

* one chip: the fused Pallas paged-attention kernel against the jnp gather
  reference (``attn_backend="jnp"``);
* ``--chips 4``: the engine on a ``(data=1, model=4)`` mesh under the
  tensor-parallel rules (heads, KV heads, d_ff, SSM heads and vocab split
  four ways) against the same requests on device 0 alone.

Both compare the last-position logits of the first decode ticks, and the
greedy tokens they pick, within :data:`ENGINE_TOL`. On one chip the
compiled paged kernel is first held to a float64 host reference
(:data:`KERNEL_TOL`). The timings printed are from a bring-up run, not a
benchmark. Every failure exits non-zero; the last line of standard
output is a JSON object naming the device, printed only when every phase
passed. There is no CPU fallback.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

#: first decode ticks whose logits are compared (every slot is busy in all
#: of them: each request generates at least GEN_RANGE[0] > PROBE_TICKS tokens)
PROBE_TICKS = 4
#: largest admitted deviation of the compiled paged kernel from a float64
#: host reference, relative to the reference's largest |output|: its inputs
#: are exact in bf16, so the roundings allowed are two of bf16's unit
#: roundoff (2^-8): the softmax weights on the MXU and the bf16 output
KERNEL_TOL = 2.0 ** -7
#: largest admitted logit deviation between two engines that serve the same
#: bf16 program with different roundings (the Pallas kernel against the jnp
#: gather; the mesh against one device), relative to the reference's
#: largest |logit|. Activations, the residual stream and the logits are
#: bf16, so a change in the order of any f32 sum moves some of them by one
#: bf16 step (2^-8); through the layers that comes to ~2e-2 of the largest
#: logit (PERF.md). An attention mask off by one position moved them by
#: 9e-2 to 2.8e-1 in a mutation test.
ENGINE_TOL = 2.0 ** -4
#: largest top-2 margin of the reference's logits, relative to its largest
#: |logit|, at which the two engines' greedy tokens may differ: two logits
#: each within the typical deviation (~2e-2 at the largest of 32000, far
#: less at a given one) can swap only across a margin this small. Any flip
#: is already bounded by ``2 * ENGINE_TOL`` once the logits pass, so this
#: fixed, tighter bound is what makes the token check a check of its own.
TIE_MARGIN = 2.0 ** -5
N_REQUESTS = 8
N_SLOTS = 4
PROMPT_RANGE = (384, 512)
GEN_RANGE = (8, 32)
BLOCK_SIZE = 16
ARCH = "zamba2-1.2b"


class SmokeFailure(Exception):
    pass


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_requests(vocab: int, seed: int):
    from repro.serve import poisson_workload

    # a burst: every request has arrived by the first tick, so both engines
    # of a comparison admit the same requests into the same slots on the
    # same ticks (generation lengths are fixed, there is no EOS)
    return poisson_workload(n_requests=N_REQUESTS, vocab=vocab, rate_rps=1e9,
                            prompt_len_range=PROMPT_RANGE,
                            gen_len_range=GEN_RANGE, seed=seed)


def max_len_for(block_size: int) -> int:
    need = PROMPT_RANGE[1] + GEN_RANGE[1] + 1
    return -(-need // block_size) * block_size


def serve(engine, requests, *, warmup: bool = False):
    """Serve ``requests`` through ``engine.run``; return ``(results,
    report, logits)``, where ``logits`` holds the ``(n_slots, vocab)``
    last-position logits of the first :data:`PROBE_TICKS` decode ticks."""
    logits = []

    def keep(lg):
        if len(logits) < PROBE_TICKS:
            logits.append(np.asarray(lg, np.float32))

    results, report = engine.run(requests, warmup=warmup, on_logits=keep)
    if len(logits) < PROBE_TICKS:
        raise SmokeFailure(f"only {len(logits)} decode ticks ran; expected "
                           f"at least {PROBE_TICKS}")
    return results, report, logits


def compare(name: str, got, want, *, tol: float) -> None:
    """Hold ``got`` to ``want`` tick by tick and slot by slot.

    At each probed tick, in every slot whose tokens have agreed so far, the
    logits must lie within ``tol`` of the reference's largest |logit|, and
    the greedy tokens must agree unless the reference's top-2 margin is at
    most :data:`TIE_MARGIN` of its largest |logit| (a near tie that two
    roundings may order either way). After such a flip the
    slot's inputs differ, so it leaves the comparison. Every tick is
    logged before :class:`SmokeFailure` is raised for any other mismatch.
    """
    live = np.ones(want[0].shape[0], bool)
    faults = []
    for t, (g, w) in enumerate(zip(got, want)):
        if not np.isfinite(g).all():
            raise SmokeFailure(f"{name}: non-finite logits at tick {t + 1}")
        devs, flips = [], []
        for s in np.flatnonzero(live):
            scale = max(float(np.max(np.abs(w[s]))), 1e-30)
            rel = float(np.max(np.abs(g[s] - w[s]))) / scale
            devs.append(rel)
            if rel > tol:
                faults.append(f"tick {t + 1} slot {s}: logits deviate by "
                              f"{rel:.3e} of max |logit|")
            tok_g, tok_w = int(np.argmax(g[s])), int(np.argmax(w[s]))
            if tok_g == tok_w:
                continue
            top2 = np.sort(w[s])[-2:]
            margin = float(top2[1] - top2[0]) / scale
            flips.append(f"slot {s} margin {margin:.3e}")
            if margin > TIE_MARGIN:
                faults.append(f"tick {t + 1} slot {s}: greedy token {tok_g} "
                              f"vs reference {tok_w}, top-2 margin "
                              f"{margin:.3e} of max |logit|")
            live[s] = False
        _log(f"{name}: tick {t + 1}: logit deviation per slot "
             f"{['%.3e' % d for d in devs]} of max |logit| (tolerance "
             f"{tol:.3e}); greedy flips {flips or 'none'}")
    if faults:
        raise SmokeFailure(f"{name}: " + "; ".join(faults))


def kernel_check(seed: int) -> None:
    """The compiled paged-attention kernel against a float64 host reference
    at zamba2-1.2b's shared-block widths (32 KV heads of 64), 4 slots at
    uneven depths over 35-page tables: decode and a 16-token window, bf16
    and int8 pools."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    B, Hk, D, bs, nb = 4, 32, 64, BLOCK_SIZE, 35
    bf16 = lambda x: np.asarray(x, np.float32).astype(jnp.bfloat16)  # noqa
    tables = (1 + np.arange(B * nb, dtype=np.int32)).reshape(B, nb)
    for T in (1, 16):
        start = np.array([0, 7, 300, nb * bs - T], np.int32)
        q = bf16(rng.standard_normal((B, T, Hk, D)))
        for kv in ("bf16", "int8"):
            shape = (B * nb + 1, bs, Hk, D)
            if kv == "int8":
                k = rng.integers(-127, 128, shape).astype(np.int8)
                v = rng.integers(-127, 128, shape).astype(np.int8)
                ks, vs = (rng.uniform(0.01, 0.1, shape[:3]).astype(
                    np.float32) for _ in range(2))
                scales = dict(k_scale=ks, v_scale=vs)
                # the kernel rounds dequantized KV through bf16
                k_ref = bf16(k * ks[..., None]).astype(np.float64)
                v_ref = bf16(v * vs[..., None]).astype(np.float64)
            else:
                k, v = (bf16(rng.standard_normal(shape)) for _ in range(2))
                scales = {}
                k_ref, v_ref = k.astype(np.float64), v.astype(np.float64)
            out = np.asarray(ops.paged_attention(
                q, k, v, tables, start, **scales), np.float64)
            want = np.zeros_like(out)
            for b in range(B):
                kb = k_ref[tables[b]].reshape(-1, Hk, D)
                vb = v_ref[tables[b]].reshape(-1, Hk, D)
                for t in range(T):
                    n = start[b] + t + 1
                    sc = np.einsum("hd,khd->hk", q[b, t].astype(np.float64),
                                   kb[:n]) * D ** -0.5
                    p = np.exp(sc - sc.max(-1, keepdims=True))
                    want[b, t] = np.einsum("hk,khd->hd", p, vb[:n]) \
                        / p.sum(-1)[:, None]
            rel = float(np.max(np.abs(out - want)) / np.max(np.abs(want)))
            _log(f"paged kernel T={T} {kv} pool vs float64: max deviation "
                 f"{rel:.3e} of max |output| (tolerance {KERNEL_TOL:.3e})")
            if not rel <= KERNEL_TOL:
                raise SmokeFailure(f"paged kernel T={T} {kv}: deviation "
                                   f"{rel:.3e} > {KERNEL_TOL:.3e}")


def kernels_in_decode(engine) -> dict:
    """Count the Mosaic kernels in the engine's lowered paged decode step
    (lowered, not run: distinct kernel bodies, not calls). A kernel that
    ran in interpret mode would not appear."""
    import jax.numpy as jnp

    toks = jnp.zeros((engine.n_slots, 1), jnp.int32)
    width = engine.max_len // engine.block_size
    text = jax.jit(
        lambda p, c, t: engine.model.paged_decode_step(
            p, c, t, live_blocks=width)
    ).lower(engine.params, engine.cache, toks).as_text()
    return {k: text.count(f'kernel_name = "{k}"')
            for k in ("_paged_kernel", "_dot_moa_kernel")}


def report_run(label: str, results, report) -> None:
    _log(f"{label} (bring-up run, not a benchmark):")
    for r in results:
        m = r.metrics
        _log(f"  req {r.uid}: prompt={r.prompt_len} tokens={r.tokens.size} "
             f"ttft={m.ttft_s * 1e3:.1f}ms {m.per_token_ms:.2f}ms/tok "
             f"first={r.tokens[:4].tolist()}")
    _log(f"  wall {report['wall_s']:.2f}s, {report['tok_per_s']:.1f} tok/s, "
         f"ttft p50={report['ttft_ms']['p50']:.1f}ms")


def same_tokens(a, b) -> bool:
    return len(a) == len(b) and all(
        x.uid == y.uid and np.array_equal(x.tokens, y.tokens)
        for x, y in zip(a, b))


def init_params(model, seed: int):
    cfg = model.cfg
    t0 = time.monotonic()
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    _log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
         f"vocab {cfg.vocab}; params {n_bytes:,} bytes "
         f"({cfg.param_dtype}), seeded init {time.monotonic() - t0:.1f}s")
    return params


def one_chip(model, seed: int, requests, max_len: int) -> None:
    """Fused Pallas paged kernel vs the jnp gather reference, one chip."""
    from repro.serve import ServeEngine

    kernel_check(seed)
    params = init_params(model, seed)

    def engine(backend):
        return ServeEngine(model, params, n_slots=N_SLOTS, max_len=max_len,
                           paged=True, block_size=BLOCK_SIZE,
                           attn_backend=backend)

    eng = engine("pallas")
    kernels = kernels_in_decode(eng)
    _log(f"Mosaic kernels in the paged decode step: {kernels}")
    if not kernels["_paged_kernel"] or not kernels["_dot_moa_kernel"]:
        raise SmokeFailure("paged attention or dot_moa did not lower to a "
                           "compiled Mosaic kernel")
    t0 = time.monotonic()
    cold, cold_report, got = serve(eng, requests, warmup=True)
    _log(f"cold run: {time.monotonic() - t0:.1f}s in all, warmup compile "
         f"{cold_report['compile_s']:.1f}s (every decode bucket), prefill "
         "compiles inside the run (one per prompt length)")
    warm, warm_report = eng.run(requests)
    report_run("warm run, fused Pallas paged attention", warm, warm_report)
    if warm_report["paged"]["attn_backend"] != "pallas":
        raise SmokeFailure("engine did not resolve to the Pallas backend")
    if not same_tokens(cold, warm):
        raise SmokeFailure("a repeated run of the same requests produced "
                           "different tokens")

    ref = engine("jnp")
    if kernels_in_decode(ref)["_paged_kernel"]:
        raise SmokeFailure("the jnp reference engine ran the Pallas kernel")
    ref_results, _, want = serve(ref, requests)
    compare("pallas vs jnp gather", got, want, tol=ENGINE_TOL)
    n_same = sum(np.array_equal(a.tokens, b.tokens)
                 for a, b in zip(cold, ref_results))
    _log(f"whole token streams identical for {n_same}/{len(cold)} requests")


def four_chips(model, seed: int, requests, max_len: int) -> None:
    """Tensor-parallel ``(data=1, model=4)`` mesh engine vs the same
    requests on device 0 alone."""
    from repro.launch.mesh import make_mesh
    from repro.parallel import DEFAULT_RULES
    from repro.serve import ServeEngine

    params = init_params(model, seed)
    kw = dict(n_slots=N_SLOTS, max_len=max_len, paged=True,
              block_size=BLOCK_SIZE)
    ref_results, _, want = serve(ServeEngine(model, params, **kw), requests)

    # the hybrid family's own serve rules replicate every model-axis
    # parameter (bitwise parity with one device), which would run four
    # copies; the tensor-parallel rules split heads, KV heads, d_ff, the
    # SSM heads and the vocab four ways
    eng = ServeEngine(model, params, mesh=make_mesh((1, 4)),
                      rules=DEFAULT_RULES, **kw)
    del params
    for name, leaf in (("KV pool k", eng.cache["kv"]["k"]),
                       ("SSM state h", eng.cache["ssm"]["h"]),
                       ("shared_attn wq", eng.params["shared_attn"]["wq"])):
        sh = leaf.sharding
        shard = sh.shard_shape(leaf.shape)
        _log(f"{name}: {sh.spec} over {len(sh.device_set)} devices "
             f"(shard {shard} of {leaf.shape})")
        if len(sh.device_set) != 4 or shard == leaf.shape:
            raise SmokeFailure(f"{name} is not split over the 4 devices")
    t0 = time.monotonic()
    got_results, report, got = serve(eng, requests, warmup=True)
    _log(f"mesh cold run: {time.monotonic() - t0:.1f}s in all, warmup "
         f"compile {report['compile_s']:.1f}s")
    report_run("mesh (data=1, model=4) engine", got_results, report)
    compare("mesh vs device 0", got, want, tol=ENGINE_TOL)
    n_same = sum(np.array_equal(a.tokens, b.tokens)
                 for a, b in zip(got_results, ref_results))
    _log(f"whole token streams identical for {n_same}/{len(got_results)} "
         "requests")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: Pallas vs gather on one chip; 4: the mesh "
                         "path vs device 0, and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {dev.platform} devices; this "
              "script runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.configs.registry import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.api import build_model

    enable_compile_cache()
    _log(f"platform={dev.platform} device_kind={dev.device_kind} "
         f"devices={len(devices)}")
    cfg = get_config(ARCH)
    model = build_model(cfg)
    requests = make_requests(cfg.vocab, args.seed)
    max_len = max_len_for(BLOCK_SIZE)
    _log(f"{len(requests)} requests, prompts "
         f"{[r.prompt_len for r in requests]}, generating "
         f"{[r.max_new_tokens for r in requests]}; {N_SLOTS} slots, "
         f"max_len {max_len}, {BLOCK_SIZE}-token pages")

    try:
        if args.chips == 4:
            four_chips(model, args.seed, requests, max_len)
        else:
            one_chip(model, args.seed, requests, max_len)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr)
        return 1
    for i, d in enumerate(devices[:args.chips]):
        stats = d.memory_stats() or {}
        _log(f"device {i} peak_bytes_in_use="
             f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
