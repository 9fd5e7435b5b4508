"""The plain references against the served engine, at smoke widths on the
CPU: prefill at the served prompt lengths, then decode through the cache
(paged for zamba2, dense slots for mamba2), on logits."""

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, traffic
from bench.tests import smoke

#: prefill in float32 compute: the chunked SSD scan against the step-by-step
#: recurrence and flash against plain softmax differ only in the order of
#: float32 sums (measured 4e-7 to 7e-7 of the largest logit)
F32_PREFILL_TOL = 1e-5
#: decode in float32 compute: the engine keeps each layer's convolution
#: history in bfloat16 whatever the compute dtype, so every decoded token
#: reads three inputs rounded to 8 mantissa bits (measured 7e-3)
F32_DECODE_TOL = 0.03
#: bfloat16 compute, as served: every activation is rounded to 8 mantissa
#: bits, layer after layer
BF16_TOL = 0.08


@pytest.mark.parametrize("workload", ["zamba2-chat", "mamba2-chat"])
def test_reference_matches_prefill_in_float32(workload):
    spec = smoke.spec(workload, compute_dtype="float32")
    model, params = harness.build(spec, 5)
    ref = harness.reference_module(spec)
    for n in spec.mix["prompt"]["ladder"]:
        toks = np.random.default_rng(n).integers(
            0, spec.model["vocab"], (1, n)).astype(np.int32)
        got, _ = model.prefill(params, {"tokens": toks},
                               max_len=spec.cell["max_len"])
        h = ref.hidden(params, jnp.asarray(toks), spec.model)
        want = np.asarray(h[0, -1] @ ref.unembedding(params).T)
        dev = np.max(np.abs(np.asarray(got)[0, -1] - want))
        assert dev / np.max(np.abs(want)) < F32_PREFILL_TOL


@pytest.mark.parametrize("workload", ["zamba2-chat", "mamba2-chat"])
@pytest.mark.parametrize("compute,tol", [("float32", F32_DECODE_TOL),
                                         ("bfloat16", BF16_TOL)])
def test_reference_matches_cached_decode(workload, compute, tol):
    spec = smoke.spec(workload, compute_dtype=compute)
    seed = 2 ** 33 + 11
    model, params = harness.build(spec, seed)
    engine = harness.make_engine(spec, model, params)
    items = traffic.schedule(spec.mix, rate_rps=12.0, seconds=1.0,
                             vocab=spec.model["vocab"], seed=seed)[:5]
    from repro.serve.request import Request

    ticks = []
    engine.start_run(on_logits=lambda lg: ticks.append(np.asarray(lg)))
    results = []
    for it in items:
        engine.submit(Request(uid=it.uid, prompt=tuple(it.prompt.tolist()),
                              max_new_tokens=it.max_new_tokens))
    # slot -> uid of every request decoded in each decode tick
    owners = []
    while not engine.scheduler.done:
        n, n_res = len(ticks), len(results)
        engine.tick(results)
        if len(ticks) > n:
            own = {s: r.uid for s, r in engine.scheduler.active.items()}
            own.update({r.slot: r.uid for r in results[n_res:]})
            owners.append(own)
    ref = harness.reference_module(spec)
    done = {r.uid: r for r in results}
    worst = 0.0
    for it in items:
        toks = done[it.uid].tokens
        seq = np.concatenate([it.prompt, toks[:-1]])[None]
        h = ref.hidden(params, jnp.asarray(seq), spec.model)
        want = np.asarray(h @ ref.unembedding(params).T)[0]
        k = 0
        for t, own in zip(ticks, owners):
            slot = [s for s, u in own.items() if u == it.uid]
            if not slot:
                continue
            k += 1
            row = it.prompt.size - 1 + k
            w = want[row]
            worst = max(worst, float(np.max(np.abs(t[slot[0]] - w))
                                     / np.max(np.abs(w))))
        assert k == toks.size - 1
        assert np.argmax(want[it.prompt.size - 1]) == toks[0] \
            or compute == "bfloat16"
    print(f"{workload} {compute}: worst {worst:.3e}")
    assert worst < tol, worst
