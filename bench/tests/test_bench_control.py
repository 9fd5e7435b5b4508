"""The control of the correctness check, at 256 wide on the CPU: the
reference computed in int8 (W8A8 projections and logits) in the program's
place reads a logit gap above the small cell's limit on the same prompts and
served tokens, where the bf16 program reads one below it. On the chip, at the
cells' own sizes, ``bench/control.py`` reads the same numbers."""

import gc

import numpy as np
import pytest

from bench import harness, traffic
from bench.tests import smoke


@pytest.mark.parametrize("workload", ["zamba2-chat", "mamba2-chat"])
def test_int8_control_fails_the_check(workload):
    spec = smoke.spec(workload, **smoke.WIDER[workload])
    seed = smoke.SEED
    items = traffic.schedule(spec.mix, rate_rps=spec.cell["rate_rps"],
                             seconds=smoke.SECONDS,
                             vocab=spec.model["vocab"], seed=seed)
    model, params = harness.build(spec, seed)
    engine = harness.make_engine(spec, model, params)
    harness.warm_up(engine, sorted({len(it.prompt) for it in items}),
                    spec.model["vocab"])
    results, _, _, _ = harness.serve_window(spec, engine, items,
                                            smoke.SECONDS)
    del engine
    gc.collect()
    sample = harness.check_sample(items, results, spec.cell, seed)
    limit = spec.cell["check"]["max_logit_gap"]
    prog = max(float(np.max(g)) for g in
               harness.reference_gaps(spec, params, sample))
    ctl = max(float(np.max(g)) for g in harness.reference_gaps(
        spec, params, sample, control="int8"))
    print(f"{workload}: program {prog:.4f}, int8 control {ctl:.4f}, "
          f"limit {limit}")
    assert prog <= limit < ctl
