"""Model FLOPs of the tokens processed in the window (prompt tokens
prefilled and output tokens decoded there, from ``bench/costs.py``) over the
window's length times the chip's bf16 peak."""

from bench import costs


def read(run):
    cfg = run.spec.model
    flops = 0.0
    for t in run.ticks:
        if t.end > run.seconds:
            continue
        flops += sum(costs.prefill_flops(cfg, p) for p in t.admitted)
        flops += sum(costs.token_flops(cfg, n - 1, logits=True)
                     for n in t.decoded)
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.peak["bf16_flops"])
