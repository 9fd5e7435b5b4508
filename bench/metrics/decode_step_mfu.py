"""The whole decode step's share of the chip's bf16 peak: model FLOPs of
the tokens decoded in the traced ticks (``bench/costs.py``) over the decode
step's device time. It bounds every kernel roofline of the decode step: a
kernel taken off the path leaves its roofline silent, not this."""

from bench import costs


def read(run):
    split = run.step_time()
    if not split:
        return None
    decode_ns, _, ticks = split
    decoding = [t for t in ticks if t.decoded]
    cfg = run.spec.model
    flops = sum(costs.token_flops(cfg, n - 1, logits=True)
                for t in decoding for n in t.decoded)
    if not flops:
        return None
    ns = decode_ns * len(decoding)
    return 100.0 * flops / (ns * 1e-9 * run.peak["bf16_flops"])
