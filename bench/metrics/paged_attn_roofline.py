"""The paged-attention kernel's share of its roofline: the least time the
chip needs for the decode ticks' attention work (the live bf16 K/V pages,
one query and output row per live slot, ``QK^T`` and ``PV``, in every
application of the shared block), over the device time of the
paged-attention kernel (``_paged_kernel``, launched as ``paged_attention``)
in those ticks."""

from bench import costs
from bench import trace as tr

KERNEL = "paged_attention"


def read(run):
    ticks = run.device_events("ops")
    if not ticks:
        return None
    kernel = set(tr.kernel_events(run.trace, run.device, KERNEL))
    cfg = run.spec.model
    apps = costs.n_shared_applications(cfg)
    bs = run.spec.cell.get("block_size")
    least = ns = 0.0
    for t, evs in ticks:
        k = [e for e in evs if e in kernel]
        if not k or not t.decoded:
            continue
        f, b = costs.paged_attention_call(cfg, t.decoded, bs)
        least += apps * costs.least_time_s(f, b, run.peak)
        ns += sum(e.dur for e in k)
    if not ns or not apps:
        return None
    return 100.0 * least / (ns * 1e-9)
