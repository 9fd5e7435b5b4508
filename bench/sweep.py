"""Find a cell's knee on the chip: the highest offered rate at which the
90th percentile of TTFT stays under a limit and the backlog does not grow.

    python bench/sweep.py --workload <cell> --rates 2 3 4 --seconds 30
        [--ttft-limit-ms 1000]

One process; for each rate, the cell's traffic at that mean rate for one
window. Prints one JSON line per rate: the end-to-end metrics, the mean
number of requests waiting for a slot in each half of the window, and
whether the rate is sustained. The cell's rate is then set by hand to about
four fifths of the knee in ``bench/cells/<cell>.json``.
"""

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def one_rate(spec, rate, seconds, seed, limit_ms):
    from bench import harness, traffic

    spec = dataclasses.replace(spec, cell=dict(spec.cell, rate_rps=rate))
    items = traffic.schedule(spec.mix, rate_rps=rate, seconds=seconds,
                             vocab=spec.model["vocab"], seed=seed)
    model, params = harness.build(spec, seed)
    engine = harness.make_engine(spec, model, params)
    harness.warm_up(engine, sorted({len(it.prompt) for it in items}),
                    spec.model["vocab"])
    results, times, ticks, _ = harness.serve_window(spec, engine, items,
                                                    seconds)
    e2e, _, _ = harness.end_to_end(items, results, times, seconds, 0.0)
    # requests due but not yet admitted, at the end of every tick
    admitted_at = sorted(r.metrics.admitted_s for r in results)
    due = sorted(it.due_s for it in items)
    halves = []
    for lo, hi in ((0.0, seconds / 2), (seconds / 2, seconds)):
        samples = [t.end for t in ticks if lo <= t.end < hi]
        waits = [sum(1 for d in due if d <= s)
                 - sum(1 for a in admitted_at if a <= s) for s in samples]
        halves.append(sum(waits) / max(len(waits), 1))
    sustained = e2e["ttft_p90_ms"] < limit_ms and \
        halves[1] <= 1.5 * halves[0] + 1.0 and len(results) == len(items)
    del engine, params
    gc.collect()
    return dict(rate_rps=rate, **e2e, waiting_first_half=halves[0],
                waiting_second_half=halves[1], finished=len(results),
                attempted=len(items), sustained=sustained)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 77)
    ap.add_argument("--ttft-limit-ms", type=float, default=1000.0)
    args = ap.parse_args(argv)

    from bench import harness

    harness.accelerator(1)
    harness.use_compile_cache(harness.ROOT)
    spec = harness.load_spec(args.workload)
    for rate in args.rates:
        t0 = time.monotonic()
        out = one_rate(spec, rate, args.seconds, args.seed,
                       args.ttft_limit_ms)
        out["wall_s"] = time.monotonic() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
