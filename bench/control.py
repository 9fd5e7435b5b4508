"""Read the numbers that set a cell's correctness limit, on the chip.

    python bench/control.py --workload <cell> --seeds <n> --seconds <s>

For each seed, in one process: the cell's own window at its own load, then
the widest logit gap of the served tokens below the float32 reference's best
(the program's reading) and the widest gap of the tokens that the reference
computed in int8 (the control) would put first at the same positions. One
JSON line per seed.
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def reading(spec, seed, seconds):
    from bench import harness, traffic

    items = traffic.schedule(spec.mix, rate_rps=spec.cell["rate_rps"],
                             seconds=seconds, vocab=spec.model["vocab"],
                             seed=seed)
    model, params = harness.build(spec, seed)
    engine = harness.make_engine(spec, model, params)
    harness.warm_up(engine, sorted({len(it.prompt) for it in items}),
                    spec.model["vocab"])
    results, _, _, _ = harness.serve_window(spec, engine, items, seconds)
    del engine
    gc.collect()
    sample = harness.check_sample(items, results, spec.cell, seed)
    prog = harness.reference_gaps(spec, params, sample)
    out = {"workload": spec.name, "seed": seed,
           "finished": len(results), "attempted": len(items),
           "tokens": int(sum(g.size for g in prog)),
           "program_gap": float(max(g.max() for g in prog))}
    ctl = harness.reference_gaps(spec, params, sample, control="int8")
    out["int8_control_gap"] = float(max(g.max() for g in ctl))
    del params
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench import harness

    harness.accelerator(1)
    harness.use_compile_cache(harness.ROOT)
    spec = harness.load_spec(args.workload)
    for i in range(args.seeds):
        print(json.dumps(reading(spec, args.first_seed + i, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
