"""Run one benchmark cell and print its result as the last line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

The cell, its configuration, traffic mix and metrics are read from
``BENCHMARK.json`` and the files it names under ``bench/``. Exits 2 without a
result when JAX finds no accelerator or fewer chips than the cell needs.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
