"""Readings of the control: the configuration's plain reference computed in
bfloat16, the next precision below the float32 the configurations state,
put in the program's place. For each seed it generates the cell's inputs
as a run would and prints the numbers the check compares, beside their
limits; no program runs and no chip is needed.

    python bench/control.py --workload poisson2d-400.solve --seconds 20 --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(root, workload, seed, seconds, spec=None) -> dict:
    from bench import harness

    spec = spec or harness.resolve(root, workload)
    run = harness.Run(root, spec, seed, seconds, False, None)
    driver = harness.load_module(root, "drivers", run.traffic["driver"])
    generator = harness.load_module(root, "generators", run.config["generator"])
    run.matrix = generator.generate(run.config["matrix"], run.rng("matrix"))
    driver.generate(run)
    return driver.control(run)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    limits = harness.resolve(ROOT, args.workload)["limits"]
    for seed in args.seeds:
        checks, correct = harness.verdict(readings(ROOT, args.workload, seed, args.seconds),
                                          limits)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": correct,
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
