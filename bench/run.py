"""Run one benchmark cell on the accelerator this process finds.

    python bench/run.py --workload poisson2d-400.solve --seed 7 --seconds 20 --trace 0

The cell is looked up by name in ``BENCHMARK.json`` at the checkout's root;
its configuration, traffic mix, driver and per-layer metrics are files
under ``bench/`` found by the names given there. The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``; with ``--trace 1`` also ``breakdown``; last of all
``checks``, each number compared with its limit). Exits non-zero with no
result where no accelerator, or fewer chips than the cell asks for, is
found.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compilation cache sits at a fixed path inside the checkout, so
    # every later run of a cell here loads what the first one compiled
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    return harness.main(args.workload, args.seed, args.seconds, bool(args.trace),
                        root=ROOT, t_start=_T0)


if __name__ == "__main__":
    sys.exit(main())
