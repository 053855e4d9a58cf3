"""The cells of BENCHMARK.json cut to a size the CPU runs in seconds: the
same files, drivers and checks, smaller matrices and windows."""
import copy
import os
import time

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2**33 + 17  # larger than 32 bits, as the runs' seeds are


def workloads(root=ROOT):
    return [c["name"] for c in harness.benchmark(root)["workloads"]]


def spec(workload, root=ROOT):
    s = copy.deepcopy(harness.resolve(root, workload))
    m = s["config"]["matrix"]
    if "nx" in m:
        m["nx"] = 16
    if "n" in m:
        m["n"] = 400
    return s


def run(workload, seconds=1.0, trace=False, root=ROOT, s=None, seed=SEED):
    return harness.execute(root, workload, seed, seconds, trace, time.perf_counter(),
                           require_chip=False, spec=s or spec(workload, root),
                           log=lambda *a, **k: None)
