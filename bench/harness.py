"""Drive one cell: generate inputs from the seed, set up, warm up, measure
for the given seconds, optionally trace, check the outputs against the
plain reference, and print the result line.

Everything particular to a configuration, a traffic mix or a per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json`` (the cell's ``config`` entry names the
  file): the deployment's sizes and solver settings, the generator and the
  plain reference it is checked against (``bench/generators/<name>.py``,
  ``bench/references/<name>.py``);
* ``bench/traffic/<traffic>.json``: the mix's parameters and the driver
  that runs it (``bench/drivers/<driver>.py``);
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
* ``bench/limits/<workload>.json``: the limit of each number compared.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Optional

import numpy as np

SETUP_METRIC = "setup_s"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold ``.`` and ``-``)."""
    key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    mod = sys.modules.get(key)
    if mod is None:
        path = os.path.join(root, "bench", kind, f"{name}.py")
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None or not os.path.exists(path):
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


def resolve(root: str, workload: str) -> dict:
    """Everything a cell names, loaded by name: its entry, configuration,
    traffic mix, limits and the metric entries that apply to it."""
    bench = benchmark(root)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": cell,
        "config": load_json(os.path.join(root, config["file"])),
        "traffic": load_json(os.path.join(root, "bench", "traffic", f"{cell['traffic']}.json")),
        "limits": load_json(os.path.join(root, "bench", "limits", f"{workload}.json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def verdict(numbers: dict, limits: dict):
    """Each number compared beside its limit, and whether all are within:
    what decides ``correct`` for a run and for the control alike."""
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


class CompileClock:
    """Seconds and count of XLA backend compiles, from ``jax.monitoring``
    (one listener per process: JAX keeps listeners for good)."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    seconds = 0.0
    count = 0
    _installed = False

    @classmethod
    def install(cls):
        if not cls._installed:
            import jax.monitoring

            def on(name, secs, **_):
                if name == cls.EVENT:
                    cls.seconds += secs
                    cls.count += 1

            jax.monitoring.register_event_duration_secs_listener(on)
            cls._installed = True


class Run:
    """What a driver and a metric reader see of one run."""

    def __init__(self, root, spec, seed, seconds, trace, peaks):
        self.root = root
        self.config = spec["config"]
        self.traffic = spec["traffic"]
        self.limits = spec["limits"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = None          # bench.trace.Trace of the traced run
        self.tracing = bool(trace)
        self.peaks = peaks
        self.counters: dict = {"plan_s": 0.0}
        self.work: dict = {}
        self.matrix: dict = {}
        self.state: dict = {}      # the driver's own

    def rng(self, stream: str) -> np.random.Generator:
        """A generator for one named stream of draws from the run's seed, so
        adding a stream never shifts another."""
        words = [self.seed & 0xFFFFFFFF, self.seed >> 32] + [ord(c) for c in stream]
        return np.random.default_rng(words)

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def plan(self):
        """Host seconds of set-up calls that plan and factor, less the XLA
        compile seconds spent inside them."""
        c0, t0 = CompileClock.seconds, time.perf_counter()
        with self.span("plan"):
            yield
        self.counters["plan_s"] += time.perf_counter() - t0 - (CompileClock.seconds - c0)


def execute(root: str, workload: str, seed: int, seconds: float, trace: bool,
            t_start: float, require_chip: bool = True, spec: Optional[dict] = None,
            log=print) -> dict:
    """One run of a cell; returns the result object. ``spec`` replaces what
    ``resolve`` would load (tests run the cells at small sizes through it);
    ``require_chip=False`` skips the look for an accelerator."""
    import jax

    spec = spec or resolve(root, workload)
    devs = jax.devices()
    dev = devs[0]
    peaks = None
    if require_chip:
        if dev.platform == "cpu" or len(devs) < spec["cell"]["chips"]:
            raise NoChip(f"{workload} needs {spec['cell']['chips']} accelerator chip(s); "
                         f"JAX found {len(devs)} x {dev.platform}")
        from bench import peaks as peaks_table

        peaks = peaks_table.lookup(dev.device_kind)
        from repro.core.api import enable_jit_cache

        enable_jit_cache()
    CompileClock.install()
    c_start = CompileClock.seconds
    run = Run(root, spec, seed, seconds, trace, peaks)
    driver = load_module(root, "drivers", run.traffic["driver"])
    generator = load_module(root, "generators", run.config["generator"])

    t_gen = time.perf_counter()
    with run.span("generate"):
        run.matrix = generator.generate(run.config["matrix"], run.rng("matrix"))
        driver.generate(run)
    gen_s = time.perf_counter() - t_gen
    log(f"generate: {gen_s:.3f} s, n={run.matrix['n']} nnz={len(run.matrix['data'])} "
        "(not in setup_s)", flush=True)

    driver.setup(run)
    setup_s = time.perf_counter() - t_start - gen_s
    run.counters["compile_s"] = CompileClock.seconds - c_start
    log(f"setup: {setup_s:.3f} s, compile {run.counters['compile_s']:.3f} s, "
        f"plan {run.counters['plan_s']:.3f} s", flush=True)

    logdir = None
    if trace:
        logdir = os.path.join(root, ".bench_trace", f"{workload}-{os.getpid()}")
        shutil.rmtree(logdir, ignore_errors=True)
        jax.profiler.start_trace(logdir)
    c_window = CompileClock.count
    with run.span("window"):
        e2e = driver.window(run)
    compiles_in_window = CompileClock.count - c_window
    if trace:
        driver.probes(run)
        jax.profiler.stop_trace()
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs[:spec["cell"]["chips"]])
    if trace:
        from bench import trace as tr

        run.trace = tr.load(tr.find(logdir))
        shutil.rmtree(logdir, ignore_errors=True)
        driver.count_work(run)

    t_check = time.perf_counter()
    with run.span("check"):
        numbers, attempted, failed = driver.check_outputs(run)
    log(f"check: {time.perf_counter() - t_check:.3f} s", flush=True)
    log(f"window: {compiles_in_window} XLA compiles inside it; seconds of each solve or "
        f"step: {run.counters.get('each_s')}", flush=True)
    checks, correct = verdict(numbers, run.limits)

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed)}
    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            value = load_module(root, "metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        w = run.trace.span("window")
        busy = run.trace.busy_seconds(w.start, w.end) if w else 0.0
        device.update(busy_s=busy, window_s=(w.end - w.start) if w else 0.0)
        result.update(metrics=metrics, device=device)
        if w is not None:
            result["breakdown"] = {"device_ops": run.trace.top_ops(w.start, w.end),
                                   "idle_gaps": run.trace.idle_by_span(w.start, w.end)}
    else:
        e2e[SETUP_METRIC] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                   for m in spec["end_to_end"]}
        result.update(metrics=metrics, device=device)
    result["checks"] = checks
    return result


def main(workload: str, seed: int, seconds: float, trace: bool, root: str,
         t_start: float) -> int:
    try:
        result = execute(root, workload, seed, seconds, trace, t_start)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
