"""The harness finds everything by name, BENCHMARK.json keeps to the
contract's shapes, each traffic mix runs end to end on the CPU at a small
size, and the comparison refuses a factor one ulp off."""
import hashlib
import json
import os
import re
import shutil

import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_every_cell_resolves_by_name():
    bench = harness.benchmark(tiny.ROOT)
    for cell in bench["workloads"]:
        spec = harness.resolve(tiny.ROOT, cell["name"])
        for kind, name in (("drivers", spec["traffic"]["driver"]),
                           ("generators", spec["config"]["generator"]),
                           ("references", spec["config"]["reference"])):
            harness.load_module(tiny.ROOT, kind, name)
        for m in spec["per_layer"]:
            assert callable(harness.load_module(tiny.ROOT, "metrics", m["name"]).read)
        assert set(spec["limits"]) == {"factor_bits_differ", "residual_over_tol", "missing"}
        assert spec["end_to_end"] and spec["per_layer"]


def test_benchmark_json_names_units_and_shapes():
    bench = harness.benchmark(tiny.ROOT)
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert not any(w.startswith("/") or ".." in w for w in bench["command"])
    cfg_names = [c["name"] for c in bench["configs"]]
    cells = [c["name"] for c in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"]) and LINE.match(c["why"])
        assert c["file"].startswith(bench["paths"][0] + "/") and os.path.exists(
            os.path.join(tiny.ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and LINE.match(w["why"])
        assert w["config"] in cfg_names and w["chips"] in (1, 4)
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(cells)
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and set(m["workloads"]) <= set(cells)
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved) and LINE.match(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower",
                                                                                   "higher")
    names = cfg_names + cells + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names)
    for cell in cells:  # every cell reports setup_s, another e2e and a per-layer metric
        spec = harness.resolve(tiny.ROOT, cell)
        assert len(spec["end_to_end"]) >= 2 and spec["per_layer"]


@pytest.mark.parametrize("workload", tiny.workloads())
def test_each_mix_runs_on_the_cpu(workload):
    r = tiny.run(workload)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    spec = tiny.spec(workload)
    assert set(r["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert list(r)[-1] == "checks"


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha1(fh.read()).hexdigest()
    return out


def test_a_cell_added_as_new_files_is_found(tmp_path):
    """A new configuration, traffic mix, limits and per-layer metric, added
    as new files and new entries only, run without an edit to any file
    that was there."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(tiny.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = _digest(root / "bench")
    cfg = json.loads((root / "bench/configs/poisson2d-400.json").read_text())
    cfg.update(name="poisson2d-20", matrix={"nx": 20})
    (root / "bench/configs/poisson2d-20.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/solve-short.json").write_text(json.dumps(
        {"driver": "solve_stream", "rhs_ring": 3, "probe_calls": 2}))
    (root / "bench/limits/poisson2d-20.solve-short.json").write_text(
        (root / "bench/limits/poisson2d-400.solve.json").read_text())
    (root / "bench/metrics/solves.total.py").write_text(
        "def read(run):\n    return len(run.counters.get('iterations', [])) or None\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "poisson2d-20", "source": "test", "why": "test",
                             "file": "bench/configs/poisson2d-20.json", "reduced": []})
    bench["workloads"].append({"name": "poisson2d-20.solve-short", "config": "poisson2d-20",
                               "traffic": "solve-short", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "solve_s":
            m["workloads"].append("poisson2d-20.solve-short")
    bench["per_layer"].append({"name": "solves.total", "unit": "solves", "better": "higher",
                               "source": "program_counter", "layer": "Krylov loop",
                               "moves": "solve_s", "workloads": ["poisson2d-20.solve-short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digest(root / "bench")
    assert all(after[k] == v for k, v in before.items())
    r = tiny.run("poisson2d-20.solve-short", seconds=0.5, trace=True, root=str(root),
                 s=harness.resolve(str(root), "poisson2d-20.solve-short"))
    assert r["correct"] and r["metrics"]["solves.total"]["value"] >= 1


def test_a_factor_one_ulp_off_is_refused(monkeypatch):
    """The timed path's factor moved by one unit in the last place in one
    entry: the solves still converge, and the comparison refuses it."""
    import repro.core.api as api

    real = api.ilu

    def ilu_one_ulp_off(*a, **k):
        fact = real(*a, **k)
        v = fact.vals.view(np.int32)
        v[len(v) // 2] += 1
        return fact

    monkeypatch.setattr(api, "ilu", ilu_one_ulp_off)
    r = tiny.run("poisson2d-400.solve", seconds=0.5)
    assert r["checks"]["factor_bits_differ"]["value"] == 1
    assert not r["correct"]


def test_no_accelerator_no_result():
    """Here JAX finds only the CPU: the command exits non-zero and prints no
    result line."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(tiny.ROOT, "bench", "run.py"),
                        "--workload", "poisson2d-400.solve", "--seed", str(tiny.SEED),
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0 and '"correct"' not in p.stdout
