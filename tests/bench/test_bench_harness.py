"""The benchmark harness (``bench/run.py``): it refuses to run without a
TPU, every file under ``bench/`` is found by a name ``BENCHMARK.json``
gives, ``BENCHMARK.json`` keeps to its contract, and a new cell with a
new configuration, driver kind and metric runs from new files and
entries alone."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "bench"))

from bench.lib import catalog  # noqa: E402

BENCH = catalog.benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=cpu_env(), capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d)
    cell = BENCH["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable] + BENCH["command"][1:] + [
            "--workload", cell, "--seed", "1", "--seconds", "1",
            "--trace", "0"],
        cwd=tmp_path, env=cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_every_file_is_found_by_a_name_in_the_benchmark():
    bench = ROOT / "bench"
    configs = {c["name"] for c in BENCH["configs"]}
    traffics = {w["traffic"] for w in BENCH["workloads"]}
    metrics = {m["name"] for m in BENCH["per_layer"]}
    on_disk = {p.stem for p in (bench / "configs").glob("*.json")}
    assert on_disk == configs
    assert {p.stem for p in (bench / "configs").glob("*.py")} == configs
    for name in configs:
        cfg = catalog.config(name)
        assert cfg["reduced"] == next(c["reduced"] for c in BENCH["configs"]
                                      if c["name"] == name)
        catalog.reference(name)
    assert {p.stem for p in (bench / "workloads").glob("*.json")} == traffics
    kinds = set()
    for name in traffics:
        kinds.add(catalog.traffic(name)["driver"])
    assert {p.stem for p in (bench / "drivers").glob("*.py")} == kinds
    for kind in kinds:
        assert callable(catalog.driver(kind).setup)
    assert {p.name[:-3] for p in (bench / "metrics").glob("*.py")} == metrics
    for name in metrics:
        assert callable(catalog.metric(name).read)
    peaks = catalog.peaks()
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_keeps_to_its_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    for word in BENCH["command"][1:]:
        assert not word.startswith("/")
        assert (ROOT / word).exists() == any(word.startswith(p + "/")
                                             for p in BENCH["paths"])
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, want in keys.items():
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names))
        for e in BENCH[section]:
            assert set(e) - {"workloads"} == want, e["name"]
            assert NAME.match(e["name"])
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in cells.values()} == configs
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    for w in cells.values():
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for cell in cells:
        reported = [m for m in BENCH["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert catalog.cell_metrics(BENCH, cell, "per_layer")
    assert len(json.dumps(BENCH)) < 64 * 1024


TOY_DRIVER = '''
from bench.lib.catalog import Spec


class Toy:
    def __init__(self, spec):
        import jax.numpy as jnp
        self.spec = spec
        self.x = jnp.arange(spec.config["n"], dtype=jnp.float32) + spec.seed % 7

    def window(self, seconds):
        total = float((self.x * self.x).sum())
        self.total = total
        return {"e2e": {"toy_rate": total}, "attempted": 1, "failed": 0,
                "counts": {"n": self.spec.config["n"]}}

    def program_bytes(self):
        return None

    def release(self):
        self.x = None

    def checks(self, control=False):
        n = self.spec.config["n"]
        want = float(sum((i + self.spec.seed % 7) ** 2 for i in range(n)))
        return [("toy_gap", abs(self.total - want), 0.0)]


def setup(spec: Spec):
    return Toy(spec)
'''


def test_a_new_cell_runs_from_new_files_and_entries(tmp_path):
    import run as harness

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    b = tmp_path / "bench"
    (b / "configs" / "toy.json").write_text(json.dumps(
        {"n": 8, "reduced": [], "source": "https://example.org/toy"}))
    (b / "configs" / "toy.py").write_text('"""Reference of toy."""\n')
    (b / "workloads" / "toy_mix.json").write_text(json.dumps(
        {"driver": "toy", "limits": {}}))
    (b / "drivers" / "toy.py").write_text(TOY_DRIVER)
    (b / "metrics" / "toy_count.py").write_text(
        "def read(trace, counts):\n    return float(counts['n'])\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "https://example.org/toy",
                             "file": "bench/configs/toy.json", "reduced": [],
                             "why": "toy"})
    bench["workloads"].append({"name": "toy.mix", "config": "toy",
                               "traffic": "toy_mix", "chips": 1, "why": "toy"})
    bench["end_to_end"].append({"name": "toy_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock", "workloads": ["toy.mix"]})
    bench["per_layer"].append({"name": "toy_count", "unit": "1", "better": "higher",
                               "source": "program_counter", "layer": "toy",
                               "moves": "toy_rate", "workloads": ["toy.mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    for trace in (0, 1):
        args = harness.parse(["--workload", "toy.mix", "--seed", "2147483705",
                              "--seconds", "0.1", "--trace", str(trace)])
        res = harness.run(args, root=tmp_path, require_chip=False)
        assert res["correct"] is True
        assert list(res)[-1] == "checks"
        assert res["checks"] == {"toy_gap": {"value": 0.0, "limit": 0.0}}
        if trace:
            assert res["metrics"] == {"toy_count": {"value": 8.0, "unit": "1"}}
            assert {"busy_s", "window_s"} <= set(res["device"])
        else:
            assert set(res["metrics"]) == {"toy_rate", "setup_s"}
            assert res["metrics"]["toy_rate"]["value"] == pytest.approx(
                sum((i + 2147483705 % 7) ** 2 for i in range(8)))
