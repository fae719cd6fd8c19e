"""Finds every piece of the benchmark by the name ``BENCHMARK.json`` gives
it.  Nothing here lists a cell, a configuration, a driver or a metric:
adding one is adding its files and its entries.

  * configuration ``<c>``: ``bench/configs/<c>.json``, with its plain
    reference beside it in ``bench/configs/<c>.py``;
  * traffic ``<t>``: ``bench/workloads/<t>.json``, whose ``driver`` key
    names the driver kind;
  * driver kind ``<k>``: ``bench/drivers/<k>.py``;
  * per-layer metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read``
    takes the reduced trace and the run's counts;
  * the chips' peaks: ``bench/peaks.json``, keyed by ``device_kind``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

BENCH = Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class Spec:
    """What a driver is given: the cell, its configuration and traffic,
    the seed, and the configuration's plain reference."""

    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    seed: int
    reference: ModuleType


def read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path) -> dict:
    return read_json(root / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    known = ", ".join(w["name"] for w in bench["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")


def config(name: str, bench_dir: Path = BENCH) -> dict:
    return read_json(bench_dir / "configs" / f"{name}.json")


def traffic(name: str, bench_dir: Path = BENCH) -> dict:
    return read_json(bench_dir / "workloads" / f"{name}.json")


def peaks(bench_dir: Path = BENCH) -> dict:
    return read_json(bench_dir / "peaks.json")


def _module(path: Path, qualname: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(qualname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[qualname] = mod
    spec.loader.exec_module(mod)
    return mod


def reference(config_name: str, bench_dir: Path = BENCH) -> ModuleType:
    return _module(bench_dir / "configs" / f"{config_name}.py",
                   f"bench_reference_{config_name}")


def driver(kind: str, bench_dir: Path = BENCH) -> ModuleType:
    return _module(bench_dir / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def metric(name: str, bench_dir: Path = BENCH) -> ModuleType:
    return _module(bench_dir / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_"))


def cell_metrics(bench: dict, cell_name: str, section: str) -> list[dict]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    this cell reports: those that list it under ``workloads``; without
    the key, an end-to-end metric is everyone's and a per-layer metric
    belongs to every cell that reports the metric it moves."""
    def listed(m: dict) -> bool | None:
        return cell_name in m["workloads"] if "workloads" in m else None

    e2e = [m["name"] for m in bench["end_to_end"] if listed(m) is not False]
    if section == "end_to_end":
        return [m for m in bench[section] if listed(m) is not False]
    return [m for m in bench[section]
            if listed(m) or (listed(m) is None and m["moves"] in e2e)]


def spec(bench: dict, workload: str, seed: int, bench_dir: Path = BENCH) -> Spec:
    """The ``Spec`` of one run of ``workload``."""
    entry = cell(bench, workload)
    return Spec(name=entry["name"], config_name=entry["config"],
                config=config(entry["config"], bench_dir),
                traffic_name=entry["traffic"],
                traffic=traffic(entry["traffic"], bench_dir),
                chips=entry["chips"], seed=seed,
                reference=reference(entry["config"], bench_dir))
