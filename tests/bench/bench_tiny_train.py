"""Helpers for the CPU tests of the ``train`` cells: a copy of the
benchmark with the Atari PPO cell shrunk to a size a test run can hold
(same configuration, driver, reference and limits of the comparison;
fewer lanes, shorter rollouts, smaller minibatches, fewer updates)."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

TINY_TRAIN_TRAFFIC = {"num_envs": 8, "batch_size": 8, "warm_updates": 1}
TINY_PPO = {"num_steps": 8, "minibatches": 2}
TINY_LIMITS = {"min_updates": 2}


def tiny_train_root(tmp: Path, traffic: dict | None = None,
                    ppo: dict | None = None) -> Path:
    """A copy of the benchmark under ``tmp`` with every ``train``
    traffic and the configurations it runs shrunk (``traffic`` and
    ``ppo`` replace some of the sizes above)."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp)
    shutil.copytree(ROOT / "bench", tmp / "bench")
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        tf = tmp / "bench" / "workloads" / f"{cell['traffic']}.json"
        t = json.loads(tf.read_text())
        if t["driver"] != "train":
            continue
        t = {**t, **TINY_TRAIN_TRAFFIC, **(traffic or {})}
        t["limits"] = dict(t["limits"], **TINY_LIMITS)
        tf.write_text(json.dumps(t))
        cf = tmp / "bench" / "configs" / f"{cell['config']}.json"
        c = json.loads(cf.read_text())
        c["ppo"].update({**TINY_PPO, **(ppo or {})})
        cf.write_text(json.dumps(c))
    return tmp
