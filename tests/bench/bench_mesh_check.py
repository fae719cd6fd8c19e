"""Runs in a process of its own with four virtual CPU devices: a
four-chip simulation cell added from data alone (the skewed Ant traffic
``data/skew_hier.json`` under the hierarchical schedule, with its entry
in a copy of ``BENCHMARK.json``), at a test's size: sound, with the
control in the program's place, and with the exchange between chips
left out (the hierarchical scheduler's all-gather of candidate costs
skipped, each shard admitting by its own costs).  Prints one JSON line
of outcomes."""

import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"

import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from bench_tiny import (control_checks, passes, run_cell,  # noqa: E402
                        tiny_root)

CELL = "antskew_hier_4chip"
SEED = 2147483731


def add_cell(root: Path) -> None:
    """The four-chip cell, as new files and entries only."""
    shutil.copy(HERE / "data" / "skew_hier.json", root / "bench" / "workloads")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": CELL, "config": "ant_lite",
                               "traffic": "skew_hier", "chips": 4,
                               "why": "skewed Ant over 4 shards"})
    for m in bench["end_to_end"]:
        if m["name"] == "sim_fps":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def local_tau(self, ss, m):
    from jax import lax
    import jax.numpy as jnp

    from repro.core.scheduler import _BIG, HAS_ACTION

    eff = jnp.where(ss.phase == HAS_ACTION, ss.cost.astype(jnp.float32), _BIG)
    neg, _ = lax.top_k(-eff, m)
    return -neg[-1]


def main() -> None:
    from repro.core.scheduler import HierarchicalScheduler

    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        root = Path(tmp)
        shutil.copy(HERE.parents[1] / "BENCHMARK.json", root)
        shutil.copytree(HERE.parents[1] / "bench", root / "bench")
        add_cell(root)
        tiny_root(root, copy=False)
        res = run_cell(root, CELL, SEED, seconds=1.0)
        out["sound"] = res["correct"]
        out["sound_checks"] = res["checks"]
        out["control"] = passes(control_checks(root, CELL, SEED + 1))
        HierarchicalScheduler._tau = local_tau
        res = run_cell(root, CELL, SEED + 2, seconds=1.0)
        out["no_exchange"] = res["correct"]
        out["no_exchange_checks"] = res["checks"]
    print(json.dumps(out))


if __name__ == "__main__":
    main()
