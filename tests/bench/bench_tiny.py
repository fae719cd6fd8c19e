"""Helpers for the benchmark's CPU tests: a copy of the benchmark whose
cells are shrunk to a size a test run can hold (same configurations,
drivers, references and limits), and one run of a cell in it with the
harness's look for a chip skipped."""

from __future__ import annotations

import json
import shutil
import sys
from contextlib import contextmanager
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "bench"), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# per traffic: the sizes the CPU tests run at
TINY_TRAFFIC = {
    "sim:device": {"num_envs": 64, "batch_size": 16, "recvs_per_call": 32,
                   "check_calls": 2},
    "sim:device-sharded": {"num_envs": 256, "batch_size": 64,
                           "recvs_per_call": 48, "check_calls": 2},
}
TINY_LIMITS = {"min_transitions": 400, "min_selection_recvs": 5}


def tiny_root(tmp: Path, copy: bool = True) -> Path:
    """A shrunk copy of the benchmark under ``tmp`` (``copy=False``:
    shrink the copy already there)."""
    if copy:
        shutil.copy(ROOT / "BENCHMARK.json", tmp)
        shutil.copytree(ROOT / "bench", tmp / "bench")
    for f in (tmp / "bench" / "workloads").glob("*.json"):
        t = json.loads(f.read_text())
        t.update(TINY_TRAFFIC[f"{t['driver']}:{t['engine']}"])
        t["limits"] = {k: TINY_LIMITS.get(k, v) for k, v in t["limits"].items()}
        f.write_text(json.dumps(t))
    return tmp


@contextmanager
def compile_cache_restored():
    """The harness turns JAX's persistent cache on; a test worker goes
    on to other tests afterwards, so put the settings back."""
    import jax

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()


def run_cell(root: Path, workload: str, seed: int, seconds: float = 1.0,
             trace: int = 0) -> dict:
    """One harness run of ``workload`` on the CPU."""
    import run as harness

    args = harness.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace)])
    with compile_cache_restored():
        return harness.run(args, root=root, require_chip=False)


def control_checks(root: Path, workload: str, seed: int,
                   seconds: float = 1.0) -> list:
    """The comparison's numbers with the control in the program's place."""
    import run as harness

    from bench.lib import catalog

    bdir = root / "bench"
    spec = catalog.spec(catalog.benchmark(root), workload, seed, bdir)
    with compile_cache_restored():
        harness.enable_cache(root / ".bench_cache")
        cell = catalog.driver(spec.traffic["driver"], bdir).setup(spec)
        cell.window(seconds)
        cell.release()
        return cell.checks(control=True)


def passes(checks) -> bool:
    import run as harness

    return harness.passes(checks)
