"""The benchmark's reduction from a profiler trace to busy, idle, kernel
and collective time (``bench/lib/trace.py``), on hand-made events with
known answers and on a small trace recorded on a TPU v5e."""

import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib.trace import (Event, Trace, complement, load,  # noqa: E402
                             merged, overlap_ns, union_ns)

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur, text=""):
    return Event(name, float(start), float(dur), text)


def two_chip_trace():
    # chip 0: ops at [0,10) [5,15) [20,30) and an all-gather [30,40)
    # overlapped by nothing, plus a kernel [40,50); window [0,100)
    chip0 = [ev("fusion.1", 0, 10), ev("fusion.2", 5, 10),
             ev("env_multi_step.3", 20, 10), ev("all-gather.4", 30, 10),
             ev("env_multi_step.5", 40, 10)]
    # chip 1: one op [0,50) and an all-gather [45,55) half hidden by it
    chip1 = [ev("fusion.1", 0, 50), ev("all-gather.4", 45, 10)]
    host = [ev("bench_window", 0, 100), ev("python_loop", 50, 50)]
    mods = {"/device:TPU:0": [ev("jit_train_step", 0, 40)],
            "/device:TPU:1": [ev("jit_train_step", 0, 60)]}
    return Trace(ops={"/device:TPU:0": chip0, "/device:TPU:1": chip1},
                 modules=mods, host=host, window=(0.0, 100.0))


def test_interval_arithmetic():
    a = [ev("x", 0, 10), ev("y", 5, 10), ev("z", 20, 5)]
    assert merged(a) == [(0.0, 15.0), (20.0, 25.0)]
    assert union_ns(a) == 20.0
    assert overlap_ns(a, [ev("w", 10, 12)]) == 5.0 + 2.0
    assert complement(a, (0.0, 30.0)) == [(15.0, 20.0), (25.0, 30.0)]


def test_busy_idle_kernel_collective():
    tr = two_chip_trace()
    # chip 0 busy: [0,15) [20,50) and its program run [0,40) = 50 ns;
    # chip 1: [0,55) and [0,60) = 60 ns
    assert tr.busy_s() == pytest.approx((50 + 60) / 2 * 1e-9)
    assert tr.idle_pct() == pytest.approx(100 * (1 - 55 / 100))
    assert tr.kernel_s("env_multi_step") == pytest.approx(20e-9)
    assert tr.kernel_count("env_multi_step") == 2
    assert tr.collective_s() == pytest.approx(20e-9)
    # chip 0: all 10 ns exposed; chip 1: [50,55) exposed
    assert tr.exposed_collective_s() == pytest.approx(15e-9)
    assert tr.module_s("train_step") == pytest.approx(50e-9)


def test_window_clips_events():
    tr = two_chip_trace()
    tr.window = (10.0, 35.0)
    # in [10,35) both chips run their programs throughout
    assert tr.busy_s() == pytest.approx(25e-9)
    assert tr.kernel_s("env_multi_step") == pytest.approx(10e-9)


def test_breakdown_lists():
    tr = two_chip_trace()
    top = tr.top_ops(3)
    assert top[0][0] == "fusion"
    assert top[0][1] == pytest.approx((10 + 10 + 50) / 2 * 1e-9)
    gaps = tr.idle_gaps(2)
    # chip 0's one gap is [50,100), under the host's python_loop ([15,20)
    # lies inside its program run)
    assert gaps == [["python_loop", pytest.approx(50e-9)]]


def test_empty_trace_reads_nothing():
    tr = Trace(ops={}, modules={}, host=[], window=(0.0, 10.0))
    assert tr.idle_pct() is None
    assert tr.busy_s() == 0.0
    assert tr.idle_gaps() == []


def test_recorded_tpu_trace():
    """Two calls of a 4-step random collect (Ant-v3, 256 lanes, 64 per
    recv) recorded on one TPU v5e chip under the harness's window
    annotation: the loader finds the device operations, the kernel, the
    program and the host spans, and the numbers agree with each other."""
    name = "ant_collect_v5e.xplane.pb"
    tr = load(str(DATA / name))
    assert list(tr.ops) == ["/device:TPU:0"]
    assert tr.kernel_count("env_multi_step") == 2 * 4
    assert 0 < tr.kernel_s("env_multi_step") < tr.module_s("collect")
    assert tr.module_s("collect") <= tr.busy_s() + 1e-12
    assert {n for n, _ in tr.idle_gaps(3)} <= {"collect_call", "no host event"}
    assert all(not n.startswith("while") for n, _ in tr.top_ops(10))
    assert tr.ops and all(v for v in tr.ops.values())
    assert tr.window_s > 0
    busy = tr.busy_s()
    assert 0 < busy <= tr.window_s
    assert 0 <= tr.idle_pct() < 100
    assert tr.kernel_s("") >= busy * len(tr.ops) * (1 - 1e-9)
    assert sum(s for _, s in tr.top_ops(10)) <= tr.kernel_s("") / len(tr.ops) + 1e-12
    assert all(s > 0 for _, s in tr.idle_gaps(10))
    assert os.path.getsize(DATA / name) < 2_000_000
