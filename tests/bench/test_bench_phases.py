"""Device time by the program's phases (``bench/lib/phases.py``) and the
phase readers of ``bench/metrics/``, on hand-made events with known
answers and on two small traces recorded on a TPU v5e: one of a program
that names no phase, and one of a program whose phases carry
``jax.named_scope`` names."""

import os
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import catalog, phases  # noqa: E402
from bench.lib import trace as tracelib  # noqa: E402
from bench.lib.phases import SCOPES, op_paths  # noqa: E402
from bench.lib.trace import Event, Trace, complement  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
UNSCOPED = "ant_collect_v5e.xplane.pb"
SCOPED = "ant_collect_scoped_v5e.xplane.pb"
PHASE_METRICS = ("select_us_per_recv.sim", "gather_scatter_us_per_recv.sim",
                 "env_wrap_us_per_recv.sim", "telemetry_us_per_recv.sim",
                 "unscoped_pct.sim")
OLD_METRICS = ("device_idle_pct.sim", "env_step_roofline.sim",
               "nonkernel_us_per_recv.sim")


def counts(recvs=8, lanes=64):
    """The counts a run of the recorded traces' 4-step collect gives
    the readers: ``recvs`` recvs of ``lanes`` lanes, 6 substeps each."""
    return {"recvs": recvs, "stepped": recvs * lanes,
            "substeps": recvs * lanes * 6,
            "peaks": catalog.peaks()["TPU v5 lite"]}


def readings(tr):
    """What the benchmark read from a trace before it read phases."""
    return ([catalog.metric(m).read(tr, counts()) for m in OLD_METRICS],
            tr.top_ops(10), tr.idle_gaps(10), tr.window)


@pytest.mark.parametrize("name", [UNSCOPED, SCOPED])
def test_wire_reader_matches_protobuf(name):
    """``op_paths`` gives each operation the name and ``tf_op`` path that
    tensorflow's own XSpace parser reads; on the unscoped trace the
    paths split its time as measured when the trace was recorded:
    89.1 us under ``jit(env_multi_step)``, 14.1 us with no path."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    data = (DATA / name).read_bytes()
    xs = xplane_pb2.XSpace()
    xs.ParseFromString(data)
    paths = op_paths(data)
    split = {"kernel": 0.0, "none": 0.0}
    for plane in xs.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        stat = {k: m.name for k, m in plane.stat_metadata.items()}
        events = [e for line in plane.lines if line.name == "XLA Ops"
                  for e in line.events]
        got = paths[plane.name]
        assert len(got) == len(events) > 0
        for e, (text, path) in zip(events, got):
            md = plane.event_metadata[e.metadata_id]
            tf_op = [s.str_value for s in md.stats
                     if stat.get(s.metadata_id) == "tf_op"]
            assert text == md.name
            assert path == (tf_op[0].rpartition(":")[0] if tf_op else "")
            if md.name.split(" = ")[0].lstrip("%").split(".")[0] in (
                    "while", "conditional", "call"):
                continue
            if "jit(env_multi_step)" in path:
                split["kernel"] += e.duration_ps * 1e-6
            elif not path:
                split["none"] += e.duration_ps * 1e-6
    if name == UNSCOPED:
        assert split["kernel"] == pytest.approx(89.1, abs=0.1)
        assert split["none"] == pytest.approx(14.1, abs=0.1)


@pytest.mark.parametrize("name", [UNSCOPED, SCOPED])
def test_loader_keeps_the_existing_readings(name):
    """Reading the phases changes no number the benchmark read before:
    the three existing readers, ``top_ops`` and ``idle_gaps`` read the
    same from a trace with its paths attached as from a plain one."""
    plain = tracelib.load(str(DATA / name))
    want = readings(plain)
    assert all(v is not None for v in want[0])
    tr = phases.load(str(DATA / name))
    assert [[tuple(e) for e in v] for v in tr.ops.values()] == \
        [[tuple(e) for e in v] for v in plain.ops.values()]
    for m in PHASE_METRICS:
        catalog.metric(m).read(tr, counts())
    assert readings(tr) == want


def scoped_trace():
    """Two chips running the same phases; times in ns, window [0, 200k).
    Returns the trace with each operation's path attached."""
    body = "jit(collect)/collect/while/body/"
    chip = [("fusion.1", 0, 10e3, body + "pool.select/top_k"),
            ("sort.2", 10e3, 5e3, body + "pool.select/sort"),
            ("fusion.3", 15e3, 20e3, body + "pool.gather/gather"),
            ("fusion.4", 35e3, 30e3, body + "pool.writeback/scatter"),
            ("fusion.5", 65e3, 7e3, body + "pool.send/scatter"),
            ("env_multi_step.6", 72e3, 40e3,
             body + "pool.env_step/jit(env_multi_step)/pallas_call"),
            ("fusion.7", 112e3, 6e3, body + "pool.env_step/select_n"),
            ("fusion.8", 118e3, 4e3,
             body + "pool.env_step/pool.telemetry/add"),
            ("copy.9", 122e3, 8e3, ""),
            ("fusion.10", 130e3, 5e3, body + "collect.policy/threefry2x32"),
            ("all-gather.11", 135e3, 5e3, body + "pool.select/all-gather"),
            ("while.12", 0, 140e3, "jit(collect)/collect/while")]
    events = [Event(n, float(s), float(d)) for n, s, d, _ in chip]
    tr = Trace(ops={"/device:TPU:0": events, "/device:TPU:1": list(events)},
               modules={}, host=[], window=(0.0, 200e3))
    path = [p for *_, p in chip]
    phases.attach(tr, {"/device:TPU:0": path, "/device:TPU:1": list(path)})
    return tr


@pytest.mark.parametrize("metric,want", [
    ("select_us_per_recv.sim", 15 / 2),
    ("gather_scatter_us_per_recv.sim", (20 + 30 + 7) / 2),
    ("env_wrap_us_per_recv.sim", 6 / 2),
    ("telemetry_us_per_recv.sim", 4 / 2),
    ("unscoped_pct.sim", 100 * 8 / 140),
])
def test_phase_readers_on_known_events(metric, want):
    """Each phase reader on two chips and 2 recvs: an operation counts
    under its innermost phase, loops and collectives under none, and
    the ``env_step`` kernel is left out of the batched env's wrapper."""
    tr = scoped_trace()
    assert catalog.metric(metric).read(tr, {"recvs": 2}) == \
        pytest.approx(want, rel=1e-12)
    if metric.endswith("_per_recv.sim"):
        assert catalog.metric(metric).read(tr, {"recvs": 0}) is None


def test_phases_partition_the_operation_time():
    """Every operation that is not a loop counts once: under one phase,
    under no phase, or as a collective."""
    for tr in (scoped_trace(), phases.load(str(DATA / SCOPED))):
        ph = phases.of(tr)
        parts = sum(ph.scope_s(p) for p in SCOPES) + ph.scope_s("")
        assert parts + tr.collective_s() == pytest.approx(ph.op_s(),
                                                          rel=1e-12)
    tr = scoped_trace()
    ph = phases.of(tr)
    assert ph.scope_s("pool.env_step") == pytest.approx(2 * 46e-6)
    assert ph.scope_s("pool.env_step", without=("env_multi_step",)) == \
        pytest.approx(2 * 6e-6)
    tr.window = (0.0, 15e3)                     # the cache follows the window
    assert ph.scope_s("pool.gather") == 0.0
    assert ph.scope_s("pool.select") == pytest.approx(2 * 15e-6)


def test_phase_readers_read_nothing_from_an_unscoped_program():
    """A program that names no phase, as the benchmark's parent may be,
    gives the phase metrics no value and raises nothing."""
    tr = phases.load(str(DATA / UNSCOPED))
    assert not phases.of(tr).has_scopes()
    for m in PHASE_METRICS:
        assert catalog.metric(m).read(tr, counts()) is None


def test_readers_find_the_trace_the_harness_recorded(tmp_path, monkeypatch):
    """In a ``--trace 1`` run the readers get the ``Trace`` alone: they
    find its file under the harness's trace directory by its operations,
    past newer files of other traces, and read nothing where no file
    holds them."""
    monkeypatch.setattr(phases, "TRACE_DIR", tmp_path)
    (tmp_path / "ant_async_sim" / "plugins").mkdir(parents=True)
    (tmp_path / "other").mkdir()
    own = tmp_path / "ant_async_sim" / "plugins" / "h.xplane.pb"
    other = tmp_path / "other" / "h.xplane.pb"
    shutil.copy(DATA / SCOPED, own)
    shutil.copy(DATA / UNSCOPED, other)
    os.utime(own, (1e9, 1e9))
    os.utime(other, (2e9, 2e9))
    tr = tracelib.load(str(own))
    for m in PHASE_METRICS:
        assert catalog.metric(m).read(tr, counts()) > 0, m
    assert phases.of(tr).trace is tr
    alone = Trace(ops={"/device:TPU:0": [Event("fusion.1", 0.0, 1e3)]},
                  modules={}, host=[], window=(0.0, 2e3))
    for m in PHASE_METRICS:
        assert catalog.metric(m).read(alone, counts()) is None


def test_bench_scopes_are_the_programs():
    """The reader's phase names are the ones the program scopes with."""
    from repro.obs import trace as program

    assert SCOPES == program.SCOPES


def test_recorded_scoped_trace():
    """Two calls of the same 4-step collect, recorded on one v5e chip
    with the pool's phases scoped (``record_trace.py``): the phases the
    collect runs carry device time, the phase readers read values, and
    the idle gap at the window's end is the host reading the pool's
    counters, under the pool's own ``pool.stats`` span."""
    tr = phases.load(str(DATA / SCOPED))
    ph = phases.of(tr)
    assert ph.has_scopes()
    assert tr.kernel_count("env_multi_step") == 2 * 4
    for p in ("pool.send", "pool.select", "pool.gather", "pool.env_step",
              "pool.writeback", "pool.telemetry", "collect",
              "collect.policy"):
        assert ph.scope_s(p) > 0, p
    assert ph.scope_s("pool.tick", "pool.transform") == 0.0
    kern = sum(e.dur_ns for e in tr.ops["/device:TPU:0"]
               if "env_multi_step" in e.name) * 1e-9
    assert 0 < kern <= ph.scope_s("pool.env_step")
    for m in PHASE_METRICS:
        assert catalog.metric(m).read(tr, counts()) > 0, m
    assert catalog.metric("unscoped_pct.sim").read(tr, counts()) < 10
    chip = sorted(tr.ops)[0]
    gaps = complement(tr.clipped(tr.ops[chip] + tr.modules.get(chip, [])),
                      tr.window)
    last = gaps[-1]
    assert last[1] == tr.window[1]
    stats = [e for e in tr.host if e.name == "pool.stats"]
    assert any(min(last[1], e.end_ns) > max(last[0], e.start_ns)
               for e in stats)
    assert os.path.getsize(DATA / SCOPED) < 2_000_000
