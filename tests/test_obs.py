"""Engine-wide telemetry (``obs/``): the ``stats()`` contract across all
six engines, the metrics registry, and the fenced trace spans.

The conformance pin mirrors tests/test_conformance.py: the same scripted
sync rollout must yield the SAME counter values on every engine — the
in-graph ``Telemetry`` pytree (device family) and the ``HostTelemetry``
numpy mirror (thread/forloop/subprocess) implement one semantics.
Multi-shard bitwise invariance runs in tests/_obs_mesh_check.py (fresh
interpreter with simulated host devices — conftest harness contract).
"""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.protocol import bind
from repro.obs.metrics import MetricsRegistry, publish_pool_stats
from repro.obs.telemetry import WAIT_EDGES, stats_to_jsonable
from repro.obs.trace import Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))

TASK = "TokenCopy-v0"
N = 4
STEPS = 3
SEED = 0


def policy(env_ids: np.ndarray, t: int) -> np.ndarray:
    return ((env_ids.astype(np.int64) * 7 + t) % 256).astype(np.int32)


# --------------------------------------------------------------------- #
# stats() conformance: all six engines, one scripted rollout
# --------------------------------------------------------------------- #
def device_stats(engine: str, **kw) -> dict:
    pool = repro.make(TASK, num_envs=N, engine=engine, seed=SEED, **kw)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    for t in range(STEPS):
        ids = np.asarray(ts.env_id)
        ps, ts = step(ps, jnp.asarray(policy(ids, t)), ts.env_id)
    return pool.stats(ps)


def host_stats(engine: str, **kw) -> dict:
    pool = repro.make(TASK, num_envs=N, engine=engine, seed=SEED, **kw)
    try:
        if hasattr(pool, "async_reset"):
            pool.async_reset()
            out = pool.recv()
        else:
            out = pool.reset()
        for t in range(STEPS):
            ids = np.asarray(out["env_id"])
            out = pool.step(policy(ids, t), ids)
        return pool.stats()
    finally:
        if hasattr(pool, "close"):
            pool.close()


def test_stats_identical_across_all_six_engines():
    """recvs / served / stepped / occupancy / cost_sum / per-lane serves
    / wait histogram: identical values everywhere (the acceptance pin)."""
    ref = device_stats("device")
    # the reference itself is fully predicted by the rollout script:
    # reset recv + STEPS step recvs, every recv serves all N lanes, and
    # only the reset recv's results are not env steps
    assert ref["recvs"] == STEPS + 1
    assert ref["served"] == N * (STEPS + 1)
    assert ref["stepped"] == N * STEPS
    assert ref["occupancy"] == pytest.approx(STEPS / (STEPS + 1))
    assert ref["cost_sum"] == N * STEPS          # TokenCopy cost == 1
    assert ref["overdue_admits"] == 0
    np.testing.assert_array_equal(ref["serves"], [STEPS + 1] * N)
    np.testing.assert_array_equal(ref["wait_ticks"], [0] * N)
    assert ref["wait_ticks_total"] == 0
    assert ref["wait_hist"][0] == N * (STEPS + 1)
    assert sum(ref["wait_hist"]) == ref["served"]
    assert ref["wait_edges"] == list(WAIT_EDGES)

    ref_j = stats_to_jsonable(ref)
    for engine, runner, kw in [
        ("device-masked", device_stats, {"batch_size": N}),
        ("device-sharded", device_stats, {"num_shards": 1}),
        ("thread", host_stats, {"num_threads": 2}),
        ("forloop", host_stats, {}),
        ("subprocess", host_stats, {"num_threads": 1}),
    ]:
        got = stats_to_jsonable(runner(engine, **kw))
        assert got == ref_j, f"{engine} stats diverge: {got} != {ref_j}"
    json.dumps(ref_j)  # the snapshot is JSON-safe


def test_async_stats_conservation_laws():
    """Async top-M: serving order is schedule business, but the counters
    stay conserved and queue waits actually accumulate."""
    pool = repro.make(TASK, num_envs=8, batch_size=4, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    for t in range(8):
        ids = np.asarray(ts.env_id)
        ps, ts = step(ps, jnp.asarray(policy(ids, t)), ts.env_id)
    s = pool.stats(ps)
    assert s["recvs"] == 9                      # reset batch + 8 steps
    assert s["served"] == s["recvs"] * 4
    assert int(s["serves"].sum()) == s["served"]
    assert int(s["wait_hist"].sum()) == s["served"]
    assert 0 <= s["stepped"] <= s["served"]
    # with 8 lanes and 4-slot blocks, half the ready lanes wait each
    # tick — the wait accounting must see that
    assert s["wait_ticks_total"] > 0
    assert int(s["wait_hist"][1:].sum()) > 0


def test_stats_mesh_invariance_subprocess():
    """Bitwise mesh-size invariance at D in {1, 2, 4} plus hierarchical
    overdue accounting (fresh interpreter, simulated host devices)."""
    script = os.path.join(ROOT, "tests", "_obs_mesh_check.py")
    p = subprocess.run([sys.executable, script, "4"], env=ENV,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout[p.stdout.index("{"):])
    assert res["devices"] == 4
    assert res["sync_stats_bitwise_all_meshes"], res
    assert res["async_served_conserved"], res
    assert res["async_serves_sum"], res
    assert res["async_stepped_bounded"], res
    assert res["async_hist_conserved"], res
    assert res["hier_overdue_counted"], res
    assert res["obs_off_raises"], res


def test_obs_false_strips_counters_and_stats_raises():
    pool = repro.make(TASK, num_envs=N, obs=False, seed=SEED)
    ps, _ = pool.reset(jax.random.PRNGKey(SEED))
    assert ps.telemetry == ()                   # zero extra pytree leaves
    with pytest.raises(RuntimeError, match="obs=False"):
        pool.stats(ps)
    hp = repro.make(TASK, num_envs=N, engine="forloop", obs=False,
                    seed=SEED)
    with pytest.raises(RuntimeError, match="obs=False"):
        hp.stats()


@pytest.mark.parametrize("engine", ["device", "forloop"])
def test_bound_pool_stats_dispatch(engine):
    """BoundEnvPool.stats() reads the owned PoolState on functional
    engines and the numpy mirror on host engines."""
    pool = repro.make(TASK, num_envs=N, engine=engine, seed=SEED)
    h = bind(pool, key=jax.random.PRNGKey(SEED))
    try:
        ts = h.reset()
        for t in range(2):
            a = policy(np.asarray(ts.env_id), t)
            ts = h.step(jnp.asarray(a), ts.env_id)
        s = h.stats()
        assert s["recvs"] == 3
        assert s["served"] == 3 * N
    finally:
        h.close()


# --------------------------------------------------------------------- #
# ThreadEnvPool recv deadline race (satellite fix)
# --------------------------------------------------------------------- #
def test_thread_recv_deadline_rechecks_worker_error():
    """A worker failure landing DURING the final (deadline-straddling)
    take must surface as the worker's RuntimeError, not be masked by the
    spurious TimeoutError."""
    pool = repro.make("CartPole-v1", engine="thread", num_envs=4,
                      batch_size=2, num_threads=2)
    orig_take = pool._states.take

    def racing_take(timeout=None):
        # the failure arrives while take blocks past the deadline
        pool._error = (0, "boom")
        time.sleep(0.08)
        raise TimeoutError

    try:
        pool._states.take = racing_take
        with pytest.raises(RuntimeError, match="worker failed"):
            pool.recv(timeout=0.02)
    finally:
        pool._states.take = orig_take
        pool._error = None
        pool.close()


# --------------------------------------------------------------------- #
# metrics registry
# --------------------------------------------------------------------- #
def test_counter_gauge_histogram_series():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2, engine="device")
    c.inc(3, engine="device")
    assert c.value() == 1
    assert c.value(engine="device") == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(3.5)
    g.set(4.5)                                  # overwrite, not add
    assert g.value() == 4.5
    h = reg.histogram("h", (0, 1, 2, 4))
    h.observe(0)
    h.observe(1.5)
    h.observe(100)                              # open-ended last bucket
    np.testing.assert_array_equal(h.counts(), [1, 1, 0, 1])
    h.observe_counts([1, 0, 0, 2])
    np.testing.assert_array_equal(h.counts(), [2, 1, 0, 3])
    with pytest.raises(ValueError):
        h.observe_counts([1, 2])                # wrong bucket count


def test_registry_get_or_create_and_clashes():
    reg = MetricsRegistry()
    assert reg.counter("m") is reg.counter("m")
    with pytest.raises(TypeError):
        reg.gauge("m")                          # kind clash
    reg.histogram("h", (0, 1))
    with pytest.raises(ValueError):
        reg.histogram("h", (0, 2))              # edge clash


def test_publish_pool_stats_and_json_export(tmp_path):
    s = device_stats("device")
    reg = MetricsRegistry()
    publish_pool_stats(reg, s, engine="device", task=TASK)
    lbl = {"engine": "device", "task": TASK}
    assert reg.gauge("pool_recvs").value(**lbl) == s["recvs"]
    assert reg.gauge("pool_occupancy").value(**lbl) == \
        pytest.approx(s["occupancy"])
    np.testing.assert_array_equal(
        reg.histogram("pool_wait_ticks", s["wait_edges"]).counts(**lbl),
        s["wait_hist"],
    )
    # re-publishing a cumulative snapshot overwrites gauges (no
    # double-count) but merges histogram counts
    publish_pool_stats(reg, s, engine="device", task=TASK)
    assert reg.gauge("pool_served").value(**lbl) == s["served"]
    snap = json.loads(reg.to_json())
    assert snap["pool_recvs"]["type"] == "gauge"
    assert snap["pool_wait_ticks"]["series"][0]["edges"] == \
        [float(e) for e in s["wait_edges"]]
    path = reg.dump(str(tmp_path / "metrics.json"))
    assert json.load(open(path)) == snap


# --------------------------------------------------------------------- #
# fenced trace spans
# --------------------------------------------------------------------- #
def test_tracer_totals_accumulate_and_events_sorted():
    tr = Tracer()
    with tr.span("a"):
        time.sleep(0.01)
    with tr.span("a"):
        time.sleep(0.01)
    with tr.span("b", cat="custom"):
        pass
    tot = tr.totals()
    assert tot["a"] >= 0.02
    assert tot["b"] >= 0.0
    evs = tr.events()
    assert [e["name"] for e in evs] == ["a", "a", "b"]
    assert all(e["ph"] == "X" for e in evs)
    assert evs == sorted(evs, key=lambda e: e["ts"])
    assert {e["cat"] for e in evs} == {"engine", "custom"}


def test_span_fence_blocks_before_close(monkeypatch):
    """The Fig-4 bucket discipline: the registered payload is
    block_until_ready'd INSIDE the span, exceptions skip the fence, and
    the fence= kwarg is the declarative form."""
    fenced = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda p: fenced.append(p))
    tr = Tracer()
    with tr.span("s") as sp:
        out = sp.fence(("payload",))
    assert out == ("payload",)                  # fence passes through
    assert fenced == [("payload",)]
    with tr.span("t", fence=("kwarg",)):
        pass
    assert fenced[-1] == ("kwarg",)
    with pytest.raises(ValueError):
        with tr.span("u") as sp:
            sp.fence(("never",))
            raise ValueError("boom")
    assert fenced[-1] == ("kwarg",)             # exception skipped fence
    assert "u" in tr.totals()                   # ... but span recorded


def test_span_fence_covers_async_dispatch():
    """Real-jax pin: a dispatched device computation must be inside the
    fenced span's wall time, not leak into the next span."""
    x = jnp.ones((256, 256))
    f = jax.jit(lambda x: (x @ x).sum())
    f(x).block_until_ready()                    # compile outside timing
    tr = Tracer()
    with tr.span("compute") as sp:
        sp.fence(f(x))
    with tr.span("idle"):
        pass
    tot = tr.totals()
    assert tot["compute"] > 0.0
    assert tot["idle"] < tot["compute"] + 1.0   # sanity, not a perf pin


def test_tracer_threaded_buffers_and_dump(tmp_path):
    tr = Tracer()
    # all four threads live at once: an exited thread's id can be reused
    together = threading.Barrier(4)

    def worker():
        with tr.span("w"):
            time.sleep(0.005)
            together.wait(timeout=10)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tr.instant("mark")
    assert tr.totals()["w"] >= 4 * 0.005        # sums across threads
    assert len({e["tid"] for e in tr.events() if e["name"] == "w"}) == 4
    path = tr.dump(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    names = {e["name"] for e in data["traceEvents"]}
    assert {"w", "mark"} <= names
    assert data["displayTimeUnit"] == "ms"


# --------------------------------------------------------------------- #
# consumers: PPO profile buckets and the DecodePool serve fence
# --------------------------------------------------------------------- #
def test_train_host_buckets_ride_tracer_and_registry():
    """train_host's Fig-4 profile is now the tracer's totals(), and a
    registry sees every history record (satellite a)."""
    from repro.rl.ppo import PPOConfig, train_host

    pool = repro.make("CartPole-v1", engine="thread", num_envs=4,
                      batch_size=4, num_threads=2)
    tr, reg = Tracer(), MetricsRegistry()
    try:
        cfg = PPOConfig(total_steps=4 * 8 * 2, num_steps=8,
                        minibatches=2, epochs=1)
        _, _, hist, prof = train_host(pool, pool.spec, cfg, seed=0,
                                      hidden=(16,), tracer=tr,
                                      registry=reg)
    finally:
        pool.close()
    assert set(prof) == {"env_step", "inference", "train", "other"}
    tot = tr.totals()
    for k, v in prof.items():
        assert v == pytest.approx(tot.get(k, 0.0))
    assert reg.counter("ppo_iterations").value() == len(hist)
    assert reg.gauge("ppo_loss").value() == \
        pytest.approx(float(hist[-1]["loss"]))


def test_decode_pool_fenced_wall_and_registry():
    """ServeStats.wall_s closes AFTER block_until_ready on the final
    lane state (satellite c) and lands in the registry."""
    from repro.envs.token_env import TokenEnv
    from repro.rl.policy_lm import LMPolicy, default_policy_config
    from repro.serving.decode_pool import DecodePool

    spec = TokenEnv(vocab=16, ep_len=4, ctx_len=8).spec
    policy_lm = LMPolicy(spec, cfg=default_policy_config(16, 16),
                         max_len=16, backend="reference")
    params = policy_lm.init(jax.random.PRNGKey(0))
    reg = MetricsRegistry()
    dp = DecodePool(policy_lm, num_lanes=2, max_new=4, registry=reg)
    outs, stats = dp.serve(params, [[1, 2], [3], [2, 1, 3]])
    assert all(len(o) == 4 for o in outs)       # every budget honored
    assert stats.total_tokens == 12
    assert stats.wall_s > 0.0
    assert 0.0 < stats.utilization <= 1.0
    lbl = {"schedule": "fifo"}
    assert reg.counter("decode_tokens").value(**lbl) == 12
    assert reg.counter("decode_requests").value(**lbl) == 3
    assert reg.gauge("decode_utilization").value(**lbl) == \
        pytest.approx(stats.utilization)
    assert reg.counter("decode_wall_s").value(**lbl) == \
        pytest.approx(stats.wall_s)
