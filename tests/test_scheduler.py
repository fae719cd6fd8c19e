"""core/scheduler.py invariants: the one module that owns async lane
selection for every engine (fifo bitwise-preserving, sjf cost order,
hierarchical mesh safety, numpy host mirror)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental.shard_map import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.scheduler import (
    HAS_ACTION,
    READY,
    SCHEDULES,
    WAITING_ACTION,
    FifoScheduler,
    HierarchicalScheduler,
    SchedState,
    SjfScheduler,
    get_scheduler,
    numpy_priority,
)

N = 16


def random_state(key, n=N, tick=7) -> SchedState:
    """A SchedState with a random phase/cost/age mix."""
    k1, k2, k3 = jax.random.split(key, 3)
    return SchedState(
        phase=jax.random.randint(k1, (n,), 0, 3, jnp.int32),
        cost=jax.random.randint(k2, (n,), 1, 40, jnp.int32),
        send_tick=jax.random.randint(k3, (n,), 0, tick + 1, jnp.int32),
        tick=jnp.int32(tick),
    )


def hier_select(ss: SchedState, m: int):
    """Run the hierarchical policy inside its required shard_map context
    (1-device mesh — the tier-1 process sees one device)."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("env",))
    sched = HierarchicalScheduler("env", 1)
    return shard_map(
        lambda phase, cost, st, tk: sched.select(
            SchedState(phase[0], cost[0], st[0], tk[0]), m
        )[None],
        mesh=mesh,
        in_specs=(P("env"),) * 4,
        out_specs=P("env"),
        check_rep=False,
    )(ss.phase[None], ss.cost[None], ss.send_tick[None], ss.tick[None])[0]


def select_any(name, ss, m):
    if name == "hierarchical":
        return hier_select(ss, m)
    return get_scheduler(name).select(ss, m)


# --------------------------------------------------------------------- #
# the core safety invariant: select never returns a non-serviceable lane
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_select_never_returns_waiting_lane(schedule):
    """While ≥ m serviceable (READY | HAS_ACTION) lanes exist, no policy
    may ever select a WAITING lane (it has no action to execute)."""
    m = 4
    for trial in range(20):
        ss = random_state(jax.random.PRNGKey(trial))
        serviceable = np.asarray(ss.phase) != WAITING_ACTION
        if serviceable.sum() < m:
            continue
        idx = np.asarray(select_any(schedule, ss, m))
        assert len(set(idx.tolist())) == m, idx
        assert serviceable[idx].all(), (schedule, idx, np.asarray(ss.phase))


@pytest.mark.parametrize("schedule", SCHEDULES)
def test_select_prefers_ready_lanes(schedule):
    """READY lanes (unconsumed results) outrank everything in every
    policy — the StateBufferQueue contract."""
    ss = SchedState(
        phase=jnp.array([READY, HAS_ACTION, READY, HAS_ACTION], jnp.int32),
        cost=jnp.array([30, 1, 30, 1], jnp.int32),
        send_tick=jnp.zeros((4,), jnp.int32),
        tick=jnp.int32(3),
    )
    idx = set(np.asarray(select_any(schedule, ss, 2)).tolist())
    assert idx == {0, 2}, idx


# --------------------------------------------------------------------- #
# policy semantics
# --------------------------------------------------------------------- #
def test_fifo_priority_is_the_pre_refactor_formula():
    """fifo must reproduce the engine's original priority bitwise —
    the formula the golden-stream conformance tests pin end to end."""
    sched = FifoScheduler(aging=1.0)
    ss = random_state(jax.random.PRNGKey(0))
    age = (ss.tick - ss.send_tick).astype(jnp.float32)
    big = jnp.float32(1e9)
    ref = jnp.where(
        ss.phase == READY,
        -big + ss.send_tick.astype(jnp.float32),
        jnp.where(
            ss.phase == HAS_ACTION,
            ss.cost.astype(jnp.float32) - 1.0 * age,
            big,
        ),
    )
    np.testing.assert_array_equal(np.asarray(sched.priority(ss)),
                                  np.asarray(ref))


def test_sjf_selects_cheapest():
    ss = SchedState(
        phase=jnp.full((6,), HAS_ACTION, jnp.int32),
        cost=jnp.array([9, 2, 40, 1, 5, 3], jnp.int32),
        send_tick=jnp.zeros((6,), jnp.int32),
        tick=jnp.int32(100),  # huge ages must NOT matter for sjf
    )
    idx = set(np.asarray(SjfScheduler().select(ss, 3)).tolist())
    assert idx == {3, 1, 5}, idx


def test_enqueue_and_complete_roundtrip():
    sched = FifoScheduler()
    ss = sched.init(4)
    assert np.all(np.asarray(ss.phase) == READY)
    ss = sched.complete(ss, jnp.array([0, 2], jnp.int32))
    assert int(ss.tick) == 1
    np.testing.assert_array_equal(
        np.asarray(ss.phase),
        [WAITING_ACTION, READY, WAITING_ACTION, READY],
    )
    ss = sched.enqueue(ss, jnp.array([0], jnp.int32), jnp.array([7]))
    assert int(ss.phase[0]) == HAS_ACTION
    assert int(ss.cost[0]) == 7
    assert int(ss.send_tick[0]) == 1


def test_hierarchical_overdue_band_prevents_starvation():
    """A heavy lane past its patience (age ≥ patience * cost) must win
    over fresh cheap lanes — the quota floor that sjf lacks."""
    ss = SchedState(
        phase=jnp.full((4,), HAS_ACTION, jnp.int32),
        cost=jnp.array([1, 1, 1, 30], jnp.int32),
        send_tick=jnp.array([30, 30, 30, 0], jnp.int32),
        tick=jnp.int32(31),  # lane 3 age = 31 ≥ 1.0 * 30
    )
    idx = np.asarray(hier_select(ss, 1))
    assert idx.tolist() == [3], idx


# --------------------------------------------------------------------- #
# construction / host mirror
# --------------------------------------------------------------------- #
def test_get_scheduler_validation():
    assert get_scheduler("fifo").name == "fifo"
    assert get_scheduler("sjf").name == "sjf"
    assert get_scheduler(
        "hierarchical", axis_name="env", num_shards=2
    ).name == "hierarchical"
    inst = SjfScheduler()
    assert get_scheduler(inst) is inst
    with pytest.raises(ValueError):
        get_scheduler("hierarchical")  # needs a mesh
    with pytest.raises(ValueError):
        get_scheduler("random")


def test_numpy_mirror_matches_device_orders():
    cost = np.array([9.0, 2.0, 40.0, 1.0], np.float32)
    st = np.zeros(4, np.float32)
    # fifo: no reordering (zeros — the host queue's native FIFO)
    assert np.all(numpy_priority("fifo", cost, st, 5) == 0)
    # sjf: exactly the cost order the device policy uses
    order = np.argsort(numpy_priority("sjf", cost, st, 5), kind="stable")
    np.testing.assert_array_equal(order, [3, 1, 0, 2])
    with pytest.raises(ValueError):
        numpy_priority("random", cost, st, 5)
