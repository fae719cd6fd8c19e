"""The engine's lane-row slab (``core/lanes.py``).

* Pack then unpack is the identity bit for bit, for the rows of every
  registered env and for the leaves the slab treats specially: NaN and
  negative-zero float patterns, ``bool``, raw PRNG keys, int32
  extremes, and 8- and 16-bit leaves widened to a word each, also
  through a pool's send and recv.  A typed PRNG key or a 64-bit leaf is
  refused when the layout is built.
* A lane row holds the env state, the action and the seven stored
  results, and nothing else; a send rewrites only the sent lanes'
  actions.
* Compiled for a described v5e at the ``ant_async_sim`` shapes (Ant-v3,
  N=4096, M=1024), a recv moves the lane rows with one gather and one
  scatter.  Before the slab, with one array per leaf, the recv held 19
  scatters, 25 gathers and 3 sorts of the indices of its ``pred``
  scatters, and the send 5 scatters and 5 gathers.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.engine import MeshEnvPool
from repro.core.lanes import LaneLayout
from repro.envs.classic import CartPole, CartPoleState
from repro.utils.pytree import pytree_dataclass

N = 6


def _random_leaf(rng: np.random.Generator, dtype, shape) -> jax.Array:
    """Random bits of ``dtype``, with the patterns a bitcast must keep."""
    if dtype == jnp.bool_:
        return jnp.asarray(rng.integers(0, 2, shape).astype(bool))
    width = np.dtype(dtype).itemsize
    bits = rng.integers(0, 2 ** (8 * width), shape,
                        dtype=np.dtype(f"uint{8 * width}"))
    flat = bits.reshape(-1)
    if dtype == jnp.float32:
        # quiet NaN with a payload, signalling NaN, -0.0, -inf
        specials = [0x7FC00001, 0x7F800001, 0x80000000, 0xFF800000]
    elif dtype == jnp.int32:
        specials = [0x80000000, 0x7FFFFFFF, 0xFFFFFFFF, 0]
    elif dtype == jnp.bfloat16:
        # NaN with a payload, -0.0, the sign bit on a high word
        specials = [0x7FC1, 0x8000, 0xFFFF]
    elif dtype == jnp.int8:
        specials = [0x80, 0x7F, 0xFF]
    else:
        specials = []
    flat[:len(specials)] = specials[:flat.size]
    return jnp.asarray(bits.view(np.dtype(dtype)))


def _random_rows(layout: LaneLayout, n: int, seed: int):
    rng = np.random.default_rng(seed)
    return layout.treedef.unflatten([
        _random_leaf(rng, c.dtype, (n,) + c.shape) for c in layout.columns])


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x if x.dtype == bool else x.view(np.uint8)


def _assert_bitwise(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(_bits(g), _bits(w))


def _round_trip(layout: LaneLayout, rows):
    slab = jax.jit(layout.pack)(rows)
    assert slab.shape == (jax.tree.leaves(rows)[0].shape[0], layout.words)
    assert slab.dtype == jnp.uint32
    _assert_bitwise(jax.jit(layout.unpack)(slab), rows)
    return slab


@pytest.mark.parametrize("task", repro.list_envs())
def test_every_registered_env_round_trips_bitwise(task):
    pool = repro.make(task, num_envs=N, batch_size=N // 2, seed=0)
    layout = pool.lanes
    rows = _random_rows(layout, N, seed=len(task))
    slab = _round_trip(layout, rows)
    assert layout.words == sum(c.words for c in layout.columns)
    # a row gather then a row scatter of other rows moves exactly them
    idx = jnp.array([4, 1, 3], jnp.int32)
    other = _random_rows(layout, 3, seed=99)
    slab2 = jax.jit(layout.scatter)(slab, idx, other)
    _assert_bitwise(jax.jit(layout.gather)(slab2, idx), other)
    keep = np.array([0, 2, 5])
    np.testing.assert_array_equal(np.asarray(slab2)[keep],
                                  np.asarray(slab)[keep])
    # the pool holds its state in the slab and reads it back
    ps, _ = pool.reset(jax.random.PRNGKey(1))
    assert ps.lanes.shape == (N, layout.words)
    _assert_bitwise(pool.lane_view(ps), jax.jit(layout.unpack)(ps.lanes))


def test_narrow_and_special_leaves_round_trip_bitwise():
    s = jax.ShapeDtypeStruct
    rows = {
        "a_f32": s((N, 3), jnp.float32),
        "c_raw_key": s((N, 2), jnp.uint32),
        "d_bool": s((N, 4), jnp.bool_),
        "e_i32": s((N,), jnp.int32),
        "f_u8": s((N, 16), jnp.uint8),
        "g_bf16": s((N, 4), jnp.bfloat16),
        "h_i8": s((N, 3), jnp.int8),
        "i_f32": s((N, 2, 2), jnp.float32),
    }
    layout = LaneLayout(rows)
    # one word per element, whatever the leaf's width
    assert layout.words == 3 + 2 + 4 + 1 + 16 + 4 + 3 + 4
    _round_trip(layout, _random_rows(layout, N, seed=5))
    # offsets follow tree order, leaves side by side
    cols = layout.columns
    assert [c.offset for c in cols] == list(
        np.cumsum([0] + [c.words for c in cols])[:-1])
    # a narrow leaf's word holds its bits zero-extended
    slab = layout.pack(dict(_random_rows(layout, N, seed=6),
                            h_i8=jnp.full((N, 3), -1, jnp.int8)))
    h = cols[list(rows).index("h_i8")]
    np.testing.assert_array_equal(
        np.asarray(slab)[:, h.offset:h.offset + h.words], 0xFF)


@pytest.mark.parametrize("leaf", [
    jax.ShapeDtypeStruct((N,), jax.eval_shape(lambda: jax.random.key(0)).dtype),
    jax.ShapeDtypeStruct((N, 2), jnp.complex64),
], ids=["typed_key", "complex64"])
def test_leaf_wider_than_a_word_is_refused(leaf):
    with pytest.raises(TypeError, match="32-bit words"):
        LaneLayout({"ok": jax.ShapeDtypeStruct((N,), jnp.float32),
                    "bad": leaf})


# --------------------------------------------------------------------- #
# what a lane row holds, and what a send writes to it
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("task,words", [
    ("Ant-v3", 49), ("PongClassic-v5", 22), ("CartPole-v1", 17),
    ("TokenCopy-v0", 79),
])
def test_lane_row_is_state_action_and_results(task, words):
    """One word per element of the env state and of the action, plus the
    seven stored results (reward, done, terminated, truncated, episode
    return and length, step cost): the engine keeps no column of its
    own in the slab."""
    pool = repro.make(task, num_envs=N, seed=0)
    state = jax.eval_shape(pool.env.init_state, jax.random.PRNGKey(0))
    state_words = sum(math.prod(x.shape) for x in jax.tree.leaves(state))
    action_words = math.prod(pool.spec.act_spec.shape)
    assert pool.lanes.words == state_words + action_words + 7 == words


@pytest.mark.parametrize("task,n,m", [
    ("Ant-v3", 8, 4), ("CartPole-v1", 4, 4),
], ids=["async-ant", "sync-cartpole"])
def test_send_rewrites_only_the_sent_lanes_actions(task, n, m):
    pool = repro.make(task, num_envs=n, batch_size=m, seed=0)
    ps, ts = pool.reset(jax.random.PRNGKey(3))
    act = pool.spec.act_spec
    actions = jnp.ones((m,) + act.shape, act.dtype)
    sent = np.asarray(ts.env_id)
    before = pool.lane_view(ps)
    assert not np.array_equal(np.asarray(before.actions)[sent], actions)
    ps2 = jax.jit(pool.send)(ps, actions, ts.env_id)
    after = pool.lane_view(ps2)

    def rows(view, ids):
        return jax.tree.map(lambda x: x[ids], view)

    # the sent lanes: the new action, every other field bit for bit
    _assert_bitwise(rows(after, sent).actions, actions)
    _assert_bitwise(rows(after.replace(actions=before.actions), sent),
                    rows(before, sent))
    # the other lanes: whole slab rows bit for bit
    others = np.setdiff1d(np.arange(n), sent)
    assert others.size == n - m
    np.testing.assert_array_equal(np.asarray(ps2.lanes)[others],
                                  np.asarray(ps.lanes)[others])


# --------------------------------------------------------------------- #
# an env with a byte-wide leaf, through every device engine
# --------------------------------------------------------------------- #
@pytree_dataclass
class LoggedCartPoleState(CartPoleState):
    log: jnp.ndarray      # (8,) uint8: substeps since reset, mod 256


class LoggedCartPole(CartPole):
    """CartPole with a byte-wide per-lane log the observation ignores."""

    def init_state(self, key):
        s = super().init_state(key)
        return LoggedCartPoleState(**vars(s), log=jnp.zeros((8,), jnp.uint8))

    def substep(self, s, action):
        return super().substep(s, action).replace(log=s.log + 1)


@pytest.mark.parametrize("kw", [
    dict(batch_size=4), dict(batch_size=8), dict(batch_size=4, mesh=1),
], ids=["async", "sync", "mesh1"])
def test_byte_leaf_rides_through_the_engine(kw):
    logged = MeshEnvPool(LoggedCartPole(), 8, **kw)
    plain = MeshEnvPool(CartPole(), 8, **kw)
    assert logged.lanes.words == plain.lanes.words + 8
    key = jax.random.PRNGKey(2)
    (pl, tl), (pp, tp) = logged.reset(key), plain.reset(key)
    step_l, step_p = jax.jit(logged.step), jax.jit(plain.step)
    for t in range(12):
        a = (np.asarray(tl.env_id) * 3 + t) % 2
        pl, tl = step_l(pl, jnp.asarray(a, jnp.int32), tl.env_id)
        pp, tp = step_p(pp, jnp.asarray(a, jnp.int32), tp.env_id)
        _assert_bitwise(tl, tp)
    view = logged.lane_view(pl)
    assert view.env_states.log.dtype == jnp.uint8
    # one substep per CartPole step: the log counts the episode's steps,
    # except on lanes that took a substep the pool has not served yet
    log = np.asarray(view.env_states.log).astype(np.int32)
    t = np.asarray(view.env_states.t)
    assert (log == log[:, :1]).all()
    assert ((log[:, 0] - t) % 256 <= 1).all()
    _assert_bitwise(view.env_states.x, plain.lane_view(pp).env_states.x)


# --------------------------------------------------------------------- #
# op counts of the Ant recv and send, compiled for a described v5e
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _ops(text: str, op: str) -> list[str]:
    return [ln for ln in text.splitlines() if f" {op}(" in ln]


def _ant_on_chip(topo, **kw):
    """The ``ant_async_sim`` pool (Ant-v3, N=4096, M=1024) on a described
    v5e chip, and its state's shapes placed there."""
    from jax.sharding import Mesh, SingleDeviceSharding

    from repro.envs.mujoco_like import MujocoLike, MujocoLikeBatch

    dev = topo.devices[0]
    pool = MeshEnvPool(MujocoLikeBatch(MujocoLike(), backend="pallas"),
                       4096, 1024, mesh=Mesh(np.array([dev]), ("env",)),
                       **kw)
    one_chip = SingleDeviceSharding(dev)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    return pool, on_chip, on_chip(jax.eval_shape(pool.init,
                                                 jax.random.PRNGKey(0)))


def test_ant_recv_and_send_move_lane_rows_in_one_op(topo):
    pool, on_chip, ps = _ant_on_chip(topo)
    m = pool._m_local
    recv = pool._jit_recv.lower(ps).compile().as_text()
    send = pool._jit_send.lower(
        ps, on_chip(jax.ShapeDtypeStruct((m, 8), jnp.float32)),
        on_chip(jax.ShapeDtypeStruct((m,), jnp.int32)),
    ).compile().as_text()
    assert "tpu_custom_call" in recv          # the env_step kernel is in
    assert len(_ops(recv, "scatter")) <= 2    # the slab and ``phase``
    assert len(_ops(recv, "gather")) <= 8
    assert not [ln for ln in _ops(recv, "sort")
                if re.search(r"pred\[\d+\]", ln.split(" sort(")[0])]
    assert len(_ops(send, "scatter")) <= 4
    assert len(_ops(send, "gather")) <= 3
