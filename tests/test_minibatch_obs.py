"""kernels/minibatch_obs: the Nature CNN's minibatch operand in one pass.

The Pallas kernel (in interpret mode here) against ``ref.py``'s jnp
form, bitwise: every uint8 value, random rollouts and permutations,
block sizes, and minibatches that are not a multiple of the block.
Then one PPO update whose operands come from the kernel against the
same update with the jnp operand: the same parameters and Adam moments,
bit for bit, and the kernel counted on each of its 16 minibatch steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import backend as kernel_backend
from repro.kernels.minibatch_obs import ops, ref

SHAPE = (4, 84, 84)       # PongClassic-v5's frame stack


def bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))


def expected(obs, idx):
    """The operand as the Nature CNN's trunk writes it in jnp."""
    x = jnp.asarray(obs)[jnp.asarray(idx)].astype(jnp.float32) / 255
    return jnp.transpose(x, (2, 3, 1, 0)).astype(jnp.bfloat16)


def test_every_uint8_value():
    obs = np.zeros((3,) + SHAPE, np.uint8)
    obs.reshape(3, -1)[:] = np.arange(SHAPE[0] * SHAPE[1] * SHAPE[2]) % 256
    obs[1] = 255 - obs[1]
    idx = np.array([2, 0, 1, 1, 0], np.int32)
    got = ops.minibatch_obs(jnp.asarray(obs), jnp.asarray(idx),
                            backend="pallas-interpret")
    assert got.shape == (84, 84, 4, 5) and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(bits(got), bits(expected(obs, idx)))
    # each value maps to the bfloat16 nearest x / 255, and all 256 occur
    table = bits((jnp.arange(256, dtype=jnp.float32) / 255).astype(
        jnp.bfloat16))
    src = np.transpose(obs[idx], (2, 3, 1, 0))
    np.testing.assert_array_equal(bits(got), table[src])
    assert len(np.unique(src)) == 256


@pytest.mark.parametrize("mb", [64, 128, 200, 300])
def test_random_rollout_and_permutation(mb):
    """Blocks of 128 rows: one partial, one whole, a whole and a partial,
    two whole and a partial."""
    rng = np.random.default_rng(mb)
    obs = rng.integers(0, 256, (96,) + SHAPE, np.uint8)
    idx = np.concatenate([rng.permutation(96), rng.permutation(96),
                          rng.permutation(96), rng.permutation(96)])[:mb]
    got = ops.minibatch_obs(jnp.asarray(obs), jnp.asarray(idx, np.int32),
                            backend="pallas-interpret")
    np.testing.assert_array_equal(bits(got), bits(expected(obs, idx)))


@pytest.mark.parametrize("backend", ["reference", "vmap", "auto"])
def test_every_backend_gives_the_same_bits(backend):
    rng = np.random.default_rng(1)
    obs = jnp.asarray(rng.integers(0, 256, (20, 2, 9, 13), np.uint8))
    idx = jnp.asarray(rng.permutation(20)[:7], jnp.int32)
    got = ops.minibatch_obs(obs, idx, backend=backend)
    want = ops.minibatch_obs(obs, idx, backend="pallas-interpret")
    np.testing.assert_array_equal(bits(got), bits(want))
    np.testing.assert_array_equal(bits(want), bits(
        ref.minibatch_obs_reference(obs, idx)))


@pytest.mark.parametrize("shape,dtype,idx_shape", [
    ((8, 3, 84, 84), jnp.uint8, (4,)),      # odd channel count
    ((8, 4, 84, 84), jnp.float32, (4,)),    # not uint8
    ((8, 84, 84), jnp.uint8, (4,)),         # not a stack of planes
    ((8, 4, 84, 84), jnp.uint8, (2, 2)),    # indices not a vector
])
def test_rejects_what_it_cannot_lay_out(shape, dtype, idx_shape):
    with pytest.raises(ValueError, match="minibatch_obs wants"):
        ops.minibatch_obs(jnp.zeros(shape, dtype),
                          jnp.zeros(idx_shape, jnp.int32),
                          backend="pallas-interpret")


def _update(backend, monkeypatch):
    """One ``make_ppo_update`` on a 16-lane x 16-step Pong rollout (4
    epochs x 4 minibatches of 64) with the kernel backend forced."""
    import repro
    from repro.rl.nets import ActorCritic
    from repro.rl.ppo import PPOConfig, PPOState, make_ppo_update

    monkeypatch.setattr(kernel_backend, "default_backend", lambda: backend)
    spec = repro.make("PongClassic-v5", num_envs=2, seed=0).spec
    net = ActorCritic(spec)
    cfg = PPOConfig(num_steps=16, clip=0.1)
    opt, update = make_ppo_update(net, cfg, 16, keep_steps=True)
    params = net.init(jax.random.PRNGKey(0))
    state = PPOState(params, opt.init(params), jnp.int32(0))
    rng = np.random.default_rng(3)
    t, m = 16, 16
    rollout = {
        "obs": jnp.asarray(rng.integers(0, 256, (t, m) + SHAPE, np.uint8)),
        "actions": jnp.asarray(rng.integers(0, 6, (t, m)), jnp.int32),
    }
    for k in ("logp", "values", "adv", "ret"):
        rollout[k] = jnp.asarray(rng.normal(size=(t, m)), jnp.float32)
    rollout["logp"] = -jnp.abs(rollout["logp"]) - 1.0   # log-probabilities
    return jax.jit(update)(state, rollout, jax.random.PRNGKey(4))


def test_update_is_bitwise_whichever_way_the_operand_is_made(monkeypatch):
    kernel_state, kernel_metrics = _update("pallas-interpret", monkeypatch)
    jnp_state, jnp_metrics = _update("reference", monkeypatch)
    assert int(kernel_metrics["obs_kernel_steps"]) == 16
    assert int(jnp_metrics["obs_kernel_steps"]) == 0
    assert np.isfinite(float(kernel_metrics["loss"]))
    # parameters, Adam's moments (the gradients' running sums) and the
    # states kept around the first and last minibatch steps
    got = jax.tree.leaves((kernel_state, kernel_metrics["steps"]))
    want = jax.tree.leaves((jnp_state, jnp_metrics["steps"]))
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(jnp.any(a != b)),
        kernel_state.params, _initial_params()))
    assert all(moved)


def _initial_params():
    import repro
    from repro.rl.nets import ActorCritic

    spec = repro.make("PongClassic-v5", num_envs=2, seed=0).spec
    return ActorCritic(spec).init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("backend,learner_devices", [("vmap", 1),
                                                     ("pallas-interpret", 2)])
def test_update_keeps_the_plain_path(backend, learner_devices, monkeypatch):
    """The off-TPU ``vmap`` choice, and a learner replicated over a mesh
    (a Pallas call cannot be partitioned there), build no operand: the
    trunk converts and transposes the gathered stacks as before, and
    the count reads 0."""
    import repro
    from repro.rl.nets import ActorCritic
    from repro.rl.ppo import PPOConfig, PPOState, make_ppo_update

    monkeypatch.setattr(kernel_backend, "default_backend", lambda: backend)
    spec = repro.make("PongClassic-v5", num_envs=2, seed=0).spec
    net = ActorCritic(spec)
    cfg = PPOConfig(num_steps=4, epochs=1, minibatches=2)
    opt, update = make_ppo_update(net, cfg, 2,
                                  learner_devices=learner_devices)
    params = net.init(jax.random.PRNGKey(0))
    state = PPOState(params, opt.init(params), jnp.int32(0))
    rollout = {"obs": jnp.zeros((4, 4) + SHAPE, jnp.uint8),
               "actions": jnp.zeros((4, 4), jnp.int32)}
    for k in ("logp", "values", "adv", "ret"):
        rollout[k] = jnp.zeros((4, 4), jnp.float32)
    text = jax.jit(update).lower(state, rollout,
                                 jax.random.PRNGKey(0)).as_text()
    assert "minibatch_obs" not in text
    _, metrics = jax.jit(update)(state, rollout, jax.random.PRNGKey(0))
    assert int(metrics["obs_kernel_steps"]) == 0
