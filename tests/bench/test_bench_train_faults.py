"""Faults planted under the ``pong_ppo`` cell's timed path, at a test
run's size: each makes the ``train`` driver's comparison read
``correct`` false.  The size is 16 lanes, 16-step rollouts and the
cell's 4 epochs x 4 minibatches: a value clip of 0.2 in place of 0.1
acts only once a sample's value has moved 0.1 within the update, which
the 16 Adam steps of the cell reach on every seed read at this size
and 8 steps of 8 lanes mostly do not; it is read on several seeds."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_tiny import run_cell  # noqa: E402
from bench_tiny_train import tiny_train_root  # noqa: E402

CELL = "pong_ppo"
SEEDS = (2147483811, 2147483821, 2147483831)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_train_root(tmp_path_factory.mktemp("bench_train_faults"),
                           traffic={"num_envs": 16, "batch_size": 16},
                           ppo={"num_steps": 16, "minibatches": 4})


def _misaligned(recv):
    """Each sync block's rows in a permutation that changes per step,
    as a priority-ordered sync recv serves them."""
    def recv_impl(self, ps):
        ps, ts = recv(self, ps)
        n = ts.env_id.shape[0]
        order = jnp.roll(jnp.arange(n)[::-1], ps.tick[0])
        return ps, jax.tree.map(lambda x: x[order], ts)
    return recv_impl


def _dropped_frame(self, state, ts, spec, axis_name=None):
    """A stack that drops its second-oldest frame and repeats the
    newest."""
    obs = ts.obs
    pushed = jnp.concatenate([state["buf"][:, 2:], obs[:, None],
                              obs[:, None]], axis=1)
    reset = (state["fresh"] | ts.done).reshape((-1,) + (1,) * obs.ndim)
    buf = jnp.where(reset, jnp.broadcast_to(obs[:, None], pushed.shape),
                    pushed)
    return ({"buf": buf, "fresh": jnp.zeros_like(state["fresh"])},
            ts.replace(obs=buf))


def _wrong_reward_clip(self, state, ts, spec, axis_name=None):
    return state, ts.replace(reward=jnp.clip(ts.reward, -0.5, 0.5))


class _ValueClip02:
    """``jax.numpy`` with the value loss's clip, the one symmetric about
    0, widened from 0.1 to 0.2."""

    def __getattr__(self, name):
        return getattr(jnp, name)

    @staticmethod
    def clip(x, lo, hi):
        if isinstance(lo, float) and lo == -hi:
            lo, hi = 2 * lo, 2 * hi
        return jnp.clip(x, lo, hi)


class _HalfMinibatch:
    """``jax`` with each minibatch's slice of the update's permutation
    cut to its first half: half of every minibatch left out."""

    class _Lax:
        def __getattr__(self, name):
            return getattr(jax.lax, name)

        @staticmethod
        def dynamic_slice_in_dim(x, start, size, axis=0):
            return jax.lax.dynamic_slice_in_dim(x, start, size // 2, axis)

    lax = _Lax()

    def __getattr__(self, name):
        return getattr(jax, name)


def _plant(monkeypatch, fault):
    from repro.core import engine, transforms
    from repro.rl import ppo

    if fault == "misaligned_rows":
        monkeypatch.setattr(engine.MeshEnvPool, "_recv_impl",
                            _misaligned(engine.MeshEnvPool._recv_impl))
    elif fault == "dropped_frame":
        monkeypatch.setattr(transforms.FrameStack, "apply", _dropped_frame)
    elif fault == "value_clip_0.2":
        monkeypatch.setattr(ppo, "jnp", _ValueClip02())
    elif fault == "half_minibatch":
        monkeypatch.setattr(ppo, "jax", _HalfMinibatch())
    elif fault == "wrong_reward_clip":
        monkeypatch.setattr(transforms.RewardClip, "apply",
                            _wrong_reward_clip)


@pytest.mark.parametrize("fault,seed", [
    ("misaligned_rows", SEEDS[0]), ("dropped_frame", SEEDS[0]),
    ("wrong_reward_clip", SEEDS[0]), ("half_minibatch", SEEDS[0]),
    *(("value_clip_0.2", seed) for seed in SEEDS)])
def test_planted_fault_is_not_correct(root, monkeypatch, fault, seed):
    _plant(monkeypatch, fault)
    res = run_cell(root, CELL, seed, seconds=10.0)
    assert res["correct"] is False, res["checks"]
