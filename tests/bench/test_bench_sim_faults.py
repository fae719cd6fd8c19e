"""The ``sim`` cells' comparison, at a size a test run can hold: a sound
run is correct; the control (the reference in bfloat16 in the program's
place) and each fault planted under the timed path are not."""

import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_tiny import control_checks, passes, run_cell, tiny_root  # noqa: E402

CELL = "ant_async_sim"
SEED = 2147483711


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_sim"))


def test_sound_run_is_correct(root):
    res = run_cell(root, CELL, SEED)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["sim_fps"]["value"] > 0


def test_control_is_not_correct(root):
    checks = control_checks(root, CELL, SEED + 1)
    assert not passes(checks), checks


def _unchanged(self, states, actions, costs):
    return states


def _half_stepped(v_step):
    def step(self, states, actions, do=None):
        n = actions.shape[0]
        half = jnp.arange(n) < n // 2
        do = half if do is None else do & half
        return v_step(self, states, actions, do)
    return step


def _altered(v_observe):
    def observe(self, states):
        obs = v_observe(self, states)
        return obs.at[0, 4].add(1e-2)
    return observe


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(root, monkeypatch, fault):
    from repro.envs.mujoco_like import MujocoLikeBatch

    if fault == "state_unchanged":
        monkeypatch.setattr(MujocoLikeBatch, "v_multi_substep", _unchanged)
    elif fault == "half_batch":
        monkeypatch.setattr(MujocoLikeBatch, "v_step",
                            _half_stepped(MujocoLikeBatch.v_step))
    else:
        monkeypatch.setattr(MujocoLikeBatch, "v_observe",
                            _altered(MujocoLikeBatch.v_observe))
    res = run_cell(root, CELL, SEED + 2)
    assert res["correct"] is False, res["checks"]
