"""The ``pong_ppo`` cell's teacher-forced comparison at a size a test run
can hold (8 lanes, 8-step rollouts, 2 minibatches, seeded random
weights): a sound run of the ``train`` driver agrees with the plain
reference layer by layer, and the control (the reference with its
products' operands rounded to ``float8_e4m3fn``) does not pass."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_tiny import control_checks, passes, run_cell  # noqa: E402
from bench_tiny_train import tiny_train_root  # noqa: E402

CELL = "pong_ppo"
SEED = 2147483741


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_train_root(tmp_path_factory.mktemp("bench_train"))


@pytest.fixture(scope="module")
def sound(root):
    return run_cell(root, CELL, SEED, seconds=6.0)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True, sound["checks"]
    assert sound["attempted"] >= 2 and sound["failed"] == 0
    assert sound["metrics"]["sim_fps"]["value"] > 0


def test_replayed_pipeline_is_exact(sound):
    for k in ("obs_checksum_mismatch", "reward_mismatch", "done_mismatch"):
        assert sound["checks"][k]["value"] == 0.0, k


def test_logits_and_values_agree_in_float32(sound):
    # the CPU computes both sides' products in float32
    assert sound["checks"]["logit_rel_err"]["value"] <= 1e-5
    assert sound["checks"]["value_rel_err"]["value"] <= 1e-5


def test_first_and_last_update_steps_agree(sound):
    # the same steps in float32: each parameter array's gradient and
    # change; the reference sums a minibatch's gradient over blocks, in
    # another order than the program
    assert sound["checks"]["grad_rel_err"]["value"] <= 1e-4
    assert sound["checks"]["step_rel_err"]["value"] <= 1e-4


def test_control_is_not_correct(root):
    checks = control_checks(root, CELL, SEED + 1, seconds=6.0)
    assert not passes(checks), checks


def test_control_rounds_operands_to_e4m3fn():
    """The control's rounding is a true ``float8_e4m3fn`` round trip,
    subnormals kept (``reduce_precision`` flushes below 2**-6 to 0), and
    its gradient passes straight through."""
    from bench.lib import catalog

    ref = catalog.reference("pong_classic")
    x = jnp.array([0.3, 0.02, 0.01, 0.005, 0.0012, 0.0005], jnp.float32)
    np.testing.assert_array_equal(
        ref.f8(x), [0.3125, 0.01953125, 0.009765625, 0.005859375,
                    0.001953125, 0.0])
    np.testing.assert_array_equal(jax.grad(lambda x: ref.f8(x).sum())(x),
                                  np.ones(6, np.float32))


def test_reference_gae_runs_down_each_lane():
    """The reference's GAE and the program's (``rl/gae.py``) against a
    plain loop over each lane's own column."""
    from bench.lib import catalog
    from repro.rl.gae import gae

    ref = catalog.reference("pong_classic")
    rng = np.random.default_rng(3)
    t, n = 9, 5
    r = rng.normal(size=(t, n)).astype(np.float32)
    v = rng.normal(size=(t, n)).astype(np.float32)
    d = rng.random((t, n)) < 0.2
    last = rng.normal(size=n).astype(np.float32)
    want = np.zeros((t, n))
    for lane in range(n):
        adv, v_next = 0.0, last[lane]
        for s in range(t - 1, -1, -1):
            nd = 1.0 - d[s, lane]
            delta = r[s, lane] + 0.99 * v_next * nd - v[s, lane]
            adv = delta + 0.99 * 0.95 * nd * adv
            want[s, lane], v_next = adv, v[s, lane]
    for f in (ref.gae, gae):
        adv, ret = f(jnp.asarray(r), jnp.asarray(v), jnp.asarray(d),
                     jnp.asarray(last), 0.99, 0.95)
        np.testing.assert_allclose(adv, want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(ret, want + v, rtol=1e-5, atol=1e-5)


def test_reference_pipeline_matches_the_image_kernels():
    """The reference's render, luma and area resize against the
    program's ``kernels/image`` on one batch of game states."""
    from bench.lib import catalog
    from repro.kernels.image import ops

    ref = catalog.reference("pong_classic")
    rng = np.random.default_rng(5)
    s = {k: jnp.asarray(rng.uniform(0, 84, 6).astype(np.float32))
         for k in ("ball_x", "ball_y", "paddle_y", "enemy_y")}
    rgb = ops.pong_render(s["ball_x"], s["ball_y"], s["paddle_y"],
                          s["enemy_y"])
    np.testing.assert_array_equal(ref.render(s), rgb)
    gray = ops.grayscale(rgb)
    np.testing.assert_array_equal(ref.luma(rgb), gray)
    np.testing.assert_array_equal(ref.area_resize(gray),
                                  ops.resize(gray, 84, 84, "area"))
    assert jax.numpy.unique(ref.area_resize(gray)).size > 1
