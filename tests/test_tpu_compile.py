"""Every Pallas kernel of the main paths, compiled ahead of time for a
described TPU v5e chip at deployment shapes.

Interpret mode (tests/test_kernels.py, tests/test_image_kernels.py)
checks what a kernel computes; only the chip's compiler refuses a block
that does not tile, a slice it cannot lower, or more scoped VMEM than a
kernel may use.  The TPU compiler is installed with jaxlib, so these
compile for a chip that is described, not attached.  The topology is
described inside a module fixture (never at import), which loads the
TPU library only in the worker that runs this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.env_step.ops import env_multi_step, env_step
from repro.kernels.image import ops as image
from repro.kernels.minibatch_obs.ops import minibatch_obs

KERNEL = "tpu_custom_call"


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


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _env_masked(s):
    n = 4096   # Ant-v3 async pool: N=4096 lanes, block 256
    return env_multi_step.lower(
        s((n, 28), jnp.float32), s((n, 8), jnp.float32), s((n,), jnp.int32),
        s((n,), jnp.float32), max_cost=9, block_n=256, backend="pallas")


def _env_uniform(s):
    n = 4096
    return env_step.lower(s((n, 28), jnp.float32), s((n, 8), jnp.float32),
                          n_sub=5, block_n=256, interpret=False)


def _grayscale(s):  # PongClassic-v5 at W1's N=1024: native RGB screens
    return image.grayscale.lower(s((1024, 210, 160, 3), jnp.uint8),
                                 backend="pallas")


def _resize(s):
    return image.resize.lower(s((1024, 210, 160), jnp.uint8), 84, 84,
                              backend="pallas")


def _crop(s):
    return image.crop.lower(s((1024, 210, 160), jnp.uint8), 34, 0, 160, 160,
                            backend="pallas")


def _pong_render(s):
    return image.pong_render.lower(*[s((1024,), jnp.float32)] * 4,
                                   backend="pallas")


def _minibatch_obs(s):  # pong_ppo's update: T=128 x N=1024, 4 minibatches
    return minibatch_obs.lower(s((131072, 4, 84, 84), jnp.uint8),
                               s((32768,), jnp.int32), backend="pallas")


def _decode(b, h, hkv, t, d, block_t):
    def lower(s):
        return decode_attention.lower(
            s((b, h, d), jnp.float32), s((b, hkv, t, d), jnp.float32),
            s((b, hkv, t, d), jnp.float32), s((b,), jnp.int32),
            block_t=block_t, backend="pallas")
    return lower


CASES = {
    "env_step_masked": _env_masked,
    "env_step_uniform": _env_uniform,
    "grayscale": _grayscale,
    "resize": _resize,
    "crop": _crop,
    "pong_render": _pong_render,
    "minibatch_obs": _minibatch_obs,
    # rl/policy_lm.py's default policy (H=4, Hkv=2, hd=16, T=max_len=64)
    # over 1024 lanes, and a serving cache (B=32, T=1024)
    "decode_attention_policy": _decode(1024, 4, 2, 64, 16, 64),
    "decode_attention_B32_T1024": _decode(32, 8, 2, 1024, 64, 512),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = CASES[name](s).compile()
    assert KERNEL in compiled.as_text()


def test_image_chain_hands_uint8_between_kernels(one_chip):
    """PongClassic-v5's observation path at N=1024 (render -> grayscale
    -> area resize through ``ops.py``) compiles to exactly its three
    kernels, each reading and writing its images as uint8, with no
    convert between them."""
    def chain(bx, by, py, ey):
        screens = image.pong_render(bx, by, py, ey, backend="pallas")
        gray = image.grayscale(screens, backend="pallas")
        return image.resize(gray, 84, 84, backend="pallas")

    s = jax.ShapeDtypeStruct((1024,), jnp.float32, sharding=one_chip)
    text = jax.jit(chain).lower(s, s, s, s).compile().as_text()
    assert "convert" not in text
    kernels = {}
    for line in text.splitlines():
        if KERNEL not in line:
            continue
        m = re.match(r"\s*(?:ROOT )?%(\w+)\.\d+ = (.*?) custom-call\(", line)
        operands = re.search(
            r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}", line)
        assert m.group(1) not in kernels, m.group(1)
        kernels[m.group(1)] = (
            re.findall(r"(\w+)\[", m.group(2)),
            re.findall(r"(\w+)\[1024,210,160\]", operands.group(1)),
        )
    # three kernels, under the names bench/metrics/image_kernels_roofline
    # .train.py reads
    assert set(kernels) == {"pong_render", "grayscale", "resize"}
    for name, (results, images_in) in kernels.items():
        assert results and set(results) == {"u8"}, (name, results)
        assert set(images_in) <= {"u8"}, (name, images_in)
    assert kernels["grayscale"][1] == ["u8"] * 3
    assert kernels["resize"][1] == ["u8"]


def test_pong_update_makes_conv1_operand_in_one_kernel(one_chip, monkeypatch):
    """PongClassic-v5's PPO update at the ``pong_ppo`` cell's T=128 and
    N=1024 (4 epochs x 4 minibatches of 32768 stacks): each minibatch
    step's conv1 operand comes from the ``minibatch_obs`` kernel, and the
    loop body keeps neither XLA's convert-multiply of the gathered
    stacks nor a copy of a 32768 x 28224-element operand."""
    import repro
    from repro.kernels import backend as kernel_backend
    from repro.rl.nets import ActorCritic
    from repro.rl.ppo import PPOConfig, PPOState, make_ppo_update

    # the described chip is not the process's backend: take its branch
    monkeypatch.setattr(kernel_backend, "default_backend", lambda: "pallas")
    net = ActorCritic(repro.make("PongClassic-v5", num_envs=2, seed=0).spec)
    opt, update = make_ppo_update(net, PPOConfig(num_steps=128), 16,
                                  keep_steps=True)
    state = jax.eval_shape(
        lambda k: (lambda p: PPOState(p, opt.init(p), jnp.int32(0)))(
            net.init(k)), jax.random.PRNGKey(0))

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = jax.tree.map(lambda x: s(x.shape, x.dtype), state)
    t, n = 128, 1024
    rollout = {"obs": s((t, n, 4, 84, 84), jnp.uint8),
               "actions": s((t, n), jnp.int32)}
    for k in ("logp", "values", "adv", "ret"):
        rollout[k] = s((t, n), jnp.float32)
    text = jax.jit(update).lower(state, rollout,
                                 s((2,), jnp.uint32)).compile().as_text()
    kernels = re.findall(r"%(\w+)\.\d+ = \S+ custom-call\(.*"
                         + KERNEL, text)
    assert kernels == ["minibatch_obs"], kernels
    assert "convert_multiply" not in text
    operand = 32768 * 4 * 84 * 84
    for dims in re.findall(r"= \w+\[([\d,]+)\]\{[^}]*\} copy\(", text):
        assert np.prod([int(d) for d in dims.split(",")]) != operand, dims
