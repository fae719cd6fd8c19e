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

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.env_step.ops import env_multi_step, env_step
from repro.kernels.image import ops as image

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
