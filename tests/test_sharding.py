"""Sharding resolver properties + dry-run machinery units."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container has no hypothesis
    from _propshim import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    BASELINE_RULES,
    SP_RULES,
    make_shard_fn,
    param_logical_axes,
    param_shardings,
    resolve,
)


@pytest.fixture(scope="module")
def mesh():
    # 1-device "mesh" with the production axis names: divisibility logic
    # still exercised (extent 1 divides everything)
    from repro.launch.mesh import make_debug_mesh

    return make_debug_mesh(1)


def test_resolve_basic(mesh):
    spec = resolve(mesh, (16, 32), ("batch", "mlp"), BASELINE_RULES)
    assert isinstance(spec, P)


@given(
    size=st.integers(1, 4096),
    extent=st.sampled_from([2, 4, 8, 16]),
)
@settings(max_examples=30, deadline=None)
def test_resolve_divisibility_fallback(size, extent):
    """A dim not divisible by the mapped mesh extent must fall back to
    replication — never a compile error."""
    devs = jax.devices() * extent  # fake: same device repeated
    import numpy as _np
    mesh = jax.sharding.Mesh(
        _np.array(devs[:extent]).reshape(1, extent), ("data", "model")
    )
    spec = resolve(mesh, (size,), ("mlp",), BASELINE_RULES)
    if size % extent == 0:
        assert spec == P("model")
    else:
        assert spec == P(None)


def test_resolve_no_axis_reuse(mesh):
    """The same mesh axis must not shard two dims of one tensor."""
    import numpy as _np
    devs = jax.devices() * 4
    m = jax.sharding.Mesh(_np.array(devs[:4]).reshape(2, 2), ("data", "model"))
    spec = resolve(m, (4, 4), ("mlp", "mlp"), BASELINE_RULES)
    assert spec[0] == "model" and spec[1] is None


def test_param_logical_axes_cover_all_archs():
    """Every parameter of every smoke arch gets a valid logical tuple."""
    from repro.configs import get_smoke_config, list_archs
    from repro.models import build_model

    for arch in list_archs():
        model = build_model(get_smoke_config(arch))
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        axes = param_logical_axes(params)
        for leaf, ax in zip(jax.tree.leaves(params), jax.tree.leaves(
                axes, is_leaf=lambda x: isinstance(x, tuple))):
            assert len(ax) == leaf.ndim, (arch, leaf.shape, ax)


def test_param_shardings_tp_axes():
    """The big matmul weights must actually be model/TP-sharded."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    import numpy as _np

    devs = jax.devices() * 2
    mesh = jax.sharding.Mesh(_np.array(devs[:2]).reshape(1, 2),
                             ("data", "model"))
    model = build_model(get_smoke_config("llama3.2-3b"))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sh = param_shardings(mesh, params, BASELINE_RULES)
    wq_spec = sh["layers"]["attn"]["wq"].spec
    assert "model" in str(wq_spec), wq_spec
    # norms replicated (stacked layer dim + feature dim, no mesh axes)
    norm_spec = sh["layers"]["attn_norm"]["scale"].spec
    assert all(a is None for a in norm_spec), norm_spec


def test_shard_fn_noop_without_mesh():
    shard = make_shard_fn(None, BASELINE_RULES)
    x = jnp.ones((4, 4))
    assert shard(x, ("batch", "mlp")) is x


def test_shard_fn_in_jit(mesh):
    shard = make_shard_fn(mesh, BASELINE_RULES)

    @jax.jit
    def f(x):
        return shard(x * 2, ("batch", "mlp"))

    out = f(jnp.ones((4, 8)))
    np.testing.assert_allclose(out, 2 * np.ones((4, 8)))


def test_collective_parser():
    from repro.launch.dryrun import parse_collectives

    hlo = """
  %param.1 = f32[1024]{0} parameter(0)
  %add.2 = f32[1024]{0} add(f32[1024]{0} %param.1, f32[1024]{0} %param.1)
  %all-reduce.3 = f32[1024]{0} all-reduce(%add.2), replica_groups={}
  %ag.4 = bf16[64,128]{1,0} all-gather(%conv.9), dimensions={0}
  %conv.9 = bf16[8,128]{1,0} convert(%param.1)
"""
    out = parse_collectives(hlo)
    assert out["all-reduce"]["count"] == 1
    assert out["all-reduce"]["operand_bytes"] == 4096
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["result_bytes"] == 64 * 128 * 2
    assert out["all-gather"]["operand_bytes"] == 8 * 128 * 2


def test_sp_rules_shard_seq():
    assert SP_RULES.get("seq") == "model"
    assert BASELINE_RULES.get("seq") is None


def test_policy_shardings_replicates_small_and_shards_large():
    """Seed-RL placement for the device-resident PPO loop: small policy
    nets replicate over the env mesh, large ones shard their largest
    divisible dim; never a divisibility compile error."""
    import numpy as _np

    from repro.distributed.sharding import policy_shardings

    devs = jax.devices() * 4
    mesh = jax.sharding.Mesh(_np.array(devs[:4]), ("env",))

    small = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
    sh = policy_shardings(mesh, small, axis_name="env")
    assert all(s.spec == P() for s in jax.tree.leaves(
        sh, is_leaf=lambda x: hasattr(x, "spec")))

    big = {
        "w": jax.ShapeDtypeStruct((2048, 1024), jnp.float32),
        "b": jax.ShapeDtypeStruct((1024,), jnp.float32),
        "odd": jax.ShapeDtypeStruct((7, 3), jnp.float32),   # indivisible
    }
    sh = policy_shardings(mesh, big, axis_name="env")
    assert sh["w"].spec == P("env", None)
    assert sh["b"].spec == P("env")
    assert sh["odd"].spec == P()          # divisibility fallback

    # the degenerate 1-shard mesh always replicates
    mesh1 = jax.sharding.Mesh(_np.array(jax.devices()[:1]), ("env",))
    sh = policy_shardings(mesh1, big, axis_name="env")
    assert all(s.spec == P() for s in jax.tree.leaves(
        sh, is_leaf=lambda x: hasattr(x, "spec")))
