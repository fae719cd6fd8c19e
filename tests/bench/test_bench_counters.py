"""The benchmark's operation and byte counters (``bench/metrics``)
against XLA's own ``cost_analysis()`` of the program's computations, at
a small size on the CPU."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import catalog  # noqa: E402


def xla_cost(f, *args) -> dict:
    c = jax.jit(f).lower(*args).compile().cost_analysis()
    return c[0] if isinstance(c, list) else c


def test_env_step_counter_matches_xla():
    from repro.kernels.env_step.ref import env_substep_reference

    env = catalog.metric("env_step_roofline.sim")
    n = 1024
    c = xla_cost(lambda s, a: env_substep_reference(s, a),
                 jnp.zeros((n, 28)), jnp.zeros((n, 8)))
    per_lane = (c["flops"] + c.get("transcendentals", 0.0)) / n
    # one unmasked substep plus the masked loop's 29 selects
    assert env.SUBSTEP_OPS == pytest.approx(per_lane + 29, rel=0.1)
    assert env.LANE_BYTES == 268


def test_roofline_readers_pick_the_binding_bound():
    from bench.lib.trace import Event, Trace

    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    tr = Trace(ops={"/device:TPU:0": [Event("env_multi_step.1", 0.0, 1e6)]},
               modules={}, host=[], window=(0.0, 2e6))
    env = catalog.metric("env_step_roofline.sim")
    counts = {"stepped": 1_000_000, "substeps": 7_000_000, "peaks": peaks}
    want = 100 * (1e6 * 268 / 819e9) / 1e-3
    assert env.read(tr, counts) == pytest.approx(want)
    assert env.read(tr, dict(counts, stepped=0)) is None
