"""Subprocess helper for tests/test_obs.py.

The tier-1 suite runs on ONE device (conftest harness contract), so the
multi-device telemetry assertions run here, in a fresh interpreter that
forces D simulated host devices before jax locks the platform.  Checks:

  * ``stats()`` snapshots of the same scripted sync rollout are
    EXACTLY equal across mesh sizes {1, 2, D} and vs the single-device
    engine — the per-shard counters are integer partial sums, so the
    host-side cross-shard sum is bitwise mesh-size-invariant (the
    telemetry contract in core/protocol.py);
  * async stats at mesh D stay conserved: ``served == recvs * M``,
    ``stepped <= served``, per-lane serves sum to ``served``;
  * the hierarchical scheduler's ``overdue_admits`` counter is wired
    through ``select_info`` at a real mesh (TokenSkew async forces the
    overdue band to fire).

Prints one JSON object; the parent test asserts on it.

Usage: python tests/_obs_mesh_check.py [D]
"""

import json
import sys

from repro.launch.mesh import force_host_device_count

D = int(sys.argv[1]) if len(sys.argv) > 1 else 4
# the helper drops any inherited device-count override (e.g. the
# 512-device flag the dryrun tests export into the parent's os.environ)
force_host_device_count(D)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.registry import make  # noqa: E402
from repro.obs.telemetry import stats_to_jsonable  # noqa: E402

TASK = "TokenCopy-v0"
N = 8      # divisible by every mesh size in {1, 2, 4}
STEPS = 6
SEED = 0


def rollout_stats(engine: str, m=None, **kw) -> dict:
    """Scripted rollout; returns the JSON-safe stats() snapshot."""
    pool = make(TASK, num_envs=N, batch_size=m, engine=engine, seed=SEED,
                **kw)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    for t in range(STEPS):
        ids = np.asarray(ts.env_id)
        a = jnp.asarray(((ids * 7 + t) % 256).astype(np.int32))
        ps, ts = step(ps, a, ts.env_id)
    return stats_to_jsonable(pool.stats(ps))


def main() -> dict:
    res: dict = {"devices": len(jax.devices()), "mesh": D}
    meshes = sorted({1, 2, D})

    # sync: full-dict exact equality across mesh sizes and vs device
    ref = rollout_stats("device")
    ok = True
    for d in meshes:
        ok &= rollout_stats("device-sharded", num_shards=d) == ref
    res["sync_stats_bitwise_all_meshes"] = bool(ok)

    # async at mesh D: serving order is mesh-dependent, but the counter
    # conservation laws are not
    s = rollout_stats("device-sharded", m=4, num_shards=D)
    res["async_served_conserved"] = s["served"] == s["recvs"] * 4
    res["async_serves_sum"] = int(sum(s["serves"])) == s["served"]
    res["async_stepped_bounded"] = 0 <= s["stepped"] <= s["served"]
    res["async_hist_conserved"] = int(sum(s["wait_hist"])) == s["served"]

    # hierarchical overdue band on the skew workload at a real mesh
    pool = make("TokenSkew-v0", num_envs=N, batch_size=4,
                engine="device-sharded", num_shards=D,
                schedule="hierarchical", sched_patience=2, seed=SEED)
    ps, ts = pool.reset(jax.random.PRNGKey(SEED))
    step = jax.jit(pool.step)
    for t in range(24):
        ids = np.asarray(ts.env_id)
        a = jnp.asarray(((ids * 7 + t) % 256).astype(np.int32))
        ps, ts = step(ps, a, ts.env_id)
    hs = pool.stats(ps)
    res["hier_overdue_counted"] = int(hs["overdue_admits"]) > 0
    return res


if __name__ == "__main__":
    print(json.dumps(main()))
