"""In-graph engine counters — the ``Telemetry`` pytree on ``PoolState``.

The device engines cannot be profiled from the host without breaking
their own thesis (the state never leaves the mesh), so the engine
counts itself: a fixed-size pytree of integer counters rides on
``PoolState`` exactly like ``tf_state`` and is updated INSIDE the
jitted ``_recv_topm`` body.
Counters cross to the host only on an explicit ``pool.stats()``
snapshot — never on the hot path.

Mesh-safety rules (the NormalizeObs discipline, see
``core/protocol.py``):

  * per-lane counters (``serves``, ``wait_ticks``) are ``(N,)`` leaves
    partitioned over the mesh axis with the env states — each lane's
    counters depend only on its own stream, so they are mesh-size
    invariant by layout;
  * per-shard counters (``wait_hist``, ``served``, ``stepped``,
    ``cost_sum``, ``overdue_admits``) are fixed-size partial sums,
    summed across shards at ``stats()`` time on the host.  All
    counters are integers, so the cross-shard sum is associative and
    the snapshot is **bitwise** mesh-size-invariant at every D — no
    collectives are ever issued for telemetry (statistics would psum;
    counters don't even need that);
  * nothing feeds back into env math, scheduling, or RNG — the served
    streams (and the fifo/atari goldens) stay bitwise-unchanged with
    telemetry enabled.

``HostTelemetry`` is the numpy mirror for the thread/forloop/
subprocess engines: the same counters with the same semantics, so
``stats()`` is engine-conformant — identical values for the same
scripted rollout on every engine (tests/test_obs.py).

Counter semantics (shared by both implementations):

  * ``serves[i]``      — times lane ``i`` was served in a recv block
    (reset results count: a serve is a served result, stepped or not);
  * ``wait_ticks[i]``  — cumulative recv-ticks lane ``i``'s results
    waited between their action being enqueued and being served;
  * ``wait_hist``      — fixed-edge histogram of those per-serve waits
    (edges ``WAIT_EDGES``, last bucket open-ended);
  * ``served``         — total served result slots (recvs x M);
  * ``stepped``        — served results produced by an actual env step
    (``served - stepped`` = reset/re-served READY slots; their ratio
    is the served-block occupancy);
  * ``cost_sum``       — total substeps (``step_cost``) of stepped
    results — the engine's real simulated work;
  * ``overdue_admits`` — lanes admitted through the hierarchical
    scheduler's overdue band (0 under fifo/sjf).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np

from repro.obs.trace import TELEMETRY
from repro.utils.pytree import pytree_dataclass

# fixed histogram edges (recv ticks waited): bucket b counts waits in
# [WAIT_EDGES[b], WAIT_EDGES[b+1]); the last bucket is open-ended.
WAIT_EDGES: tuple[int, ...] = (0, 1, 2, 4, 8, 16, 32, 64)
NUM_BUCKETS = len(WAIT_EDGES)

# telemetry leaves that carry the per-shard (D, ...) dim on the pool-
# level PoolState (everything except the per-lane (N,) counters)
PER_SHARD_FIELDS = (
    "wait_hist", "served", "stepped", "cost_sum", "overdue_admits"
)


@pytree_dataclass
class Telemetry:
    """The in-graph counters (local per-shard view; all int32)."""

    serves: Any          # (N,) per-lane serve count
    wait_ticks: Any      # (N,) per-lane cumulative queue-wait ticks
    wait_hist: Any       # (NUM_BUCKETS,) fixed-edge wait histogram
    served: Any          # ()  served result slots
    stepped: Any         # ()  served results backed by an env step
    cost_sum: Any        # ()  substep cost sum over stepped results
    overdue_admits: Any  # ()  hierarchical overdue-band admissions


def init_telemetry(num_envs: int) -> Telemetry:
    """Fresh local-view counters for one shard's ``num_envs`` lanes."""
    import jax.numpy as jnp

    n = int(num_envs)
    return Telemetry(
        serves=jnp.zeros((n,), jnp.int32),
        wait_ticks=jnp.zeros((n,), jnp.int32),
        wait_hist=jnp.zeros((NUM_BUCKETS,), jnp.int32),
        served=jnp.int32(0),
        stepped=jnp.int32(0),
        cost_sum=jnp.int32(0),
        overdue_admits=jnp.int32(0),
    )


def telemetry_local(t: Telemetry) -> Telemetry:
    """Strip the (1,) shard dim from per-shard leaves (entering
    shard_map) — the ``_local_view`` move for telemetry."""
    return t.replace(**{f: getattr(t, f)[0] for f in PER_SHARD_FIELDS})


def telemetry_shard(t: Telemetry) -> Telemetry:
    """Inverse: re-add the leading per-shard dim (leaving shard_map)."""
    return t.replace(**{f: getattr(t, f)[None] for f in PER_SHARD_FIELDS})


def _hist_counts(wait):
    """Per-bucket counts of one block's waits, scatter-free: a
    duplicate-index ``at[buckets].add(1)`` scatter serializes on XLA CPU
    and dominated the instrumented hot loop (~8% of the whole sync
    collect); the dense (M, B) compare + column sum fuses instead.
    ``count[b] = #(wait >= edge[b]) - #(wait >= edge[b+1])``."""
    import jax.numpy as jnp

    edges = jnp.asarray(WAIT_EDGES, jnp.int32)
    cum = jnp.sum(
        wait[:, None] >= edges[None, :], axis=0
    ).astype(jnp.int32)
    return cum - jnp.concatenate([cum[1:], jnp.zeros((1,), jnp.int32)])


def _lane_counts(idx, wait, num_envs):
    """Per-lane (serve count, wait-tick sum) for one served block,
    scatter-free for the same reason as ``_hist_counts``: the two
    ``at[idx].add`` lane scatters were the next-largest instrumented
    cost after the histogram.  The (M, N) one-hot compare fuses with
    the surrounding block instead."""
    import jax.numpy as jnp

    onehot = jnp.arange(num_envs, dtype=idx.dtype)[None, :] == idx[:, None]
    return (
        jnp.sum(onehot, axis=0, dtype=jnp.int32),
        jnp.sum(jnp.where(onehot, wait[:, None], 0), axis=0,
                dtype=jnp.int32),
    )


@jax.named_scope(TELEMETRY)
def record_serve(
    tele: Telemetry,
    idx,            # (M,) served lane indices
    wait,           # (M,) int ticks waited by each served result
    stepped_mask,   # (M,) bool — result backed by an env step
    step_cost,      # (M,) int substep cost (counted where stepped)
    overdue_admits, # ()  int32 overdue-band admissions this recv
    full_block: bool = False,  # static: block serves ALL lanes and
                               # ``wait`` is in LANE order (sync mode)
) -> Telemetry:
    """One recv block's counter update (pure; runs inside the jitted
    per-shard recv body).  Fixed shapes only — no env data touched.

    ``full_block=True`` is the sync-mode fast path: ``idx`` is a
    permutation of all N lanes (the engine's selection never repeats a
    lane within a block), so the per-lane counters reduce to full-
    vector adds — no one-hot needed.  The caller must then pass
    ``wait`` in lane order (``tick - send_tick``, ungathered); the
    histogram and the block sums are order-invariant either way, so
    the counters are bitwise identical to the gathered path."""
    import jax.numpy as jnp

    wait = wait.astype(jnp.int32)
    if full_block:
        d_serves = jnp.int32(1)
        d_wait = wait
    else:
        d_serves, d_wait = _lane_counts(idx, wait, tele.serves.shape[0])
    return tele.replace(
        serves=tele.serves + d_serves,
        wait_ticks=tele.wait_ticks + d_wait,
        wait_hist=tele.wait_hist + _hist_counts(wait),
        served=tele.served + jnp.int32(idx.shape[0]),
        stepped=tele.stepped + jnp.sum(stepped_mask.astype(jnp.int32)),
        cost_sum=tele.cost_sum + jnp.sum(
            jnp.where(stepped_mask, step_cost.astype(jnp.int32), 0)
        ),
        overdue_admits=tele.overdue_admits
        + overdue_admits.astype(jnp.int32),
    )


# --------------------------------------------------------------------- #
# host-side snapshot formatting (ONE implementation for every engine)
# --------------------------------------------------------------------- #
def format_stats(
    recvs: int,
    serves: np.ndarray,
    wait_ticks: np.ndarray,
    wait_hist: np.ndarray,
    served: int,
    stepped: int,
    cost_sum: int,
    overdue_admits: int,
) -> dict:
    """The ``pool.stats()`` dict — shared by the device snapshot and the
    host mirror so keys and derived values cannot drift."""
    served = int(served)
    stepped = int(stepped)
    return {
        "recvs": int(recvs),
        "served": served,
        "stepped": stepped,
        "occupancy": (stepped / served) if served else 0.0,
        "cost_sum": int(cost_sum),
        "overdue_admits": int(overdue_admits),
        "serves": np.asarray(serves, np.int64),
        "wait_ticks": np.asarray(wait_ticks, np.int64),
        "wait_ticks_total": int(np.asarray(wait_ticks, np.int64).sum()),
        "wait_hist": np.asarray(wait_hist, np.int64),
        "wait_edges": list(WAIT_EDGES),
    }


def snapshot_device(telemetry: Telemetry, tick) -> dict:
    """Host snapshot of a pool-level (sharded-layout) ``Telemetry``:
    per-lane leaves are the global (N,) arrays; per-shard partial sums
    are summed over the leading D dim (integer adds — bitwise mesh-
    size-invariant); ``tick`` is replicated per shard, so shard 0's
    copy IS the global recv count.  This is the ONLY host transfer
    telemetry ever performs."""
    tick = np.asarray(tick)
    return format_stats(
        recvs=int(tick.reshape(-1)[0]),
        serves=np.asarray(telemetry.serves),
        wait_ticks=np.asarray(telemetry.wait_ticks),
        wait_hist=np.asarray(telemetry.wait_hist).sum(axis=0),
        served=int(np.asarray(telemetry.served).sum()),
        stepped=int(np.asarray(telemetry.stepped).sum()),
        cost_sum=int(np.asarray(telemetry.cost_sum).sum()),
        overdue_admits=int(np.asarray(telemetry.overdue_admits).sum()),
    )


def stats_to_jsonable(stats: dict) -> dict:
    """JSON-safe copy of a ``stats()`` dict (arrays -> lists)."""
    return {
        k: v.tolist() if isinstance(v, np.ndarray) else v
        for k, v in stats.items()
    }


class HostTelemetry:
    """Numpy mirror of ``Telemetry`` for the host engines.

    The pool records what it enqueues (``on_enqueue`` tags each lane's
    outstanding work item as a step or a reset) and what it serves
    (``record_block`` once per recv block), so the counters carry the
    exact semantics of the in-graph ones — including the step/reset
    distinction the served block alone cannot reveal.
    """

    def __init__(self, num_envs: int):
        n = int(num_envs)
        self.num_envs = n
        self.serves = np.zeros(n, np.int64)
        self.wait_ticks = np.zeros(n, np.int64)
        self.wait_hist = np.zeros(NUM_BUCKETS, np.int64)
        self.served = 0
        self.stepped = 0
        self.cost_sum = 0
        self.overdue_admits = 0
        self.tick = 0
        self._send_tick = np.zeros(n, np.int64)
        self._kind_step = np.zeros(n, bool)

    def on_enqueue(self, env_ids, stepped: bool) -> None:
        """Lanes received work (an action, or a reset when ``stepped``
        is False) at the current tick."""
        ids = np.asarray(env_ids, np.int64)
        self._send_tick[ids] = self.tick
        self._kind_step[ids] = stepped

    def record_block(self, env_ids, step_cost) -> None:
        """One recv block was served; advances the tick (the host
        mirror of ``Scheduler.complete``)."""
        ids = np.asarray(env_ids, np.int64)
        wait = self.tick - self._send_tick[ids]
        self.serves[ids] += 1
        self.wait_ticks[ids] += wait
        buckets = np.sum(
            wait[:, None] >= np.asarray(WAIT_EDGES[1:], np.int64)[None, :],
            axis=1,
        )
        np.add.at(self.wait_hist, buckets, 1)
        self.served += int(ids.size)
        stepped = self._kind_step[ids]
        self.stepped += int(stepped.sum())
        self.cost_sum += int(
            np.asarray(step_cost, np.int64)[stepped].sum()
        )
        self.tick += 1

    def snapshot(self) -> dict:
        return format_stats(
            recvs=self.tick,
            serves=self.serves,
            wait_ticks=self.wait_ticks,
            wait_hist=self.wait_hist,
            served=self.served,
            stepped=self.stepped,
            cost_sum=self.cost_sum,
            overdue_admits=self.overdue_admits,
        )


__all__ = [
    "NUM_BUCKETS",
    "PER_SHARD_FIELDS",
    "WAIT_EDGES",
    "HostTelemetry",
    "Telemetry",
    "format_stats",
    "init_telemetry",
    "record_serve",
    "snapshot_device",
    "stats_to_jsonable",
    "telemetry_local",
    "telemetry_shard",
]
