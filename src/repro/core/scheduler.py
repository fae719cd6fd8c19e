"""Pluggable async scheduler — THE selection policy behind every engine.

EnvPool's async mode (``batch_size M < num_envs N``) is a scheduling
problem: each ``recv`` must pick the M lanes to serve next, and under
heterogeneous step cost the pick *is* a first-order throughput lever
(Sample Factory's lesson).  Every engine consumes one functional
contract:

  * ``SchedState`` — a pytree of the per-lane scheduling signals
    (phase / predicted cost / enqueue tick / global tick).  The device
    engines alias it onto the matching ``PoolState`` fields; the host
    engine mirrors it in numpy.
  * ``enqueue(ss, lane_ids, costs)`` — lanes received an action.
  * ``select(ss, m)`` — the M lanes to serve this recv (policy-defined).
  * ``complete(ss, idx)`` — served lanes go back to WAITING, tick += 1.

Policies
--------
``fifo`` (default)
    Bitwise-preserves the pre-scheduler engine behavior: READY lanes
    first in enqueue order, then HAS_ACTION by predicted cost minus
    queue age (SJF softened by aging so nobody starves), WAITING last.
``sjf``
    Pure shortest-job-first on the per-lane cost signal (ties broken by
    lane index via ``top_k`` stability).  Maximizes served-steps/sec on
    long-tail workloads by construction — and by construction it
    *starves* persistently expensive lanes while cheap work exists.
    Use it when throughput of the cheap majority is the objective.
``hierarchical``
    The sharded policy (cost-aware hierarchical top-M).  Each shard
    nominates its ``C = min(n_local, 2*m)`` cheapest serviceable lanes
    with their costs; one ``lax.all_gather`` of that fixed-size (D, C)
    cost matrix — never of env data — lets every shard compute the same
    global admission threshold ``tau`` (the cost of the M-th cheapest
    nominee), which implicitly assigns per-shard quotas: a shard's
    admitted lanes are exactly its nominees among the global top-M.
    Lanes above ``tau`` are deferred; a deferred lane within one
    rotation (n/m ticks) of its ``patience * cost`` deadline jumps to an
    overdue band served ahead of everything but READY — and since its
    near-due peers jump with it, expensive lanes are served in grouped,
    cross-shard-aligned bursts (one block-max-cost hit amortized over a
    whole heavy block) instead of each poisoning a cheap block one lane
    per tick.  Hot shards are never
    starved: selection is still a local top-M over priority bands, so a
    shard whose lanes are all deferred simply serves its cheapest m.

jit / shard_map safety rules
----------------------------
Every method is a pure function of its array arguments with static
shapes — safe under ``jit``, ``vmap``, ``lax.scan`` and ``shard_map``:

  * no Python branching on traced values; priorities are encoded as one
    f32 band ordering resolved by a single ``lax.top_k``;
  * ``select`` always returns exactly ``m`` indices (a static shape) —
    "fewer than m serviceable" is a caller-level contract violation,
    not a dynamic case;
  * only ``HierarchicalScheduler`` communicates, and only via one
    ``lax.all_gather`` of a fixed-size cost matrix inside the caller's
    ``shard_map`` (set ``axis_name`` to the mesh axis); it must not be
    used outside a mapped context;
  * nothing here reads host state, time, or RNG — identical inputs give
    identical selections on every shard and every mesh size.

``numpy_priority`` mirrors the policy formulas for the host engine:
``ThreadEnvPool`` orders its work queue by the same bands (``fifo``
keeps the caller's enqueue order — the host pool's native completion
semantics — so host fifo behavior is also bitwise-preserved).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs.trace import SELECT
from repro.utils.pytree import pytree_dataclass

# lane phases (shared with the device pool; duplicated values would skew)
WAITING_ACTION = 0   # result consumed; agent owes us an action
HAS_ACTION = 1       # action stored; step not yet executed
READY = 2            # unconsumed result available

_BIG = jnp.float32(1e9)   # fifo/sjf WAITING band (pre-refactor value,
                          # kept bitwise) / unserviceable sentinel
# hierarchical band layout.  Offsets are powers of two small enough that
# f32 still resolves ±1 cost/age increments *within* a band (ulp(2^20)
# = 0.125; a 1e9-style offset would swallow them, ulp(1e9) = 64), and
# within-band values are clipped to ±_CAP so no band can bleed into its
# neighbor: READY(-2^22) < overdue(-2^20) < admitted(0) < deferred(2^20)
# < WAITING(2^22).
_CAP = jnp.float32(2 ** 19)
_BAND = jnp.float32(2 ** 20)
_EDGE = jnp.float32(2 ** 22)

SCHEDULES = ("fifo", "sjf", "hierarchical")


@pytree_dataclass
class SchedState:
    """Per-lane scheduling signals (all shapes static under jit).

    The device engines build this as a *view* of the matching
    ``PoolState`` fields and write the results back, so there is one
    source of truth for lane phase bookkeeping.
    """

    phase: jnp.ndarray      # (N,) int32 — WAITING_ACTION / HAS_ACTION / READY
    cost: jnp.ndarray       # (N,) int32 predicted cost of the pending step
    send_tick: jnp.ndarray  # (N,) int32 tick the action was enqueued
    tick: jnp.ndarray       # ()  int32 global recv counter


class Scheduler:
    """Functional scheduling policy: pure functions over ``SchedState``."""

    name: str = "base"
    # True for policies that communicate across a mapped mesh axis and
    # therefore only work inside shard_map (registry/engine validation)
    needs_axis: bool = False

    # ------------------------------------------------------------------ #
    # shared primitives
    # ------------------------------------------------------------------ #
    def init(self, num_envs: int) -> SchedState:
        """Fresh pool: every lane READY (async_reset semantics)."""
        n = int(num_envs)
        return SchedState(
            phase=jnp.full((n,), READY, jnp.int32),
            cost=jnp.zeros((n,), jnp.int32),
            send_tick=jnp.zeros((n,), jnp.int32),
            tick=jnp.int32(0),
        )

    @jax.named_scope(SELECT)
    def enqueue(self, ss: SchedState, lane_ids: jnp.ndarray,
                costs: jnp.ndarray) -> SchedState:
        """Lanes ``lane_ids`` received an action with predicted ``costs``."""
        lane_ids = lane_ids.astype(jnp.int32)
        return ss.replace(
            phase=ss.phase.at[lane_ids].set(HAS_ACTION),
            cost=ss.cost.at[lane_ids].set(costs.astype(jnp.int32)),
            send_tick=ss.send_tick.at[lane_ids].set(ss.tick),
        )

    @jax.named_scope(SELECT)
    def select(self, ss: SchedState, m: int) -> jnp.ndarray:
        """The ``m`` lanes to serve this recv (lowest priority value
        first).  Never returns a WAITING lane while ≥ m serviceable
        (READY or HAS_ACTION) lanes exist — the band encoding keeps
        every serviceable priority strictly below the WAITING band."""
        _, idx = lax.top_k(-self.priority(ss), m)
        return idx.astype(jnp.int32)

    @jax.named_scope(SELECT)
    def select_info(self, ss: SchedState, m: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        """``(idx, overdue_admits)`` — the selection plus the scalar
        int32 count of lanes admitted through an overdue/deadline band
        this recv (the telemetry signal, ``obs/telemetry.py``).  The
        base policies have no deadline band, so the count is 0 and
        ``idx`` is exactly ``select``'s — the engine can call this
        unconditionally without perturbing fifo/sjf selections."""
        return self.select(ss, m), jnp.int32(0)

    @jax.named_scope(SELECT)
    def complete(self, ss: SchedState, idx: jnp.ndarray) -> SchedState:
        """Served lanes go back to WAITING; the global tick advances."""
        return ss.replace(
            phase=ss.phase.at[idx].set(WAITING_ACTION), tick=ss.tick + 1
        )

    # ------------------------------------------------------------------ #
    # policy surface
    # ------------------------------------------------------------------ #
    def priority(self, ss: SchedState) -> jnp.ndarray:
        """(N,) f32, lower = served earlier.  Must keep READY lanes below
        every HAS_ACTION lane and WAITING lanes above everything."""
        raise NotImplementedError


class FifoScheduler(Scheduler):
    """The pre-scheduler engine policy, preserved bitwise: READY first
    (completion order ~ FIFO), then HAS_ACTION by predicted cost minus
    queue age (SJF + aging; aging makes queue-time lower effective
    priority, so nobody starves), WAITING last."""

    name = "fifo"

    def __init__(self, aging: float = 1.0):
        self.aging = float(aging)

    def priority(self, ss: SchedState) -> jnp.ndarray:
        age = (ss.tick - ss.send_tick).astype(jnp.float32)
        ready_p = -_BIG + ss.send_tick.astype(jnp.float32)
        has_p = ss.cost.astype(jnp.float32) - self.aging * age
        wait_p = _BIG
        return jnp.where(
            ss.phase == READY,
            ready_p,
            jnp.where(ss.phase == HAS_ACTION, has_p, wait_p),
        )


class SjfScheduler(Scheduler):
    """Pure shortest-job-first on the per-lane cost signal.

    No aging: while cheap lanes keep rejoining the queue, persistently
    expensive lanes are never served (documented starvation tradeoff —
    the throughput ceiling for the cheap majority).  Equal-cost lanes
    rotate only through phase changes; ties break by lane index
    (``top_k`` stability), which is what makes the policy deterministic.
    """

    name = "sjf"

    def priority(self, ss: SchedState) -> jnp.ndarray:
        ready_p = -_BIG + ss.send_tick.astype(jnp.float32)
        return jnp.where(
            ss.phase == READY,
            ready_p,
            jnp.where(
                ss.phase == HAS_ACTION, ss.cost.astype(jnp.float32), _BIG
            ),
        )


class HierarchicalScheduler(Scheduler):
    """Cost-aware hierarchical top-M for the sharded pool (module
    docstring has the full story).  Runs *inside* the caller's
    ``shard_map``: ``select`` all-gathers one fixed-size per-shard
    candidate cost matrix over ``axis_name`` and every shard derives the
    same admission threshold from it.

    Bands (low→high): READY < overdue < admitted (cost ≤ tau, SJF with
    aging) < deferred (cost > tau) < WAITING.  ``patience`` scales how
    many ticks a deferred lane of cost c waits (due at ``age ≥
    patience * c``, joined one n/m-tick rotation early for burst
    grouping) before the overdue band guarantees service — the
    anti-starvation quota floor.
    """

    name = "hierarchical"
    needs_axis = True

    def __init__(self, axis_name: str, num_shards: int,
                 aging: float = 1.0, patience: float = 1.0):
        self.axis_name = axis_name
        self.num_shards = int(num_shards)
        self.aging = float(aging)
        self.patience = float(patience)

    def _tau(self, ss: SchedState, m: int) -> jnp.ndarray:
        """Global admission cost: the (D*m)-th cheapest nominated lane
        across all shards (one all-gather of a (D, C) f32 matrix)."""
        n = ss.phase.shape[0]
        c = min(n, 2 * m)
        eff = jnp.where(
            ss.phase == HAS_ACTION, ss.cost.astype(jnp.float32), _BIG
        )
        neg_cand, _ = lax.top_k(-eff, c)              # local C cheapest
        cands = lax.all_gather(-neg_cand, self.axis_name)  # (D, C)
        flat = cands.reshape(-1)
        neg_top, _ = lax.top_k(-flat, self.num_shards * m)
        return -neg_top[-1]                           # (D*m)-th smallest

    def select(self, ss: SchedState, m: int) -> jnp.ndarray:
        return self.select_info(ss, m)[0]

    @jax.named_scope(SELECT)
    def select_info(self, ss: SchedState, m: int
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
        tau = self._tau(ss, m)
        age = (ss.tick - ss.send_tick).astype(jnp.float32)
        cost = ss.cost.astype(jnp.float32)
        serviceable = ss.phase == HAS_ACTION

        admitted = serviceable & (cost <= tau)
        # burst grouping: a *deferred* (above-tau) lane joins the
        # overdue band up to one full rotation (n/m ticks) before its
        # deadline, so when the first heavy lane comes due its near-due
        # peers ride the same block instead of trickling out one per
        # tick — one aligned block-max-cost hit rather than a poisoned
        # block per lane.  Admitted lanes never enter the band: it must
        # out-rank them only when a burst is actually due.
        slack = jnp.float32(ss.phase.shape[0] // max(m, 1))
        overdue = serviceable & ~admitted & (
            self.aging * (age + slack) >= self.patience * cost
        )
        # SJF-with-aging inside the overdue and admitted bands, clipped
        # so a band can never bleed into its neighbor (see _CAP note)
        sjf_aged = jnp.clip(cost - self.aging * age, -_CAP, _CAP)
        # band encoding, one top_k resolves it (see class docstring)
        pri = jnp.where(
            ss.phase == READY,
            -_EDGE + jnp.minimum(ss.send_tick.astype(jnp.float32), _CAP),
            jnp.where(
                overdue,
                -_BAND + sjf_aged,
                jnp.where(
                    admitted,
                    sjf_aged,
                    jnp.where(
                        serviceable, _BAND + jnp.minimum(cost, _CAP), _EDGE
                    ),
                ),
            ),
        )
        _, idx = lax.top_k(-pri, m)
        idx = idx.astype(jnp.int32)
        # telemetry signal (obs/telemetry.py): how many of the selected
        # lanes rode the overdue band this recv — a fixed-size scalar
        # derived from masks already computed, no extra comms
        return idx, jnp.sum(overdue[idx].astype(jnp.int32))


# --------------------------------------------------------------------- #
# construction
# --------------------------------------------------------------------- #
def get_scheduler(
    schedule: str | Scheduler = "fifo",
    aging: float = 1.0,
    axis_name: str | None = None,
    num_shards: int | None = None,
    patience: float = 1.0,
) -> Scheduler:
    """Resolve a policy name (or pass through an instance).

    ``hierarchical`` needs the mesh context (``axis_name``/``num_shards``
    — the sharded pool provides them); asking for it anywhere else
    raises, as does an unknown name.  ``patience`` is the hierarchical
    policy's fairness knob (deferred lane of cost c is due at ``age >=
    patience * c``; exposed as ``make(..., sched_patience=...)``) —
    lower is fairer, higher is greedier.  The fifo/sjf policies have no
    deadline band, so the knob is accepted and unused there.
    """
    if isinstance(schedule, Scheduler):
        return schedule
    if patience <= 0:
        raise ValueError(f"patience must be > 0, got {patience}")
    if schedule == "fifo":
        return FifoScheduler(aging=aging)
    if schedule == "sjf":
        return SjfScheduler()
    if schedule == "hierarchical":
        if axis_name is None or num_shards is None:
            raise ValueError(
                "schedule='hierarchical' is the cross-shard policy: it "
                "needs a device mesh (use engine='device-sharded')"
            )
        return HierarchicalScheduler(axis_name, num_shards, aging=aging,
                                     patience=patience)
    raise ValueError(f"unknown schedule {schedule!r}; known: {SCHEDULES}")


# --------------------------------------------------------------------- #
# host (numpy) mirror — ThreadEnvPool work-queue ordering
# --------------------------------------------------------------------- #
def numpy_priority(
    schedule: str,
    cost: np.ndarray,
    send_tick: np.ndarray,
    tick: int,
    aging: float = 1.0,
) -> np.ndarray:
    """Host mirror of the policy priorities for lanes being enqueued.

    Lower = pulled by a worker earlier.  ``fifo`` returns zeros — the
    caller's enqueue order IS the host pool's native FIFO (preserving
    pre-scheduler behavior bitwise); ``sjf`` orders by the last observed
    per-lane cost (the host cost estimator) — like ``SjfScheduler``, no
    aging term (same documented starvation tradeoff).  ``hierarchical``
    is cross-shard only and has no host mirror (``ThreadEnvPool``
    rejects it at construction).  ``send_tick``/``tick``/``aging`` are
    accepted so age-based host policies can slot in without a signature
    change.
    """
    del send_tick, tick, aging
    cost = np.asarray(cost, np.float32)
    if schedule == "fifo":
        return np.zeros_like(cost)
    if schedule == "sjf":
        return cost
    raise ValueError(
        f"no host mirror for schedule {schedule!r}; known: ('fifo', 'sjf')"
    )


__all__ = [
    "HAS_ACTION",
    "READY",
    "SCHEDULES",
    "WAITING_ACTION",
    "FifoScheduler",
    "HierarchicalScheduler",
    "SchedState",
    "Scheduler",
    "SjfScheduler",
    "get_scheduler",
    "numpy_priority",
]
