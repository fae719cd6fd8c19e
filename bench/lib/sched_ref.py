"""Plain reference of the async pool's selection rule, read back from a
served trajectory.

In a collect loop every served lane gets its action at once, so at each
recv every lane holds an action: lane ``i``, last served in block ``j``
with a step of cost ``c_i``, has age ``r - j`` at the recv that serves
block ``r + 1``.  The policies (``fifo``: cost minus age; ``hierarchical``:
the banded cost-aware rule with one global admission cost) rank the
lanes of each shard, and each shard serves its ``m`` lowest.

Ties at the ``m``-th place may go either way, so the check is the rule
itself: every served lane ranks at or below the ``m``-th lowest value of
its shard, and every lane that ranks strictly below it was served.
"""

from __future__ import annotations

import numpy as np

# hierarchical band layout (f32 values, as the policy states them)
CAP = np.float32(2 ** 19)
BAND = np.float32(2 ** 20)


def fifo_priority(cost: np.ndarray, age: np.ndarray,
                  aging: float = 1.0) -> np.ndarray:
    return cost.astype(np.float32) - np.float32(aging) * age.astype(np.float32)


def hierarchical_priority(cost: np.ndarray, age: np.ndarray, shards: int,
                          m: int, aging: float = 1.0,
                          patience: float = 1.0) -> np.ndarray:
    """Priorities of all ``N`` lanes (shard-major) for one recv."""
    n = cost.shape[0] // shards
    c = min(n, 2 * m)
    cost = cost.astype(np.float32)
    age = age.astype(np.float32)
    cand = np.concatenate([np.sort(cost[s * n:(s + 1) * n])[:c]
                           for s in range(shards)])
    tau = np.sort(cand)[shards * m - 1]
    admitted = cost <= tau
    slack = np.float32(n // max(m, 1))
    overdue = ~admitted & (np.float32(aging) * (age + slack)
                           >= np.float32(patience) * cost)
    sjf = np.clip(cost - np.float32(aging) * age, -CAP, CAP)
    return np.where(overdue, -BAND + sjf,
                    np.where(admitted, sjf, BAND + np.minimum(cost, CAP)))


def violates(prio: np.ndarray, served: np.ndarray, m: int) -> bool:
    """True when the served set breaks the lowest-``m`` rule of one
    shard (``prio`` over the shard's lanes, ``served`` local ids)."""
    tau = np.partition(prio, m - 1)[m - 1]
    mask = np.zeros(prio.shape[0], bool)
    mask[served] = True
    return bool(np.any(prio[mask] > tau) or np.any(prio[~mask] < tau))


def selection_mismatches(ids: np.ndarray, send_cost: np.ndarray,
                         num_envs: int, schedule: str, shards: int,
                         aging: float = 1.0, patience: float = 1.0
                         ) -> tuple[int, int]:
    """``(checked, mismatched)`` recvs of one collect call.

    ``ids``: ``(S + 1, M)`` served lane ids, block 0 first.
    ``send_cost``: ``(S + 1, M)`` cost of the step each served lane was
    sent into (NaN where unknown).  A recv is checked once every lane
    has been served in an earlier block of the call and every cost that
    ranks it is known."""
    blocks, m_all = ids.shape
    n_all = num_envs
    last = np.full(n_all, -1)
    cost = np.full(n_all, np.nan, np.float32)
    checked = bad = 0
    m = m_all // shards
    for r in range(blocks - 1):
        last[ids[r]] = r
        cost[ids[r]] = send_cost[r]
        if (last < 0).any() or np.isnan(cost).any():
            continue
        age = (r - last).astype(np.float32)
        if schedule == "hierarchical":
            prio = hierarchical_priority(cost, age, shards, m, aging,
                                         patience)
        else:
            prio = fifo_priority(cost, age, aging)
        n = n_all // shards
        nxt = ids[r + 1]
        for s in range(shards):
            local = nxt[s * m:(s + 1) * m] - s * n
            bad_s = (local.min() < 0 or local.max() >= n
                     or violates(prio[s * n:(s + 1) * n], local, m))
            if bad_s:
                bad += 1
                break
        checked += 1
    return checked, bad
