"""The selection rule's plain reference (``bench/lib/sched_ref.py``) on
served sequences made by hand: a sequence that follows the rule reads no
mismatch, and one lane swapped for a lane the rule ranks above it reads
one."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.lib import sched_ref  # noqa: E402

N, M, BLOCKS = 32, 8, 40


def served_sequence(schedule: str, shards: int, seed: int):
    """Blocks of served lane ids and the cost each served lane was sent
    into: the first ``N / M`` blocks serve every lane once, in order; each
    later block serves each shard's lowest ``M / shards`` by the rule."""
    rng = np.random.default_rng(seed)
    ids = np.zeros((BLOCKS, M), np.int64)
    cost_sent = rng.integers(5, 10, (BLOCKS, M)).astype(np.float32)
    last = np.full(N, -1)
    cost = np.zeros(N, np.float32)
    n, m = N // shards, M // shards
    for r in range(BLOCKS):
        if r < N // M:
            ids[r] = np.arange(r * M, (r + 1) * M)
        else:
            age = (r - 1 - last).astype(np.float32)
            if schedule == "hierarchical":
                prio = sched_ref.hierarchical_priority(cost, age, shards, m)
            else:
                prio = cost - age
            ids[r] = np.concatenate([
                s * n + np.argsort(prio[s * n:(s + 1) * n], kind="stable")[:m]
                for s in range(shards)])
        last[ids[r]] = r
        cost[ids[r]] = cost_sent[r]
    return ids, cost_sent


@pytest.mark.parametrize("schedule,shards", [("fifo", 1), ("hierarchical", 2),
                                             ("hierarchical", 4)])
@pytest.mark.parametrize("seed", [0, 1])
def test_rule_following_sequence_reads_no_mismatch(schedule, shards, seed):
    ids, cost = served_sequence(schedule, shards, seed)
    checked, bad = sched_ref.selection_mismatches(ids, cost, N, schedule,
                                                  shards)
    assert checked == BLOCKS - N // M
    assert bad == 0


@pytest.mark.parametrize("schedule,shards", [("fifo", 1), ("hierarchical", 2)])
def test_swapped_lane_reads_a_mismatch(schedule, shards):
    ids, cost = served_sequence(schedule, shards, 2)
    r = BLOCKS - 1
    # serve in the last block the lane of its shard that waited least
    # instead of the one that waited most
    n, m = N // shards, M // shards
    block = ids[r, :m]
    last = {int(lane): b for b in range(r) for lane in ids[b]}
    unserved = [lane for lane in range(n) if lane not in block]
    fresh = max(unserved, key=lambda lane: (last[lane], lane))
    oldest = min(block, key=lambda lane: last[int(lane)])
    ids[r, list(block).index(oldest)] = fresh
    _, bad = sched_ref.selection_mismatches(ids, cost, N, schedule, shards)
    assert bad == 1


def test_ties_at_the_cut_may_go_either_way():
    prio = np.array([1.0, 2.0, 2.0, 3.0], np.float32)
    assert not sched_ref.violates(prio, np.array([0, 1]), 2)
    assert not sched_ref.violates(prio, np.array([0, 2]), 2)
    assert sched_ref.violates(prio, np.array([1, 2]), 2)
    assert sched_ref.violates(prio, np.array([0, 3]), 2)
