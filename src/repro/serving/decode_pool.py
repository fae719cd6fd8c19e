"""Continuous-batching decode pool: the scheduler's top-M selection
applied to token generation.

A fixed block of ``num_lanes`` decode lanes plays the role the env pool
plays for episodes: each lane holds one in-flight request's static
per-lane KV-cache row (``rl/policy_lm.LMPolicy`` lane layout), every
``step()`` decodes ONE token for every lane in the block, and admission
swaps fresh prompts into finished lanes — fixed block shapes with
masked lanes, so the jitted programs never recompile as requests
join/leave (the EnvPool batch_size < num_envs idea, applied to
serving).

Two disciplines, one compiled program:

* ``continuous=True`` (default): a lane is re-admitted the moment its
  request finishes — every decode step does useful work on (almost)
  every lane.
* ``continuous=False``: run-to-completion static batching — the next
  batch is admitted only when EVERY lane has finished, so short
  requests idle behind the batch's longest one (the padding waste
  continuous batching exists to reclaim).

The host-side request queue is scheduler-fed: ``schedule="fifo"`` keeps
arrival order, ``"sjf"`` admits shortest-total-work first (the
``core/scheduler.py`` policy vocabulary on the serving axis).
"""

from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs.metrics import MetricsRegistry, publish_serve_stats
from repro.obs.trace import Tracer
from repro.rl.policy_lm import LMPolicy, _select
from repro.utils.pytree import pytree_dataclass


@pytree_dataclass
class ServeLaneState:
    """Per-lane serving state, lane-major SoA (leading dim = num_lanes
    on every leaf — the ``PoolState`` layout)."""

    k: jnp.ndarray        # (N, n_layers, Hkv, T, hd)
    v: jnp.ndarray
    length: jnp.ndarray   # (N,) int32 — valid cache entries
    last_tok: jnp.ndarray  # (N,) int32 — next token to feed
    active: jnp.ndarray   # (N,) bool — lane holds a live request
    req_id: jnp.ndarray   # (N,) int32 — request the lane serves (-1 free)
    n_new: jnp.ndarray    # (N,) int32 — tokens generated so far
    max_new: jnp.ndarray  # (N,) int32 — per-request generation budget


@dataclasses.dataclass
class ServeStats:
    requests: int
    total_tokens: int        # useful generated tokens
    decode_steps: int        # step() invocations (each = num_lanes slots)
    lane_slots: int          # decode_steps * num_lanes
    wall_s: float

    @property
    def utilization(self) -> float:
        return self.total_tokens / max(self.lane_slots, 1)

    @property
    def tokens_per_s(self) -> float:
        return self.total_tokens / max(self.wall_s, 1e-9)


class DecodePool:
    """Continuous-batching decode server over ``num_lanes`` KV-cache
    lanes driven by an ``LMPolicy`` backbone (see module docstring)."""

    def __init__(self, policy: LMPolicy, num_lanes: int, max_new: int,
                 eos_token: int | None = None, schedule: str = "fifo",
                 registry: MetricsRegistry | None = None):
        if schedule not in ("fifo", "sjf"):
            raise ValueError(f"unknown serving schedule {schedule!r}")
        self.policy = policy
        self.num_lanes = int(num_lanes)
        self.max_new = int(max_new)
        self.eos_token = eos_token
        self.schedule = schedule
        # obs/metrics.py sink: every serve() publishes its ServeStats
        # (decode_* counters + utilization/throughput gauges)
        self.registry = registry
        self._jit_step = jax.jit(self._step_impl)
        self._jit_admit = jax.jit(self._admit_impl)

    # ------------------------------ state --------------------------- #
    def init_lanes(self) -> ServeLaneState:
        base = self.policy.init_lanes(self.num_lanes)
        n = self.num_lanes
        return ServeLaneState(
            k=base.k, v=base.v, length=base.length,
            last_tok=jnp.zeros((n,), jnp.int32),
            active=jnp.zeros((n,), bool),
            req_id=jnp.full((n,), -1, jnp.int32),
            n_new=jnp.zeros((n,), jnp.int32),
            max_new=jnp.full((n,), self.max_new, jnp.int32),
        )

    # ---------------------------- admission ------------------------- #
    def _admit_impl(self, params: Any, lanes: ServeLaneState,
                    admit: jnp.ndarray,    # (N,) bool
                    prompts: jnp.ndarray,  # (N, P) int32 (padded)
                    plen: jnp.ndarray,     # (N,) int32
                    req_ids: jnp.ndarray,  # (N,) int32
                    req_max_new: jnp.ndarray,  # (N,) int32
                    ) -> tuple[ServeLaneState, jnp.ndarray]:
        """Prefill admitted lanes and emit their first generated token.

        Prefill-as-decode: the prompt streams through the SAME cached
        ``decode_step`` the hot loop runs, one position per scan step,
        masked by ``j < plen`` — one compiled program for any ragged
        mix of prompt lengths, no per-length recompiles.  Lanes outside
        ``admit`` are scribbled on during the scan and restored from
        the pre-scan cache afterwards (their rows are dead until their
        own re-admission anyway, but restoring keeps this exact)."""
        pol = self.policy
        P = prompts.shape[1]
        k0, v0 = lanes.k, lanes.v

        def one_pos(carry, j):
            kc, vc, first = carry
            live = admit & (j < plen)
            tok = prompts[:, j]
            pos = jnp.where(live, j, 0)
            logits, _, kc, vc = pol.decode_step(params, tok, kc, vc, pos)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            first = jnp.where(admit & (j == plen - 1), nxt, first)
            return (kc, vc, first), None

        first0 = jnp.zeros((self.num_lanes,), jnp.int32)
        (kc, vc, first), _ = lax.scan(
            one_pos, (k0, v0, first0), jnp.arange(P))
        sel = admit[:, None, None, None, None]
        lanes = lanes.replace(
            k=jnp.where(sel, kc, k0),
            v=jnp.where(sel, vc, v0),
            length=jnp.where(admit, plen, lanes.length),
            last_tok=jnp.where(admit, first, lanes.last_tok),
            active=admit | lanes.active,
            req_id=jnp.where(admit, req_ids, lanes.req_id),
            n_new=jnp.where(admit, 1, lanes.n_new),
            max_new=jnp.where(admit, req_max_new, lanes.max_new),
        )
        return lanes, first

    # ------------------------------ decode -------------------------- #
    def _step_impl(self, params: Any, lanes: ServeLaneState
                   ) -> tuple[ServeLaneState, jnp.ndarray, jnp.ndarray]:
        """One continuous-batching decode step over the whole block.

        Every lane computes (fixed shapes); only ``active`` lanes
        advance — a finished/free lane's row is dead weight until
        re-admission, which is exactly the utilization gap the
        run-to-completion discipline pays everywhere."""
        pol = self.policy
        pos = jnp.minimum(lanes.length, pol.max_len - 1)
        logits, _, kc, vc = pol.decode_step(
            params, lanes.last_tok, lanes.k, lanes.v, pos)
        nxt, _ = _select(logits, None)
        n_new = lanes.n_new + 1
        done = lanes.active & (n_new >= lanes.max_new)
        if self.eos_token is not None:
            done = done | (lanes.active & (nxt == self.eos_token))
        done = done | (lanes.active & (pos + 1 >= pol.max_len - 1))
        emitted = lanes.active
        lanes = lanes.replace(
            k=kc, v=vc,
            length=jnp.where(lanes.active, pos + 1, lanes.length),
            last_tok=jnp.where(lanes.active, nxt, lanes.last_tok),
            n_new=jnp.where(lanes.active, n_new, lanes.n_new),
            active=lanes.active & ~done,
        )
        return lanes, nxt, emitted

    # ------------------------------ serve --------------------------- #
    def serve(self, params: Any, prompts: Sequence[Sequence[int]],
              continuous: bool = True,
              max_new: Sequence[int] | None = None,
              ) -> tuple[list[list[int]], ServeStats]:
        """Decode every request; returns (per-request token lists,
        throughput/utilization stats).  ``max_new`` optionally skews the
        per-request generation budget (default: the pool's)."""
        n_req = len(prompts)
        budgets = ([self.max_new] * n_req if max_new is None
                   else [int(m) for m in max_new])
        order = list(range(n_req))
        if self.schedule == "sjf":
            order.sort(key=lambda i: len(prompts[i]) + budgets[i])
        pending = deque(order)
        P = max(len(p) for p in prompts)
        if P + max(budgets) > self.policy.max_len:
            raise ValueError(
                f"prompt_len {P} + max_new {max(budgets)} exceeds the "
                f"policy's static cache ({self.policy.max_len})")

        lanes = self.init_lanes()
        outputs: list[list[int]] = [[] for _ in range(n_req)]
        steps = 0
        # fenced serve timing (obs/trace.py): the span blocks on the
        # final lane state before closing, so wall_s covers the full
        # decode compute — without the fence, in-flight KV updates from
        # the last steps would leak out of the measurement
        tr = Tracer()
        with tr.span("serve") as sp:
            while pending or bool(np.asarray(lanes.active).any()):
                active_np = np.asarray(lanes.active)
                free = np.flatnonzero(~active_np)
                all_free = not active_np.any()
                may_admit = continuous or all_free
                if pending and len(free) and may_admit:
                    admit = np.zeros(self.num_lanes, bool)
                    pr = np.zeros((self.num_lanes, P), np.int32)
                    pl = np.zeros(self.num_lanes, np.int32)
                    rid = np.full(self.num_lanes, -1, np.int32)
                    mx = np.full(self.num_lanes, self.max_new, np.int32)
                    for lane in free:
                        if not pending:
                            break
                        r = pending.popleft()
                        admit[lane] = True
                        pl[lane] = len(prompts[r])
                        pr[lane, :len(prompts[r])] = prompts[r]
                        rid[lane] = r
                        mx[lane] = budgets[r]
                    lanes, first = self._jit_admit(
                        params, lanes, jnp.asarray(admit), jnp.asarray(pr),
                        jnp.asarray(pl), jnp.asarray(rid), jnp.asarray(mx))
                    first_np = np.asarray(first)
                    for lane in np.flatnonzero(admit):
                        outputs[int(rid[lane])].append(int(first_np[lane]))
                    # a freshly admitted lane might already be done
                    # (budget 1): retire it before the next decode step
                    lanes = lanes.replace(
                        active=lanes.active & (lanes.n_new < lanes.max_new))
                if not bool(np.asarray(lanes.active).any()):
                    continue
                rid_np = np.asarray(lanes.req_id)
                lanes, toks, emitted = self._jit_step(params, lanes)
                steps += 1
                toks_np, em_np = np.asarray(toks), np.asarray(emitted)
                for lane in np.flatnonzero(em_np):
                    outputs[int(rid_np[lane])].append(int(toks_np[lane]))
            sp.fence(lanes)
        wall = tr.totals()["serve"]
        total = sum(len(o) for o in outputs)
        stats = ServeStats(
            requests=n_req, total_tokens=total, decode_steps=steps,
            lane_slots=steps * self.num_lanes, wall_s=wall,
        )
        if self.registry is not None:
            publish_serve_stats(self.registry, stats,
                                schedule=self.schedule)
        return outputs, stats


__all__ = ["DecodePool", "ServeLaneState", "ServeStats"]
