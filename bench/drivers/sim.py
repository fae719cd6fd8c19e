"""Driver kind ``sim``: the EnvPool pure-simulation protocol (paper §4.1,
random actions) through ``core/xla_loop.py::build_random_collect_fn``.

The window drives the random collect: one jitted ``lax.scan`` of
``recvs_per_call`` send/recv steps over the pool, called again and again
with a fresh key, at most ``calls_in_flight`` calls dispatched ahead of
the device (so that a stall of the host shorter than that many calls
leaves the chip busy).  ``sim_fps`` counts the
agent steps the collect served, times the task's nominal substeps per
step, over all the time of the window.

Correctness: a sample of the window's collect calls, drawn from the
seed, is kept on the device.  Once the window has closed, each served
transition of those calls (a lane's observation, the action it was sent,
the observation, reward, done and step cost it was served next) is
compared with the configuration's plain reference, and each recv's
served set with the selection rule (``bench/lib/sched_ref.py``).  This
covers the scheduler's selection, the engine's gather and scatter, the
batched env and the ``env_step`` kernel.
"""

from __future__ import annotations

import collections
import sys
import time
from typing import Any

import numpy as np

from bench.lib.catalog import Spec

U32 = 2 ** 32      # the pool's counters are 32-bit and may wrap
# a foot closer than this to the contact height is within the rounding
# of the chip's cosine against the host's (about 1e-7 of a height)
CONTACT_TOL = 1e-4


def setup(spec: Spec) -> "SimCell":
    return SimCell(spec)


class SimCell:
    def __init__(self, spec: Spec):
        import jax

        import repro
        from repro.core.xla_loop import build_random_collect_fn

        self.spec = spec
        t, c = spec.traffic, spec.config
        kw: dict[str, Any] = dict(c.get("env", {}), **t.get("env", {}))
        if t["engine"] == "device-sharded":
            kw["num_shards"] = spec.chips
        self.pool = repro.make(
            c["task"], num_envs=t["num_envs"], batch_size=t["batch_size"],
            engine=t["engine"], seed=spec.seed, schedule=t["schedule"], **kw)
        self.steps = int(t["recvs_per_call"])
        key = jax.random.PRNGKey(spec.seed)
        k_reset, self.key = jax.random.split(key)
        ps, ts = self.pool.reset(k_reset)
        self.collect = build_random_collect_fn(
            self.pool, num_steps=self.steps).lower(ps, None, ts, key).compile()
        m = self.collect.memory_analysis()
        self.prog_bytes = (m.argument_size_in_bytes + m.output_size_in_bytes
                           - m.alias_size_in_bytes + m.temp_size_in_bytes)
        self.calls = 0
        for _ in range(int(t["warm_calls"])):
            ps, ts, _, _ = self._call(ps, ts)
        jax.block_until_ready((ps, ts))
        self.ps, self.ts = ps, ts
        self.kept: list = []

    def _call(self, ps, ts):
        import jax

        k = jax.random.fold_in(self.key, self.calls)
        self.calls += 1
        return self.collect(ps, None, ts, k)

    # ---------------------------------------------------------------- #
    def window(self, seconds: float) -> dict:
        import jax

        t, c = self.spec.traffic, self.spec.config
        keep = int(t["check_calls"])
        rng = np.random.default_rng([self.spec.seed, 7])
        before = self.pool.stats(self.ps)
        ps, ts = self.ps, self.ts
        calls = 0
        pending: collections.deque = collections.deque()
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("collect_call"):
                ps, ts, traj, acts = self._call(ps, ts)
            calls += 1
            # reservoir sample of the window's calls, drawn from the seed
            item = (traj, acts, ts)
            if len(self.kept) < keep:
                self.kept.append(item)
            else:
                j = int(rng.integers(0, calls))
                if j < keep:
                    self.kept[j] = item
            del item, traj, acts
            pending.append(ts.env_id)
            if len(pending) >= t["calls_in_flight"]:
                with jax.profiler.TraceAnnotation("wait_oldest_call"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("wait_last_call"):
            jax.block_until_ready((ps, ts))
        elapsed = time.perf_counter() - t0
        self.ps, self.ts = ps, ts
        after = self.pool.stats(ps)
        recvs = calls * self.steps
        served = recvs * self.pool.batch_size
        fps = served * c["nominal_substeps"] / elapsed
        return {
            "e2e": {"sim_fps": fps, "window_s": elapsed, "recvs": recvs},
            "attempted": served,
            "failed": 0,
            "counts": {
                "recvs": recvs,
                "served": (after["served"] - before["served"]) % U32,
                "stepped": (after["stepped"] - before["stepped"]) % U32,
                "substeps": (after["cost_sum"] - before["cost_sum"]) % U32,
                "state_width": 28, "action_width": 8,
            },
        }

    def program_bytes(self) -> int:
        """The collect program's footprint: arguments, outputs and temp."""
        return self.prog_bytes

    def release(self) -> None:
        self.ps = self.ts = None

    # ---------------------------------------------------------------- #
    def transitions(self) -> dict:
        """Every served transition of the kept calls, as host arrays."""
        import jax

        rows: dict[str, list] = {k: [] for k in (
            "obs", "act", "next_obs", "rew", "done", "term", "trunc",
            "cost", "ep_len", "lane", "call")}
        sel = []
        for call, (traj, acts, last) in enumerate(self.kept):
            traj, acts, last = jax.device_get((traj, acts, last))
            blocks = {k: np.concatenate([np.asarray(getattr(traj, k)),
                                         np.asarray(getattr(last, k))[None]])
                      for k in ("obs", "env_id", "reward", "done",
                                "terminated", "truncated", "step_cost",
                                "episode_length")}
            ids = blocks["env_id"]
            s1 = ids.shape[0]
            pos = {}        # lane -> (block, row) of its last serve
            send_cost = np.full(ids.shape, np.nan, np.float32)
            for b in range(s1):
                for r, lane in enumerate(ids[b]):
                    if lane in pos and b > 0:
                        pb, pr = pos[lane]
                        rows["obs"].append(blocks["obs"][pb, pr])
                        rows["act"].append(acts[pb, pr])
                        rows["next_obs"].append(blocks["obs"][b, r])
                        rows["rew"].append(blocks["reward"][b, r])
                        rows["done"].append(blocks["done"][b, r])
                        rows["term"].append(blocks["terminated"][b, r])
                        rows["trunc"].append(blocks["truncated"][b, r])
                        rows["cost"].append(blocks["step_cost"][b, r])
                        rows["ep_len"].append(blocks["episode_length"][b, r])
                        rows["lane"].append(lane)
                        rows["call"].append(call)
                        send_cost[pb, pr] = blocks["step_cost"][b, r]
                    pos[lane] = (b, r)
            sel.append((ids, send_cost,
                        np.rint(blocks["obs"][..., 26]).astype(np.int64)))
        out = {k: np.asarray(v) for k, v in rows.items()}
        out["selection"] = sel
        return out

    def checks(self, control: bool = False) -> list[tuple[str, float, float]]:
        """Compare the kept calls with the reference.  ``control=True``
        puts the reference, computed in bfloat16, in the program's
        place: its observations and rewards stand for the served ones."""
        import jax
        import jax.numpy as jnp

        from bench.lib import sched_ref

        ref = self.spec.reference
        t, c = self.spec.traffic, self.spec.config
        lim = t["limits"]
        tr = self.transitions()
        heavy = int(dict(c.get("env", {}), **t.get("env", {})).get(
            "heavy_iters", 1))
        contacts = np.rint(tr["obs"][:, 26]).astype(np.int64)
        # the solver multiplier a served step cost implies (1 or heavy)
        extra = tr["cost"] - ref.BASE_COST
        scale = np.where(contacts > 0, extra / np.maximum(contacts, 1), 1.0)
        ok_scale = (contacts == 0) & (extra == 0) | (
            (contacts > 0) & ((scale == 1) | (scale == heavy)))
        cost_ref = ref.BASE_COST + contacts * np.where(ok_scale, scale, 1.0)
        cost_ref = cost_ref.astype(np.int32)
        max_cost = int(ref.BASE_COST + 4 * heavy)
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            run = jax.jit(ref.step, static_argnums=(3, 4))
            nxt, rew, term, margin, near = jax.device_get(run(
                tr["obs"], tr["act"], cost_ref, max_cost, jnp.float32))
            if control:
                cn, cr, *_ = jax.device_get(run(
                    tr["obs"], tr["act"], cost_ref, max_cost, jnp.bfloat16))
        done = tr["done"].astype(bool)
        live = ~done
        if control:     # a reset's observation is the new episode's
            tr["next_obs"] = np.where(live[:, None], cn, tr["next_obs"])
            tr["rew"] = cr
        # a foot within rounding of the contact threshold may touch on
        # one side and not on the other: such steps are not compared
        sure = near > CONTACT_TOL
        print(f"[bench] compared {int((live & sure).sum())} of "
              f"{len(sure)} transitions; {int((~sure).sum())} skipped "
              f"near contact", file=sys.stderr, flush=True)
        obs_err = (np.abs(tr["next_obs"][live & sure] - nxt[live & sure])
                   / (1.0 + np.abs(nxt[live & sure])))
        rew_err = (np.abs(tr["rew"][sure] - rew[sure])
                   / (1.0 + np.abs(rew[sure])))
        # termination: the served flag against the reference's, away
        # from the threshold; a truncation ends an episode at its limit
        clear = margin > 1e-4
        trunc_ok = tr["trunc"].astype(bool) & (
            tr["ep_len"] == self.pool.spec.max_episode_steps)
        term_bad = clear & ((tr["term"].astype(bool) != term)
                            | (done & ~term & ~trunc_ok))
        reset_bad = done & ~ref.is_reset_obs(tr["next_obs"])
        # a heavy scene keeps its multiplier for the whole episode
        scale_bad = 0
        last_scale: dict[tuple[int, int], float] = {}
        for key, sc, ct, d in zip(zip(tr["call"], tr["lane"]), scale,
                                  contacts, done):
            if ct > 0:
                if key in last_scale and last_scale[key] != sc:
                    scale_bad += 1
                last_scale[key] = sc
            if d:
                last_scale.pop(key, None)
        checked = bad = dup = 0
        for ids, send_cost, n_feet in tr["selection"]:
            dup += sum(len(np.unique(b)) != b.size for b in ids)
            # a step sent but not served again within the call: its cost
            # follows from the contacts where the multiplier cannot matter
            known = (n_feet == 0) | (heavy == 1)
            send_cost = np.where(np.isnan(send_cost) & known,
                                 ref.BASE_COST + n_feet, send_cost)
            n_c, n_b = sched_ref.selection_mismatches(
                ids, send_cost, self.pool.num_envs, t["schedule"],
                self.spec.chips if t["engine"] == "device-sharded" else 1)
            checked += n_c
            bad += n_b
        return [
            ("obs_rel_err", float(obs_err.max(initial=0.0)), lim["obs_rel_err"]),
            ("reward_rel_err", float(rew_err.max(initial=0.0)),
             lim["reward_rel_err"]),
            ("done_mismatch", float(term_bad.sum() + reset_bad.sum()), 0.0),
            ("cost_mismatch", float((~ok_scale).sum() + scale_bad), 0.0),
            ("selection_mismatch", float(bad + dup), 0.0),
            ("transitions_short", float(
                max(0, lim["min_transitions"] - int((live & sure).sum()))),
             0.0),
            ("recvs_unchecked", float(
                max(0, lim["min_selection_recvs"] - checked)), 0.0),
        ]
