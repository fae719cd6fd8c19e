"""Driver kind ``train``: a PPO job with the policy in the same program
(EnvPool paper §4.2; CleanRL's ``ppo_atari_envpool.py``), through
``rl/ppo.py``'s ``init_train_device`` and ``make_train_step``: the
program ``train_device`` runs, one fused collect and update per call.

The window calls ``train_step`` again and again, with at most
``updates_in_flight`` updates dispatched ahead of the device (so that a
stall of the host shorter than an update leaves the chip busy).
``sim_fps`` counts the agent steps of the window's updates, all of
which are whole, times the task's nominal frameskip, over all the time
of the window.  An update whose loss is not finite has failed.

Correctness, teacher-forced on the timed path's own updates: updates
drawn from the seed among the window's first ``min_updates`` are kept.
Before a kept update is dispatched the driver copies, on the device,
the learner state (parameters and Adam's moments), the lanes' game
states and the frame stacks they were served; ``train_step`` returns,
on every update, each step's actions, logits, values, rewards and dones,
the bootstrap values, checksums of the served observations, one row in
16 per step, and the learner states around the update's first and last
minibatch steps (``record``).  Once the window has closed and the pool's
state is freed, the configuration's plain reference, run on the same
device as plain jitted ``jax.numpy``:

  * replays every lane from the kept state under the recorded actions:
    the checksums, rewards and dones must match exactly (the game and
    the pipeline are integer or exact on both sides);
  * runs the policy, its products at the configuration's precision, on
    the replayed observations at the kept parameters: the recorded
    logits and values against it, each as one vector (``|program -
    reference| / |reference|``);
  * runs GAE on the recorded values and bootstrap values, and the
    update's first and last minibatch steps (in the update's own
    permutations), each from the program's learner state before it
    (``record["steps"]``): the clipped gradient the program took (read
    off Adam's first moment) and each parameter array's change, against
    the reference's from the same state.  The whole update is not
    compared: at one bfloat16 pass its 16 Adam steps part from any
    other run's, the reference's at the same precision too, by 0.002 to
    over 1 of the change by seed; one step at a time they do not.

This covers the sync engine's lane order, the game, the render and the
image pipeline, the frame stack and the reward clip, the policy's
forward pass, GAE and the PPO update.
"""

from __future__ import annotations

import collections
import sys
import time

import numpy as np

from bench.lib.catalog import Spec

U32 = 2 ** 32       # the pool's counters are 32-bit and may wrap
# rows of rollout the reference takes per call: 4096 stacks and their
# float32 activations fit in what the release of the pool frees
REFERENCE_BLOCK = 4096
PPO_KEYS = ("total_steps", "num_steps", "lr", "gamma", "lam", "clip",
            "vf_coef", "ent_coef", "epochs", "minibatches", "max_grad_norm",
            "anneal_lr", "vf_clip")


def setup(spec: Spec) -> "TrainCell":
    return TrainCell(spec)


def _rel(got, want):
    """``|got - want| / |want|``, Euclidean norms over every element."""
    import jax.numpy as jnp

    return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)


def _copy(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, tree)


class TrainCell:
    def __init__(self, spec: Spec):
        import jax

        import repro
        from repro.rl.ppo import PPOConfig, init_train_device

        self.spec = spec
        t, c = spec.traffic, spec.config
        self.pool = repro.make(
            c["task"], num_envs=t["num_envs"], batch_size=t["batch_size"],
            engine=t["engine"], seed=spec.seed, schedule=t["schedule"],
            **c.get("env", {}))
        self.cfg = PPOConfig(**{k: c["ppo"][k] for k in PPO_KEYS})
        self.net, state, step, (ps, ts), self.key = init_train_device(
            self.pool, self.cfg, seed=spec.seed)
        k = jax.random.PRNGKey(0)
        self.step = step.lower(state, ps, ts, k, k).compile()
        m = self.step.memory_analysis()
        self.prog_bytes = (m.argument_size_in_bytes + m.output_size_in_bytes
                           - m.alias_size_in_bytes + m.temp_size_in_bytes)
        pool = self.pool          # no reference back to the cell
        self.snapshot = jax.jit(lambda s, ps, ts: _copy(
            (s, pool.lane_view(ps).env_states, ts.obs)))
        self.copy = jax.jit(_copy)
        jax.block_until_ready((self.snapshot(state, ps, ts),
                               self.copy(state)))
        for _ in range(int(t["warm_updates"])):
            (state, ps, ts, _, _), _ = self._update(state, ps, ts)
        jax.block_until_ready((state, ps, ts))
        self.state, self.ps, self.ts = state, ps, ts
        rng = np.random.default_rng([spec.seed, 7])
        lim = t["limits"]
        self.keep = sorted(rng.choice(int(lim["min_updates"]),
                                      int(t["kept_updates"]), replace=False))
        self.kept: list[dict] = []
        self.updates = 0

    def _update(self, state, ps, ts):
        """Dispatch one update: its outputs, and its update key ``ku``
        (the minibatch permutations' seed)."""
        import jax

        self.key, kc, ku = jax.random.split(self.key, 3)
        return self.step(state, ps, ts, kc, ku), ku

    # ---------------------------------------------------------------- #
    def window(self, seconds: float) -> dict:
        import jax

        t, c = self.spec.traffic, self.spec.config
        before = self.pool.stats(self.ps)
        state, ps, ts = self.state, self.ps, self.ts
        losses, pending = [], collections.deque()
        n = 0
        t0 = time.perf_counter()
        while True:
            if n - 1 in self.keep:    # the previous kept update's result
                self.kept[-1]["after"] = self.copy(state)
            if n in self.keep:
                s0, lanes, stack = self.snapshot(state, ps, ts)
                self.kept.append({"state": s0, "lanes": lanes,
                                  "stack": stack})
            with jax.profiler.TraceAnnotation("train_update"):
                (state, ps, ts, metrics, record), ku = self._update(
                    state, ps, ts)
            if n in self.keep:
                self.kept[-1].update(ku=ku, record=record)
            del record
            n += 1
            losses.append(metrics["loss"])
            pending.append(metrics["loss"])
            if len(pending) >= t["updates_in_flight"]:
                with jax.profiler.TraceAnnotation("wait_oldest_update"):
                    pending.popleft().block_until_ready()
            if time.perf_counter() - t0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("wait_last_update"):
            jax.block_until_ready((state, ps, ts))
        elapsed = time.perf_counter() - t0
        if self.kept and "after" not in self.kept[-1]:
            self.kept[-1]["after"] = self.copy(state)
        self.state, self.ps, self.ts = state, ps, ts
        self.updates = n
        after = self.pool.stats(ps)
        steps = n * self.cfg.num_steps * self.pool.batch_size
        failed = int(sum(not np.isfinite(float(x)) for x in losses))
        return {
            "e2e": {"sim_fps": steps * c["nominal_frameskip"] / elapsed,
                    "window_s": elapsed, "updates": n},
            "attempted": n,
            "failed": failed,
            "counts": {
                "updates": n, "agent_steps": steps,
                "recvs": n * self.cfg.num_steps,    # one sync recv a step
                "served": (after["served"] - before["served"]) % U32,
                "num_steps": self.cfg.num_steps,
                "num_envs": self.pool.batch_size,
                "epochs": self.cfg.epochs,
                "obs_shape": tuple(self.pool.spec.obs_spec.shape),
                "num_actions": self.net.act_dim,
            },
        }

    def program_bytes(self) -> int:
        """The train step's footprint: arguments, outputs and temp."""
        return self.prog_bytes

    def release(self) -> None:
        self.state = self.ps = self.ts = None

    # ---------------------------------------------------------------- #
    def checks(self, control: bool = False) -> list[tuple[str, float, float]]:
        """Compare the kept updates with the reference.  ``control=True``
        puts the reference, its products' operands rounded to
        ``float8_e4m3fn``, in the program's place: its logits, values,
        gradients and steps stand for the recorded ones."""
        import jax
        import jax.numpy as jnp

        from repro.rl.ppo import checksum_rows

        ref = self.spec.reference
        lim = self.spec.traffic["limits"]
        bad = {"obs_checksum_mismatch": 0, "reward_mismatch": 0,
               "done_mismatch": 0}
        # every reading, worst taken at the end (a NaN stays a NaN)
        err: dict[str, list] = {"logit_rel_err": [], "value_rel_err": [],
                                "grad_rel_err": [], "step_rel_err": []}
        for item in self.kept:
            rec, s0 = item["record"], item["state"]
            lanes = {k: getattr(item["lanes"], k) for k in ref.STATE_KEYS}
            seen, rew, done, sums, last = ref.replay(
                lanes, item["stack"], rec["actions"],
                self.pool.spec.max_episode_steps)
            rows = jax.vmap(checksum_rows, (None, 0))(
                sums.shape[1], jnp.arange(sums.shape[0]))
            bad["obs_checksum_mismatch"] += int(jnp.sum(
                jnp.take_along_axis(sums, rows, 1) != rec["obs_sum"]))
            bad["reward_mismatch"] += int(jnp.sum(rew != rec["rewards"]))
            bad["done_mismatch"] += int(jnp.sum(done != rec["dones"]))
            logits, values = self._forward(s0.params, seen, False)
            if control:
                got_pi, got_v = self._forward(s0.params, seen, True)
                _, got_last = self._forward(s0.params, last[None], True)
            else:
                got_pi, got_v, got_last = (rec["pi"], rec["values"],
                                           rec["last_values"])
            err["logit_rel_err"].append(
                _rel(got_pi.reshape(logits.shape), logits))
            err["value_rel_err"].append(
                _rel(got_v.reshape(values.shape), values))
            # the update's first and last minibatch steps, teacher-forced:
            # each from the program's own learner state before it, on the
            # replayed rollout, with the logits and values the program
            # (or the control) acted and bootstrapped with
            data, perms = self._rollout_data(rec["actions"], rew, done,
                                             got_pi, got_v, got_last,
                                             item["ku"])
            mb = perms.shape[1] // self.cfg.minibatches
            steps = rec["steps"]
            for name, before, after, idx in (
                    ("first", s0, steps["first"], perms[0, :mb]),
                    ("last", steps["last"], item["after"], perms[-1, -mb:])):
                g_ref = self._grad(before.params, seen, data, idx, False)
                p_ref = self._adam(before, g_ref)
                if control:
                    g_got = self._grad(before.params, seen, data, idx, True)
                    p_got = self._adam(before, g_got)
                else:
                    # Adam's first moment moved by (1 - b1) times the
                    # clipped gradient the program took
                    b1 = ref.ADAM_B1
                    g_got = jax.tree.map(lambda m1, m0: (m1 - b1 * m0)
                                         / (1 - b1), after.opt.mu,
                                         before.opt.mu)
                    p_got = after.params
                leaves = (jax.tree.leaves(before.params),
                          jax.tree.leaves(g_got), jax.tree.leaves(g_ref),
                          jax.tree.leaves(p_got), jax.tree.leaves(p_ref))
                off_g = [_rel(g, w) for _, g, w, _, _ in zip(*leaves)]
                off_p = [_rel(q - p, w - p) for p, _, _, q, w in zip(*leaves)]
                err["grad_rel_err"].append(jnp.max(jnp.stack(off_g)))
                err["step_rel_err"].append(jnp.max(jnp.stack(off_p)))
                print(f"[bench] kept update, {name} step: each parameter "
                      "array's gradient off by "
                      + " ".join(f"{float(x):.3g}" for x in off_g)
                      + "; its change off by "
                      + " ".join(f"{float(x):.3g}" for x in off_p),
                      file=sys.stderr, flush=True)
        return [
            *((k, float(v), 0.0) for k, v in bad.items()),
            *((k, float(np.max(v)) if v else 0.0, float(lim[k]))
              for k, v in err.items()),
            ("updates_short", float(max(
                0, int(lim["min_updates"]) - self.updates)), 0.0),
            ("updates_unchecked", float(len(self.keep) - sum(
                "after" in k for k in self.kept)), 0.0),
        ]

    def _rollout_data(self, actions, rew, done, pi, values, last_v, ku):
        """What the update's minibatches draw from: the flat ``(T * N,)``
        actions, old log-probabilities (from the logits ``pi``), values,
        GAE's advantages and returns (on ``values`` and the bootstrap
        ``last_v``); and each epoch's permutation of the rows."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        steps, n_env = rew.shape
        rows = steps * n_env
        acts = actions.reshape(rows).astype(jnp.int32)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(pi.reshape(rows, -1)), acts[:, None], 1)[:, 0]
        values = values.reshape(steps, n_env)
        adv, ret = self.spec.reference.gae(rew, values, done,
                                           last_v.reshape(n_env), cfg.gamma,
                                           cfg.lam)
        data = {"actions": acts, "logp": logp, "values": values.reshape(rows),
                "adv": adv.reshape(rows), "ret": ret.reshape(rows)}
        perms = jnp.stack([jax.random.permutation(k, rows)
                           for k in jax.random.split(ku, cfg.epochs)])
        return data, perms

    def _grad(self, params, seen, data, idx, control: bool):
        """The reference's clipped minibatch gradient at ``params``."""
        return self.spec.reference.minibatch_grad(
            params, seen, data, idx, self.spec.config["ppo"], REFERENCE_BLOCK,
            control)

    def _adam(self, before, g):
        """The parameters after the reference's Adam step on ``g`` from
        the learner state ``before``."""
        cfg = self.cfg
        rows = cfg.num_steps * self.pool.batch_size
        total = (max(1, cfg.total_steps // rows) * cfg.epochs
                 * cfg.minibatches)
        lr = self.spec.reference.learning_rate(
            self.spec.config["ppo"], int(before.step), total)
        return self.spec.reference.adam_step(
            before.params, before.opt.mu, before.opt.nu, g, lr,
            np.int32(int(before.opt.count) + 1))[0]

    def _forward(self, params, seen, control: bool):
        """The reference's ``(T*N, A)`` logits and ``(T*N,)`` values over
        the stacks ``seen`` ``(T, N, ...)``, a few rollout steps at a
        time (at most ``REFERENCE_BLOCK`` rows, a divisor of T)."""
        import jax.numpy as jnp

        steps, n_env = seen.shape[:2]
        per = max(d for d in range(1, steps + 1) if steps % d == 0
                  and d * n_env <= max(REFERENCE_BLOCK, n_env))
        parts = [self.spec.reference.forward_steps(params, seen, t, per,
                                                   control)
                 for t in range(0, steps, per)]
        return (jnp.concatenate([p[0] for p in parts]),
                jnp.concatenate([p[1] for p in parts]))
