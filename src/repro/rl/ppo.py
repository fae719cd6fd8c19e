"""PPO (Schulman et al. 2017) over any EnvPool engine — the paper's §4.2
end-to-end integration.

``train(pool, cfg)`` is the engine-agnostic entry: it dispatches on the
``core.protocol`` contract — functional (device-family) pools get the
fully-jitted on-device driver, host pools the numpy driver — so the
same call works over `device`, `device-sharded`, `thread`, `forloop`,
and `subprocess`.

  * ``train_device``: fully device-resident — collect (``lax.scan``
    over the mesh engine, paper App. E) and the PPO update are ONE
    jitted, donated-buffer ``train_step``: the ``PoolState`` is donated
    (``donate_argnums``) so XLA reuses the SoA env buffers in place, it
    stays sharded across the whole collect+update loop, and it never
    crosses the host boundary — the only per-iteration host sync is the
    scalar metrics dict.  Policy parameters are placed by
    ``distributed/sharding.py::policy_shardings`` rules: replicated
    across the env mesh for small nets, sharded over it for large ones
    (Seed-RL style).  Accepts any mesh engine (``engine="device"`` is
    the degenerate 1-shard mesh).
  * ``train_pipelined``: the PIPELINED device driver (Sample Factory's
    no-idle-hardware argument / Seed-RL's actor-learner split).  The
    fused ``train_device`` program serializes collect and update — the
    env mesh idles during the PPO epochs and the learner idles during
    the rollout scan.  ``train_pipelined`` splits them into TWO jitted
    programs dispatched concurrently each iteration: the collect scan
    (``core/xla_loop.py::build_pipelined_collect_fn``, PoolState and
    TimeStep donated, env state sharded over the mesh) runs behind the
    *previous* params while the single-device learner program consumes
    the previous rollout — double buffering: two rollout buffers are in
    flight at any time, and neither program depends on the other within
    an iteration (collect(t) needs params(t-1); update(t) needs
    rollout(t-1)).  The consumed rollout is therefore exactly one policy
    step stale, which V-trace (``rl/vtrace.py``; ``PPOConfig.rho_clip``
    / ``c_clip``) corrects: the learner recomputes values and target
    log-probs under its current params and regresses toward the
    truncated-importance-weighted targets, while the fused on-policy
    path keeps plain GAE.  The learner state deliberately lives on ONE
    device: inside the fused mesh program the PPO epochs run replicated
    on every shard (D redundant copies of the update work — the
    simulated-mesh cost of the serialization), whereas the pipelined
    learner pays it once and leaves the mesh to the envs.
  * ``train_host``: numpy loop over a host engine (thread / subprocess /
    for-loop) with the SAME jitted update — this is the configuration the
    paper's Figure 4 profiles (env-step vs inference vs train vs other
    timing).  Each profile bucket is closed only after
    ``block_until_ready`` on that stage's outputs, so async XLA dispatch
    cannot leak one bucket's work into the next.
  * ``train_host_pipelined``: the same pipeline over a host engine —
    an actor thread steps the pool (inference behind the latest
    published params) and streams every served batch into a
    ``core/buffers.py::StateBufferQueue`` ring (the paper's Appendix-D
    block hand-off, now a hot path) while the learner thread takes
    blocks, stacks a rollout, and runs the identical V-trace update;
    the queue's bounded-occupancy backpressure caps how far the actor
    can run ahead, bounding the policy lag the importance weights must
    absorb.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import MeshEnvPool
from repro.core.protocol import EnvPool, is_functional
from repro.kernels.backend import resolve_backend
from repro.kernels.minibatch_obs.ops import (
    minibatch_obs,
    supports,
    uses_kernel,
)
from repro.obs.metrics import MetricsRegistry, publish_history
from repro.obs.trace import PPO_COLLECT, PPO_GAE, PPO_UPDATE, Tracer
from repro.rl.gae import gae
from repro.rl.nets import ActorCritic
from repro.rl.vtrace import vtrace
from repro.optim import adamw, linear_decay
from repro.utils.pytree import pytree_dataclass


@dataclasses.dataclass
class PPOConfig:
    total_steps: int = 100_000
    num_steps: int = 128          # rollout length per env (N_steps)
    lr: float = 2.5e-4
    gamma: float = 0.99
    lam: float = 0.95
    clip: float = 0.2
    vf_coef: float = 0.5
    ent_coef: float = 0.01
    epochs: int = 4
    minibatches: int = 4
    max_grad_norm: float = 0.5
    anneal_lr: bool = True
    vf_clip: bool = True
    # V-trace truncation thresholds (rho-bar / c-bar, Espeholt et al.
    # 2018) for the pipelined drivers' one-step-stale rollouts; the
    # fused on-policy path ignores them and keeps plain GAE.
    rho_clip: float = 1.0
    c_clip: float = 1.0


@pytree_dataclass
class PPOState:
    params: Any
    opt: Any
    step: jnp.ndarray


def make_ppo_update(net: ActorCritic, cfg: PPOConfig, total_updates: int,
                    keep_steps: bool = False, learner_devices: int = 1):
    """``(opt, update)``: ``update(state, rollout, key) -> (state,
    metrics)`` runs every epoch's minibatch steps.  With ``keep_steps``,
    ``metrics["steps"]`` holds ``first``, the learner state after the
    update's first minibatch step, and ``last``, the state before its
    last one: what it takes to check those two steps one at a time,
    each from the program's own parameters.

    A pixel net over uint8 stacks takes each minibatch's conv1 operand
    from ``kernels/minibatch_obs`` wherever the kernel backend is not
    the off-TPU ``vmap`` choice and the learner runs on one device
    (``learner_devices``; XLA cannot partition a Pallas call over the
    mesh ``train_device`` replicates the update on): the gather, the
    scaling to bfloat16 and the batch-minor layout in one pass, the same
    bits as XLA's.  ``metrics["obs_kernel_steps"]`` counts the minibatch
    steps whose operand the Pallas kernel made."""
    opt = adamw(b1=0.9, b2=0.999, eps=1e-5, weight_decay=0.0,
                clip_norm=cfg.max_grad_norm)
    lr_fn = (linear_decay(cfg.lr, total_updates) if cfg.anneal_lr
             else (lambda s: cfg.lr))

    def loss_fn(params, batch, batch_minor):
        logp, ent, v = net.logp_entropy(params, batch["obs"], batch["actions"],
                                        batch_minor)
        ratio = jnp.exp(logp - batch["logp"])
        adv = batch["adv"]
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        pg1 = -adv * ratio
        pg2 = -adv * jnp.clip(ratio, 1 - cfg.clip, 1 + cfg.clip)
        pg_loss = jnp.mean(jnp.maximum(pg1, pg2))
        if cfg.vf_clip:
            v_clip = batch["values"] + jnp.clip(
                v - batch["values"], -cfg.clip, cfg.clip
            )
            vf_loss = 0.5 * jnp.mean(
                jnp.maximum((v - batch["ret"]) ** 2, (v_clip - batch["ret"]) ** 2)
            )
        else:
            vf_loss = 0.5 * jnp.mean((v - batch["ret"]) ** 2)
        ent_loss = -jnp.mean(ent)
        loss = pg_loss + cfg.vf_coef * vf_loss + cfg.ent_coef * ent_loss
        return loss, {"pg": pg_loss, "vf": vf_loss, "ent": -ent_loss,
                      "ratio": jnp.mean(ratio)}

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    backend = resolve_backend()

    def update(state: PPOState, rollout: dict[str, jnp.ndarray], key: jax.Array):
        """rollout leaves: (T, M, ...) — flattened to (T*M, ...)."""
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in rollout.items()}
        B = flat["obs"].shape[0]
        mb = B // cfg.minibatches
        step0, n_steps = state.step, cfg.epochs * cfg.minibatches
        batch_minor = (net.pixel and backend != "vmap"
                       and learner_devices == 1
                       and supports(flat["obs"].shape[1:], flat["obs"].dtype))
        kernel = batch_minor and uses_kernel(backend)

        def epoch(carry, ek):
            perm = jax.random.permutation(ek, B)

            def mb_step(carry, i):
                state, kept = carry
                idx = jax.lax.dynamic_slice_in_dim(perm, i * mb, mb)
                batch = {k: v[idx] for k, v in flat.items()
                         if not (batch_minor and k == "obs")}
                if batch_minor:
                    batch["obs"] = minibatch_obs(flat["obs"], idx,
                                                 backend=backend)
                (loss, metrics), grads = grad_fn(state.params, batch,
                                                 batch_minor)
                metrics = dict(metrics, obs_kernel_steps=jnp.int32(kernel))
                lr = lr_fn(state.step)
                params, opt_state = opt.update(grads, state.opt, state.params, lr)
                new = PPOState(params, opt_state, state.step + 1)
                if keep_steps:
                    k = state.step - step0
                    pick = lambda c, a, b: jax.tree.map(  # noqa: E731
                        lambda x, y: jnp.where(c, y, x), a, b)
                    kept = {"first": pick(k == 0, kept["first"], new),
                            "last": pick(k == n_steps - 1, kept["last"],
                                         state)}
                return (new, kept), (loss, metrics)

            carry, (losses, metrics) = jax.lax.scan(
                mb_step, carry, jnp.arange(cfg.minibatches)
            )
            return carry, (losses, metrics)

        keys = jax.random.split(key, cfg.epochs)
        kept = {"first": state, "last": state} if keep_steps else None
        (state, kept), (losses, metrics) = jax.lax.scan(
            epoch, (state, kept), keys)
        out = {k: jnp.mean(v) for k, v in metrics.items()}
        out["obs_kernel_steps"] = jnp.sum(metrics["obs_kernel_steps"])
        out["loss"] = jnp.mean(losses)
        if keep_steps:
            out["steps"] = kept
        return state, out

    return opt, update


def make_vtrace_ppo_update(net: ActorCritic, cfg: PPOConfig,
                           total_updates: int):
    """The pipelined learner's update program: V-trace-corrected PPO.

    ``update(state, rollout, key)`` consumes the raw hand-off rollout
    (``build_pipelined_collect_fn`` layout: obs / actions / behavior
    ``logp`` / rewards / dones / ``last_obs``), recomputes values and
    target log-probs under the CURRENT params, forms V-trace value
    targets and rho-clipped advantages (``rl/vtrace.py``) to absorb the
    one-step policy lag, then runs the standard PPO epochs (the clipped
    surrogate's ratio is taken against the recorded behavior log-prob).
    Shared by ``train_pipelined`` and ``train_host_pipelined``.
    """
    opt, ppo_update = make_ppo_update(net, cfg, total_updates)

    def update(state: PPOState, traj: dict[str, jnp.ndarray], key: jax.Array):
        T, M = traj["rewards"].shape
        obs_flat = traj["obs"].reshape((T * M,) + traj["obs"].shape[2:])
        act_flat = traj["actions"].reshape(
            (T * M,) + traj["actions"].shape[2:]
        )
        target_logp, _, v = net.logp_entropy(state.params, obs_flat, act_flat)
        target_logp = target_logp.reshape(T, M)
        values = v.reshape(T, M)
        _, last_v = net.forward(state.params, traj["last_obs"])
        vs, pg_adv = vtrace(
            traj["logp"], target_logp, traj["rewards"], values,
            traj["dones"], last_v, gamma=cfg.gamma, lam=cfg.lam,
            rho_clip=cfg.rho_clip, c_clip=cfg.c_clip,
        )
        rollout = {
            "obs": traj["obs"], "actions": traj["actions"],
            "logp": traj["logp"], "values": values,
            "adv": pg_adv, "ret": vs,
        }
        state, metrics = ppo_update(state, rollout, key)
        # observability of the lag the correction absorbs: the mean raw
        # importance ratio pi/mu over the consumed rollout (1.0 = no lag)
        metrics = dict(metrics, rho_behavior=jnp.mean(
            jnp.exp(target_logp - traj["logp"])
        ))
        return state, metrics

    return opt, update


def _episode_metrics(traj_dones, traj_ep_ret):
    """In-graph episode stats: (episodes, ep_sum) scalars — the division
    happens host-side where a zero count can be handled without NaN."""
    episodes = jnp.sum(traj_dones)
    ep_sum = jnp.sum(jnp.where(traj_dones, traj_ep_ret, 0.0))
    return episodes, ep_sum


def _record(history: list[dict], rec: dict, episodes: int, ep_sum: float,
            log_fn, registry: MetricsRegistry | None = None) -> None:
    """Append one iteration record, carrying ``mean_return`` forward when
    the iteration completed zero episodes (previously ``ep_sum / 0``
    produced NaN, which breaks strict-JSON serialization of the
    history).  With a ``registry``, the record is also published as
    ``ppo_*`` metrics (obs/metrics.py)."""
    if episodes > 0:
        mean_return = ep_sum / episodes
    else:
        mean_return = history[-1]["mean_return"] if history else 0.0
    rec = dict(rec, episodes=episodes, mean_return=float(mean_return))
    history.append(rec)
    if registry is not None:
        publish_history(registry, rec)
    if log_fn:
        log_fn(rec)


# --------------------------------------------------------------------- #
# fully on-device driver
# --------------------------------------------------------------------- #
CHECKSUM_MUL = 2654435761     # odd: every element position weighs in
# the served rows a step checksums: one in CHECKSUM_STRIDE, a different
# one each step (on a v5e, widening and summing every 4x84x84 uint8
# stack of a 1024-lane step takes about 2.6 ms, 14% of a Pong update)
CHECKSUM_STRIDE = 16


def obs_checksum(obs: jnp.ndarray) -> jnp.ndarray:
    """One exact ``uint32`` per row of a served block of integer
    observations: the sum, modulo 2**32, of the row's elements each
    times a weight of its position.  Equal rows give equal sums on every
    backend, and a moved, dropped or altered element changes the sum."""
    x = obs.reshape(obs.shape[0], -1).astype(jnp.uint32)
    w = (jnp.arange(1, x.shape[1] + 1, dtype=jnp.uint32)
         * jnp.uint32(CHECKSUM_MUL))
    return jnp.sum(x * w, axis=1, dtype=jnp.uint32)


def checksum_rows(m: int, t) -> jnp.ndarray:
    """The rows of an ``m``-row block that step ``t`` checksums: ``t mod
    s``, ``s + t mod s``, ... with ``s = gcd(CHECKSUM_STRIDE, m)``."""
    s = math.gcd(CHECKSUM_STRIDE, m)
    return jnp.arange(m // s) * s + t % s


def make_train_step(pool, cfg: PPOConfig, net: ActorCritic, update):
    """``train_device``'s program, jitted and not yet compiled:
    ``train_step(state, ps, ts, kc, ku) -> (state, ps, ts, metrics,
    record)``.  Public so that a caller can lower or compile ahead of
    time the exact program ``train_device`` runs, to read its memory
    and its kernels.

    ``record`` holds, per rollout step and served row (``(T, M, ...)``;
    a sync pool serves row ``i`` as lane ``i``): the action, the
    policy's logits (or mean), the value, the reward and done served
    after the action; ``obs_sum`` ``(T, M / s)``, ``obs_checksum`` of
    the observations served with them in the rows ``checksum_rows``
    picks; ``last_values`` ``(M,)``, GAE's bootstrap; and, where
    ``update`` keeps them (``make_ppo_update(keep_steps=True)``),
    ``steps``, the learner states around the update's first and last
    minibatch steps.  Some tens of MB per update: what it takes to check
    the update against a replay from a copy of the state it started
    from."""

    def train_step(state, ps, ts, kc, ku):
        """ONE fused collect+update: the rollout scan and the PPO epochs
        lower into a single XLA program.  ``ps``/``ts`` are donated —
        the env SoA buffers are updated in place and never leave the
        mesh; ``ps`` stays sharded through the entire body."""

        def one_step(carry, xs):
            ps, ts = carry
            k, t = xs
            a, logp, v, pi = net.sample(state.params, ts.obs, k)
            ps, new_ts = pool.step(ps, a, ts.env_id)
            rows = checksum_rows(new_ts.obs.shape[0], t)
            data = {
                "obs": ts.obs, "actions": a, "logp": logp, "values": v,
                "pi": pi, "rewards": new_ts.reward, "dones": new_ts.done,
                "ep_ret": new_ts.episode_return,
                "obs_sum": obs_checksum(new_ts.obs[rows]),
            }
            return (ps, new_ts), data

        with jax.named_scope(PPO_COLLECT):
            keys = jax.random.split(kc, cfg.num_steps)
            (ps, ts), traj = jax.lax.scan(
                one_step, (ps, ts), (keys, jnp.arange(cfg.num_steps)))
        with jax.named_scope(PPO_GAE):
            _, last_v = net.forward(state.params, ts.obs)
            adv, ret = gae(traj["rewards"], traj["values"], traj["dones"],
                           last_v, cfg.gamma, cfg.lam)
        rollout = {
            "obs": traj["obs"], "actions": traj["actions"],
            "logp": traj["logp"], "values": traj["values"],
            "adv": adv, "ret": ret,
        }
        record = {k: traj[k] for k in ("actions", "pi", "values", "rewards",
                                       "dones", "obs_sum")}
        record["last_values"] = last_v
        with jax.named_scope(PPO_UPDATE):
            state, metrics = update(state, rollout, ku)
        if "steps" in metrics:
            record["steps"] = metrics.pop("steps")
        # episode stats reduced in-graph: only scalars cross to the host.
        # The count and sum cross separately — the mean is formed host-
        # side (``_record``) so a zero-episode iteration carries the
        # previous value forward instead of emitting ``0/0 = NaN``.
        episodes, ep_sum = _episode_metrics(traj["dones"], traj["ep_ret"])
        metrics = dict(metrics, episodes=episodes, ep_sum=ep_sum)
        return state, ps, ts, metrics, record

    return jax.jit(train_step, donate_argnums=(0, 1, 2))


def init_train_device(pool, cfg: PPOConfig, seed: int = 0,
                      hidden: tuple[int, ...] = (256, 128, 64)):
    """``train_device``'s set-up: ``(net, state, train_step, (ps, ts),
    key)`` — the policy, its learner state placed on the pool's mesh,
    the jitted ``train_step``, the pool's first state, and the key each
    update splits its own from (``key, kc, ku = split(key, 3)``)."""
    net = ActorCritic(pool.spec, hidden=hidden)
    key = jax.random.PRNGKey(seed)
    key, k_init, k_pool = jax.random.split(key, 3)
    params = net.init(k_init)

    steps_per_iter = cfg.num_steps * pool.batch_size
    total_updates = max(
        1, cfg.total_steps // steps_per_iter
    ) * cfg.epochs * cfg.minibatches
    mesh = getattr(pool, "mesh", None)
    opt, update = make_ppo_update(
        net, cfg, total_updates, keep_steps=True,
        learner_devices=1 if mesh is None else mesh.size)
    state = PPOState(params=params, opt=opt.init(params), step=jnp.int32(0))

    # policy placement (distributed/sharding.py): replicated across the
    # env mesh for small nets, sharded over it for large ones (Seed-RL
    # style); the optimizer moments follow their params.  The whole
    # learner state is committed, so the jitted train_step below
    # inherits it without explicit in_shardings — and takes back on the
    # next call the placement it returned: a leaf left unplaced would
    # come back placed and compile the program a second time.
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.distributed.sharding import policy_shardings

        placement = policy_shardings(
            mesh, params, axis_name=getattr(pool, "axis_name", "env")
        )
        rep = NamedSharding(mesh, PartitionSpec())
        state = jax.device_put(state, PPOState(
            params=placement,
            opt=state.opt.replace(mu=placement, nu=placement, count=rep),
            step=rep,
        ))

    train_step = make_train_step(pool, cfg, net, update)

    ps, ts = pool.reset(k_pool)
    if hasattr(pool, "device_put"):
        ps = pool.device_put(ps)   # pin the env state to the mesh layout
    return net, state, train_step, (ps, ts), key


def train_device(
    pool: "MeshEnvPool | Any",   # any mesh engine (device/device-sharded)
    cfg: PPOConfig,
    seed: int = 0,
    log_fn: Callable[[dict], None] | None = None,
    hidden: tuple[int, ...] = (256, 128, 64),
):
    net, state, train_step, (ps, ts), key = init_train_device(
        pool, cfg, seed, hidden)
    steps_per_iter = cfg.num_steps * pool.batch_size
    n_iters = max(1, cfg.total_steps // steps_per_iter)
    history = []
    t0 = time.time()
    for it in range(n_iters):
        key, kc, ku = jax.random.split(key, 3)
        state, ps, ts, metrics, _ = train_step(state, ps, ts, kc, ku)
        episodes = int(metrics.pop("episodes"))
        ep_sum = float(metrics.pop("ep_sum"))
        rec = {
            "iter": it,
            "env_steps": (it + 1) * steps_per_iter,
            "time_s": time.time() - t0,
            **{k: float(v) for k, v in metrics.items()},
        }
        _record(history, rec, episodes, ep_sum, log_fn)
    return state, net, history


# --------------------------------------------------------------------- #
# pipelined device driver (double-buffered collect/train, V-trace lag
# correction — see the module docstring)
# --------------------------------------------------------------------- #
def train_pipelined(
    pool: "MeshEnvPool | Any",   # any mesh engine (device/device-sharded)
    cfg: PPOConfig,
    seed: int = 0,
    log_fn: Callable[[dict], None] | None = None,
    hidden: tuple[int, ...] = (256, 128, 64),
):
    """Pipelined collect/train over a functional (mesh) engine.

    Two jitted programs per iteration instead of one fused
    ``train_step``:

      * ``collect`` (``build_pipelined_collect_fn``): the donated
        rollout scan, sharded over the env mesh, sampling behind the
        params published by the PREVIOUS iteration's update;
      * ``update`` (``make_vtrace_ppo_update``): the single-device
        learner consuming the PREVIOUS rollout — one policy step stale,
        V-trace corrected.

    Neither program depends on the other inside an iteration, so with
    async dispatch they overlap: the env mesh collects rollout t+1
    while the learner trains on rollout t (double buffering — two
    rollout pytrees in flight).  The learner state is committed to a
    single device: it pays the PPO epochs once, instead of the fused
    program's D replicated copies across the mesh, and its params are
    re-broadcast to the mesh each iteration (the Seed-RL learner→actor
    push).  Returns ``(state, net, history)`` with the same history
    schema as ``train_device`` plus ``rho_behavior`` (mean importance
    ratio pi/mu — the observed policy lag the correction absorbs).
    """
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from repro.core.xla_loop import build_pipelined_collect_fn

    if not is_functional(pool):
        raise ValueError("train_pipelined needs a functional (device-"
                         "family) engine; host engines use "
                         "train_host_pipelined")

    net = ActorCritic(pool.spec, hidden=hidden)
    key = jax.random.PRNGKey(seed)
    key, k_init, k_pool = jax.random.split(key, 3)
    params = net.init(k_init)

    # learner placement: ONE device (the first of the pool's mesh).  The
    # fused path replicates the update across all D shards; the
    # pipelined learner pays it once and pushes params back out.  (A
    # mesh-sharded learner for >1M-param policies is the multi-host
    # disaggregation direction, ROADMAP #1.)
    mesh = getattr(pool, "mesh", None)
    learner_dev = (mesh.devices.flat[0] if mesh is not None
                   else jax.devices()[0])
    learner_sharding = SingleDeviceSharding(learner_dev)
    params = jax.tree.map(
        lambda x: jax.device_put(x, learner_sharding), params
    )

    M = pool.batch_size
    steps_per_iter = cfg.num_steps * M
    total_updates = max(
        1, cfg.total_steps // steps_per_iter
    ) * cfg.epochs * cfg.minibatches
    opt, vupdate = make_vtrace_ppo_update(net, cfg, total_updates)
    state = PPOState(params=params, opt=opt.init(params), step=jnp.int32(0))

    def policy(p, obs, k):
        a, logp, _, _ = net.sample(p, obs, k)
        return a, logp

    collect = build_pipelined_collect_fn(pool, policy, cfg.num_steps)

    def update_step(state, traj, ku):
        state, metrics = vupdate(state, traj, ku)
        episodes, ep_sum = _episode_metrics(traj["dones"], traj["ep_ret"])
        return state, dict(metrics, episodes=episodes, ep_sum=ep_sum)

    update = jax.jit(update_step, donate_argnums=(0,))

    def to_mesh(p):
        """Publish the learner's params to the env mesh (replicated) —
        the per-iteration actor push.  A no-op placement-wise when the
        pool has no mesh."""
        if mesh is None:
            return p
        rep = NamedSharding(mesh, PartitionSpec())
        return jax.tree.map(lambda x: jax.device_put(x, rep), p)

    def to_learner(tree):
        return jax.tree.map(
            lambda x: jax.device_put(x, learner_sharding), tree
        )

    ps, ts = pool.reset(k_pool)
    if hasattr(pool, "device_put"):
        ps = pool.device_put(ps)   # pin the env state to the mesh layout

    # prologue: rollout 0 behind the init params
    key, kc = jax.random.split(key)
    ps, ts, traj_prev = collect(ps, to_mesh(state.params), ts, kc)

    n_iters = max(1, cfg.total_steps // steps_per_iter)
    history: list[dict] = []
    t0 = time.time()
    for it in range(n_iters):
        key, kc, ku = jax.random.split(key, 3)
        # dispatch collect(t+1) behind the CURRENT params — the update
        # dispatched below produces the next ones, so the rollout the
        # learner consumes is always exactly one policy step stale
        ps, ts, traj_next = collect(ps, to_mesh(state.params), ts, kc)
        state, metrics = update(state, to_learner(traj_prev), ku)
        traj_prev = traj_next
        episodes = int(metrics.pop("episodes"))
        ep_sum = float(metrics.pop("ep_sum"))
        rec = {
            "iter": it,
            "env_steps": (it + 1) * steps_per_iter,
            "time_s": time.time() - t0,
            **{k: float(v) for k, v in metrics.items()},
        }
        _record(history, rec, episodes, ep_sum, log_fn)
    return state, net, history


# --------------------------------------------------------------------- #
# multi-host disaggregated driver (env processes + a learner process)
# --------------------------------------------------------------------- #
def train_disaggregated(
    pool: Any,                     # MeshEnvPool on an env-process-only mesh
    cfg: PPOConfig,
    seed: int = 0,
    log_fn: Callable[[dict], None] | None = None,
    hidden: tuple[int, ...] = (256, 128, 64),
    learner_process: int | None = None,
):
    """Actor/learner disaggregation across processes (ROADMAP #1: the
    SRL/Spreeze split).  Multi-controller SPMD: EVERY process of the
    ``jax.distributed`` job calls this with the same arguments; the role
    decides which programs a process actually executes.

      * env processes (all but one) drive ``pool`` — whose mesh must
        live entirely on THEIR devices
        (``distributed.sharding.disaggregated_env_mesh``) — running the
        same donated pipelined collect as ``train_pipelined``;
      * the learner process runs the V-trace PPO update on its own
        hardware, a whole process removed from env stepping;
      * the roles meet only at driver-level ``host_broadcast`` points:
        rollout t crosses env->learner while the env mesh is already
        collecting t+1, and the updated params cross back, placed onto
        the env mesh via the ``policy_shardings`` layout.  The rollout
        the learner consumes is therefore exactly one policy step stale
        — the same lag schedule as ``train_pipelined``, absorbed by the
        same V-trace correction.  (``device_put`` onto another process's
        devices is not portable, so the hand-off ships host-side through
        one replicated broadcast per direction — fixed cost per
        iteration, never inside an engine program.)

    Returns ``(state, net, history)``.  ``history`` is identical on
    every process (metrics ride the params broadcast); ``state`` is
    authoritative on the learner — env processes return the final
    broadcast params over a never-advanced local opt state.
    """
    from repro.core.xla_loop import build_pipelined_collect_fn
    from repro.distributed.sharding import host_broadcast, policy_shardings

    if jax.process_count() < 2:
        raise ValueError("train_disaggregated needs >= 2 processes — join "
                         "them with launch.mesh.initialize_multihost()")
    if not is_functional(pool):
        raise ValueError("train_disaggregated needs a functional (device-"
                         "family) engine")
    if learner_process is None:
        learner_process = jax.process_count() - 1
    is_learner = jax.process_index() == learner_process
    mesh = pool.mesh
    if any(d.process_index == learner_process for d in mesh.devices.flat):
        raise ValueError("pool mesh overlaps the learner process; build it "
                         "with distributed.sharding.disaggregated_env_mesh")
    # the env process that sources the rollout broadcast: wherever the
    # mesh's first device lives (rollouts are replicated env-side first)
    env_src = int(mesh.devices.flat[0].process_index)

    net = ActorCritic(pool.spec, hidden=hidden)
    key = jax.random.PRNGKey(seed)   # same seed everywhere -> same stream
    key, k_init, k_pool = jax.random.split(key, 3)
    params_host = jax.tree.map(np.asarray, net.init(k_init))
    # one explicit sync so every process provably starts from the
    # learner's params (init is deterministic, but the contract is
    # "params come from the learner")
    params_host = host_broadcast(params_host, learner_process)

    M = pool.batch_size
    steps_per_iter = cfg.num_steps * M
    total_updates = max(
        1, cfg.total_steps // steps_per_iter
    ) * cfg.epochs * cfg.minibatches
    opt, vupdate = make_vtrace_ppo_update(net, cfg, total_updates)

    def policy(p, obs, k):
        a, logp, _, _ = net.sample(p, obs, k)
        return a, logp

    collect = build_pipelined_collect_fn(pool, policy, cfg.num_steps)

    def update_step(state, traj, ku):
        state, metrics = vupdate(state, traj, ku)
        episodes, ep_sum = _episode_metrics(traj["dones"], traj["ep_ret"])
        return state, dict(metrics, episodes=episodes, ep_sum=ep_sum)

    update = jax.jit(update_step, donate_argnums=(0,))

    # every process derives the rollout/metrics STRUCTURE abstractly:
    # the learner needs same-shape placeholders for the broadcast it
    # doesn't source (and vice versa), and eval_shape never touches a
    # device, so tracing the env-mesh collect is legal on the learner
    state = PPOState(params=jax.tree.map(jnp.asarray, params_host),
                     opt=opt.init(jax.tree.map(jnp.asarray, params_host)),
                     step=jnp.int32(0))
    k_abs = jax.random.PRNGKey(0)
    abs_ps, abs_ts = jax.eval_shape(pool.reset, k_abs)
    _, _, abs_traj = jax.eval_shape(collect, abs_ps, state.params, abs_ts,
                                    k_abs)
    traj_zeros = jax.tree.map(lambda s: np.zeros(s.shape, s.dtype), abs_traj)
    _, abs_metrics = jax.eval_shape(update_step, state, abs_traj, k_abs)
    metric_keys = sorted(abs_metrics)

    pshard = policy_shardings(mesh, params_host)

    def place_params(p_host):
        """learner->env push: the policy_shardings placement (replicated
        over the env mesh for small nets).  Env processes only — the
        learner's devices are outside this mesh by construction."""
        return jax.tree.map(jax.device_put, p_host, pshard)

    def fetch(tree):
        """Env-side host read: replicate over the env mesh, then numpy."""
        return jax.tree.map(np.asarray, pool.replicate(tree))

    history: list[dict] = []
    traj_host = traj_zeros
    params_dev = None
    key, kc0 = jax.random.split(key)   # split on ALL processes: one stream
    if not is_learner:
        ps, ts = pool.reset(pool.put_replicated(np.asarray(k_pool)))
        ps = pool.device_put(ps)
        params_dev = place_params(params_host)
        # prologue: rollout 0 behind the init params
        ps, ts, traj_prev = collect(ps, params_dev,
                                    ts, pool.put_replicated(np.asarray(kc0)))
        traj_host = fetch(traj_prev)

    n_iters = max(1, cfg.total_steps // steps_per_iter)
    t0 = time.time()
    for it in range(n_iters):
        key, kc, ku = jax.random.split(key, 3)
        # rollout t crosses env->learner (every process participates)
        traj_rx = host_broadcast(traj_host, env_src)
        if is_learner:
            state, metrics = update(state, traj_rx, ku)
            params_host = jax.tree.map(np.asarray, state.params)
            mvec = np.array([float(metrics[k]) for k in metric_keys])
        else:
            # dispatch collect(t+1) behind the CURRENT params NOW — it
            # overlaps with the learner's update on rollout t
            ps, ts, traj_next = collect(ps, params_dev, ts,
                                        pool.put_replicated(np.asarray(kc)))
            mvec = np.zeros((len(metric_keys),), np.float64)
        # updated params (+ metrics) cross back learner->envs
        params_host, mvec = host_broadcast((params_host, mvec),
                                           learner_process)
        if not is_learner:
            params_dev = place_params(params_host)
            traj_host = fetch(traj_next)
        metrics = dict(zip(metric_keys, mvec.tolist()))
        episodes = int(metrics.pop("episodes"))
        ep_sum = float(metrics.pop("ep_sum"))
        rec = {
            "iter": it,
            "env_steps": (it + 1) * steps_per_iter,
            "time_s": time.time() - t0,
            **{k: float(v) for k, v in metrics.items()},
        }
        _record(history, rec, episodes, ep_sum, log_fn)
    if not is_learner:
        state = state.replace(params=jax.tree.map(jnp.asarray, params_host))
    return state, net, history


# --------------------------------------------------------------------- #
# host-engine driver (the paper's Fig. 4 profile path)
# --------------------------------------------------------------------- #
def train_host(
    env_pool,                     # ThreadEnvPool / ForLoopEnv / SubprocessEnv
    spec=None,
    cfg: PPOConfig | None = None,
    seed: int = 0,
    log_fn: Callable[[dict], None] | None = None,
    hidden: tuple[int, ...] = (256, 128, 64),
    tracer: Tracer | None = None,
    registry: MetricsRegistry | None = None,
):
    """Returns (state, net, history, profile) where profile has the paper's
    four timing buckets: env_step / inference / train / other.

    Bucket discipline: JAX dispatch is async, so every bucket is closed
    only after ``block_until_ready`` on that stage's outputs — without
    the fence the ``time.time()`` around ``sample``/``update`` measures
    dispatch, and the compute silently leaks into whichever bucket
    blocks next (historically ``env_step``, inflating the paper's
    Fig. 4 env share).  The buckets are ``obs/trace.py`` fenced spans,
    each also a ``jax.profiler`` annotation, so a profiler session
    records them beside the device's operations; the returned profile
    is the tracer's ``totals()`` (pass a ``tracer`` to share one).  A ``registry`` receives each iteration record as
    ``ppo_*`` metrics.

    ``spec`` defaults to ``env_pool.spec`` (every protocol engine
    carries it); the explicit argument remains for backward compat.
    """
    if spec is None:
        spec = env_pool.spec
    if cfg is None:
        cfg = PPOConfig()
    net = ActorCritic(spec, hidden=hidden)
    key = jax.random.PRNGKey(seed)
    key, k_init = jax.random.split(key)
    params = net.init(k_init)

    M = getattr(env_pool, "batch_size", env_pool.num_envs)
    steps_per_iter = cfg.num_steps * M
    total_updates = max(1, cfg.total_steps // steps_per_iter) \
        * cfg.epochs * cfg.minibatches
    opt, update = make_ppo_update(net, cfg, total_updates)
    state = PPOState(params=params, opt=opt.init(params), step=jnp.int32(0))

    sample = jax.jit(net.sample)
    forward = jax.jit(net.forward)
    update = jax.jit(update, donate_argnums=(0,))
    gae_fn = jax.jit(
        lambda r, v, d, lv: gae(r, v, d, lv, cfg.gamma, cfg.lam)
    )

    if hasattr(env_pool, "async_reset"):
        env_pool.async_reset()
        out = env_pool.recv()
    else:
        out = env_pool.reset()

    # ONE fencing implementation: each bucket is an obs/trace.py span;
    # ``sp.fence(...)`` supplies the outputs block_until_ready must wait
    # for before the span closes, exactly the old hand-rolled discipline
    tr = tracer if tracer is not None else Tracer()
    history = []
    n_iters = max(1, cfg.total_steps // steps_per_iter)
    t_start = time.time()
    for it in range(n_iters):
        traj: dict[str, list] = {k: [] for k in
                                 ("obs", "actions", "logp", "values",
                                  "rewards", "dones", "ep_ret")}
        for t in range(cfg.num_steps):
            with tr.span("inference") as sp:
                key, ks = jax.random.split(key)
                obs = jnp.asarray(out["obs"])
                a, logp, v, _ = sample(state.params, obs, ks)
                # fence the bucket: the dispatch returns futures; without
                # blocking, inference compute would be billed to env_step
                sp.fence((a, logp, v))
                a_np = np.asarray(a)
            with tr.span("env_step"):
                new_out = env_pool.step(a_np, out["env_id"])
            with tr.span("other"):
                traj["obs"].append(obs)
                traj["actions"].append(a)
                traj["logp"].append(logp)
                traj["values"].append(v)
                traj["rewards"].append(np.asarray(new_out["reward"]))
                traj["dones"].append(np.asarray(new_out["done"]))
                traj["ep_ret"].append(
                    np.asarray(new_out["episode_return"])
                )
                out = new_out

        with tr.span("other") as sp:   # GAE time belongs to other
            rewards = jnp.asarray(np.stack(traj["rewards"]))
            dones = jnp.asarray(np.stack(traj["dones"]))
            values = jnp.stack(traj["values"])
            _, last_v = forward(state.params, jnp.asarray(out["obs"]))
            adv, ret = gae_fn(rewards, values, dones, last_v)
            rollout = {
                "obs": jnp.stack(traj["obs"]),
                "actions": jnp.stack(traj["actions"]),
                "logp": jnp.stack(traj["logp"]),
                "values": values,
                "adv": adv, "ret": ret,
            }
            sp.fence((adv, ret))
        with tr.span("train") as sp:
            key, ku = jax.random.split(key)
            state, metrics = update(state, rollout, ku)
            sp.fence(metrics["loss"])

        done_arr = np.stack(traj["dones"])
        rets = np.stack(traj["ep_ret"])[done_arr]
        rec = {
            "iter": it, "env_steps": (it + 1) * steps_per_iter,
            "time_s": time.time() - t_start,
            **{k: float(v) for k, v in metrics.items()},
        }
        _record(history, rec, int(rets.size), float(rets.sum()), log_fn,
                registry)
    totals = tr.totals()
    prof = {k: totals.get(k, 0.0)
            for k in ("env_step", "inference", "train", "other")}
    return state, net, history, prof


# --------------------------------------------------------------------- #
# pipelined host driver: actor thread -> StateBufferQueue -> learner
# --------------------------------------------------------------------- #
def train_host_pipelined(
    env_pool,                     # ThreadEnvPool / ForLoopEnv / SubprocessEnv
    spec=None,
    cfg: PPOConfig | None = None,
    seed: int = 0,
    log_fn: Callable[[dict], None] | None = None,
    hidden: tuple[int, ...] = (256, 128, 64),
    tracer: Tracer | None = None,
    registry: MetricsRegistry | None = None,
):
    """The pipelined driver over a host engine — Appendix D's queues on
    an actual hot path.

    An actor thread loops ``sample -> step`` (inference behind the
    latest params the learner has published) and streams every served
    batch into a ``StateBufferQueue`` via ``put_batch`` — one slice
    write into the pre-allocated ring, no copies on take.  The learner
    thread ``take``s ``num_steps`` blocks, stacks the rollout, and runs
    the same V-trace-corrected PPO update as ``train_pipelined``
    (behavior log-probs recorded by the actor; values/target log-probs
    recomputed under the current params).  The ring's bounded occupancy
    is the backpressure: the actor blocks once ``num_blocks`` batches
    are outstanding, so its policy lag stays bounded by the queue depth
    rather than growing with learner stalls.

    Returns ``(state, net, history, profile)``; the profile buckets are
    ``actor_wait`` (learner time blocked on the queue — env stepping
    that did NOT overlap), ``train`` and ``other`` — ``obs/trace.py``
    fenced spans, same as ``train_host`` (pass a ``tracer`` to share
    one; its totals sum over threads, and a profiler session records
    each span on the thread that ran it).
    """
    if spec is None:
        spec = env_pool.spec
    if cfg is None:
        cfg = PPOConfig()

    from repro.core.buffers import StateBufferQueue

    net = ActorCritic(spec, hidden=hidden)
    key = jax.random.PRNGKey(seed)
    key, k_init = jax.random.split(key)
    params = net.init(k_init)

    M = getattr(env_pool, "batch_size", env_pool.num_envs)
    steps_per_iter = cfg.num_steps * M
    total_updates = max(1, cfg.total_steps // steps_per_iter) \
        * cfg.epochs * cfg.minibatches
    opt, vupdate = make_vtrace_ppo_update(net, cfg, total_updates)
    state = PPOState(params=params, opt=opt.init(params), step=jnp.int32(0))
    # NO donate_argnums here: the actor thread samples with the published
    # params buffers concurrently, and donating state would invalidate the
    # exact buffers it holds mid-inference (unlike train_pipelined, where
    # the collect program gets its own replicated device_put copy).
    update = jax.jit(vupdate)
    sample = jax.jit(net.sample)

    obs_dt = np.dtype(spec.obs_spec.dtype)
    act_dt = np.dtype(spec.act_spec.dtype)
    fields = {
        "obs": (tuple(spec.obs_spec.shape), obs_dt),
        "next_obs": (tuple(spec.obs_spec.shape), obs_dt),
        "actions": (tuple(spec.act_spec.shape), act_dt),
        "logp": ((), np.float32),
        "rewards": ((), np.float32),
        "dones": ((), np.bool_),
        "ep_ret": ((), np.float32),
    }
    queue = StateBufferQueue(fields, M, env_pool.num_envs)

    # the published behavior params: written by the learner, read by the
    # actor (a dict-slot swap is atomic under the GIL)
    published = {"params": state.params}
    stop = threading.Event()
    failure: list[BaseException] = []

    def actor():
        try:
            akey = jax.random.PRNGKey(seed + 1)
            if hasattr(env_pool, "async_reset"):
                env_pool.async_reset()
                out = env_pool.recv()
            else:
                out = env_pool.reset()
            while not stop.is_set():
                akey, ks = jax.random.split(akey)
                obs = jnp.asarray(out["obs"])
                a, logp, _, _ = sample(published["params"], obs, ks)
                a_np = np.asarray(a)
                new_out = env_pool.step(a_np, out["env_id"])
                batch = {
                    "obs": np.asarray(out["obs"]),
                    "next_obs": np.asarray(new_out["obs"]),
                    "actions": a_np,
                    "logp": np.asarray(logp),
                    "rewards": np.asarray(new_out["reward"], np.float32),
                    "dones": np.asarray(new_out["done"], bool),
                    "ep_ret": np.asarray(
                        new_out["episode_return"], np.float32
                    ),
                }
                while not stop.is_set():
                    try:
                        # bounded-occupancy backpressure: wait for the
                        # learner, re-checking stop so shutdown can't
                        # deadlock against a full ring
                        queue.put_batch(batch, timeout=0.1)
                        break
                    except TimeoutError:
                        continue
                out = new_out
        except BaseException as e:  # surface actor crashes to the learner
            failure.append(e)
            stop.set()

    thread = threading.Thread(target=actor, daemon=True)
    thread.start()

    tr = tracer if tracer is not None else Tracer()
    history: list[dict] = []
    n_iters = max(1, cfg.total_steps // steps_per_iter)
    t_start = time.time()
    try:
        for it in range(n_iters):
            with tr.span("actor_wait"):
                blocks = []
                for _ in range(cfg.num_steps):
                    while True:
                        if failure:
                            raise RuntimeError(
                                "pipelined actor thread died"
                            ) from failure[0]
                        try:
                            blocks.append(queue.take(timeout=5.0))
                            break
                        except TimeoutError:
                            continue

            with tr.span("other"):
                traj = {
                    k: jnp.asarray(np.stack([b[k] for b in blocks]))
                    for k in ("obs", "actions", "logp", "rewards",
                              "dones", "ep_ret")
                }
                traj["last_obs"] = jnp.asarray(blocks[-1]["next_obs"])

            with tr.span("train") as sp:
                key, ku = jax.random.split(key)
                state, metrics = update(state, traj, ku)
                sp.fence(metrics["loss"])
                published["params"] = state.params  # learner->actor push

            dones = np.stack([b["dones"] for b in blocks])
            rets = np.stack([b["ep_ret"] for b in blocks])[dones]
            rec = {
                "iter": it, "env_steps": (it + 1) * steps_per_iter,
                "time_s": time.time() - t_start,
                **{k: float(v) for k, v in metrics.items()},
            }
            _record(history, rec, int(rets.size), float(rets.sum()),
                    log_fn, registry)
    finally:
        stop.set()
        thread.join(timeout=10.0)
    totals = tr.totals()
    prof = {k: totals.get(k, 0.0)
            for k in ("actor_wait", "train", "other")}
    return state, net, history, prof


# --------------------------------------------------------------------- #
# engine-agnostic entry (core.protocol dispatch)
# --------------------------------------------------------------------- #
def train(
    pool: "EnvPool",
    cfg: PPOConfig,
    seed: int = 0,
    log_fn: Callable[[dict], None] | None = None,
    hidden: tuple[int, ...] = (256, 128, 64),
):
    """PPO over ANY engine via the ``EnvPool`` protocol.

    Functional (device-family) pools run the fully-jitted on-device
    driver; host pools run the numpy driver.  Returns ``(state, net,
    history)`` either way; call ``train_host`` directly if the paper's
    Fig. 4 timing buckets are needed.
    """
    if is_functional(pool):
        return train_device(pool, cfg, seed=seed, log_fn=log_fn, hidden=hidden)
    state, net, history, _prof = train_host(
        pool, pool.spec, cfg, seed=seed, log_fn=log_fn, hidden=hidden
    )
    return state, net, history
