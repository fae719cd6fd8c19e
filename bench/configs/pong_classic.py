"""Plain reference of the ``pong_classic`` configuration: Atari Pong PPO
as CleanRL's ``ppo_atari_envpool.py`` runs it (sync pool, 128-step
rollouts, Nature CNN, clipped PPO), over the repo's Pong-like game.
It imports nothing of the program; it is straightforward ``jax.numpy``
run under ``jax.jit`` in blocks, so that it fits on one chip.

Game (per lane, per agent step): the step costs ``4`` emulator frames,
``+2`` after a point, ``+3`` on an episode's first step.  Each frame
the agent's paddle moves by 2 rows up (actions 2, 4) or down (3, 5),
the opponent follows the ball at up to 1.6 rows, the ball moves by its
velocity, bounces off the walls and off either paddle (speed x1.05 and
spin ``0.35 (y - paddle) / 12``), and a side scores when the ball
passes the other's paddle; the ball is then served again from the
centre at a random angle in (-0.7, 0.7).  An episode ends at 21 points
for either side (terminated) or at ``max_episode_steps`` (truncated),
and is reset at once from a key split off the lane's own.  The random
draws use ``jax.random`` on the lane's raw key, as the game does.

Pipeline (per served lane, integer fixed point): the 210 x 160 RGB
screen (background, two paddles, ball; compares of f32 index
arithmetic), luma ``(9798 R + 19235 G + 3735 B + 2**14) >> 15``, an
area resize to 84 x 84 with weights quantised to 8 bits that sum to
256 per row (largest-remainder rounding), a stack of the last 4 frames
refilled with the first frame of a new episode, and the reward clipped
to [-1, 1].

Policy: the Nature CNN (Mnih et al. 2015): conv 32x8x8/4, 64x4x4/2,
64x3x3/1, fc 512, logits and a value, ReLU, pixels scaled by 1/255;
every product at the configuration's precision, ``Precision.DEFAULT``
(one bfloat16 pass with float32 sums on a TPU), or, for the control,
with each operand first rounded to ``float8_e4m3fn``, the precision
below.  Against float32 products (``Precision.HIGHEST``) a sound
program's logits and gradients read as far off as the control's.

PPO step: GAE (gamma, lambda) run back in time down each lane's own
column; per minibatch the advantages are normalised (population
standard deviation), the loss is the clipped surrogate, plus
``vf_coef`` times the clipped value loss, minus ``ent_coef`` times the
entropy; the gradient, summed over blocks of the minibatch, is clipped
to a global norm and applied by one Adam step (b1 0.9, b2 0.999, eps
1e-5) at a learning rate annealed linearly to 0 over the minibatch
steps of ``total_steps``.  Which rows a minibatch holds is an input.

Departures from CleanRL that the program makes and this reference
follows: the game is the repo's Pong-like stand-in, not the ALE ROM,
without sticky actions, no-op starts, episodic life or frame max-pool;
the learning rate falls per minibatch step, not per update; advantages
are normalised with the population standard deviation (torch's
``std`` is the sample one); parameters start He-normal, not
orthogonal (the check starts from the program's own parameters, so
this never enters a comparison).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------- game
H = W = 84                      # the game's grid
PADDLE_LEN = 12
WIN_SCORE = 21
FRAMESKIP, SERVE_FRAMES, RESET_FRAMES = 4, 2, 3
MIN_COST, MAX_COST = 4, 9
# the native screen and its palette: background, agent, opponent, ball
RGB_H, RGB_W = 210, 160
BG, AGENT, ENEMY, BALL = ((144, 72, 17), (92, 186, 92), (213, 130, 74),
                          (236, 236, 236))
STATE_KEYS = ("ball_x", "ball_y", "ball_vx", "ball_vy", "paddle_y",
              "enemy_y", "score_us", "score_them", "just_scored", "t",
              "rng", "ep_return", "reward_acc")
CHECKSUM_MUL = 2654435761
# the policy's products at the configuration's precision: one bfloat16
# pass on a TPU (float32 on a CPU)
CHECK_PRECISION = jax.lax.Precision.DEFAULT
ADAM_B1 = 0.9


def fresh_lane(key):
    """A new episode's state from one raw key."""
    rng, k1, k2 = jax.random.split(key, 3)
    angle = jax.random.uniform(k1, (), jnp.float32, -0.7, 0.7)
    side = jnp.where(jax.random.bernoulli(k2), 1.0, -1.0)
    z = jnp.float32(0.0)
    return dict(
        ball_x=jnp.float32(W / 2), ball_y=jnp.float32(H / 2),
        ball_vx=side * 1.5 * jnp.cos(angle), ball_vy=1.5 * jnp.sin(angle),
        paddle_y=jnp.float32(H / 2), enemy_y=jnp.float32(H / 2),
        score_us=jnp.int32(0), score_them=jnp.int32(0),
        just_scored=jnp.bool_(False), t=jnp.int32(0), rng=rng,
        ep_return=z, reward_acc=z)


def frame(s, action):
    """One emulator frame of one lane."""
    half = PADDLE_LEN / 2
    dy = jnp.where((action == 2) | (action == 4), -2.0,
                   jnp.where((action == 3) | (action == 5), 2.0, 0.0))
    paddle_y = jnp.clip(s["paddle_y"] + dy, half, H - half)
    enemy_dy = jnp.clip(s["ball_y"] - s["enemy_y"], -1.6, 1.6)
    enemy_y = jnp.clip(s["enemy_y"] + enemy_dy, half, H - half)
    bx = s["ball_x"] + s["ball_vx"]
    by = s["ball_y"] + s["ball_vy"]
    vy = jnp.where((by < 1) | (by > H - 2), -s["ball_vy"], s["ball_vy"])
    by = jnp.clip(by, 1.0, H - 2.0)
    hit_pad = (bx >= W - 4) & (jnp.abs(by - paddle_y) <= half + 1)
    hit_enemy = (bx <= 3) & (jnp.abs(by - enemy_y) <= half + 1)
    vx = jnp.where(hit_pad | hit_enemy, -s["ball_vx"] * 1.05, s["ball_vx"])
    vy = jnp.where(hit_pad, vy + 0.35 * (by - paddle_y) / PADDLE_LEN, vy)
    vy = jnp.where(hit_enemy, vy + 0.35 * (by - enemy_y) / PADDLE_LEN, vy)
    bx = jnp.clip(bx, 0.0, jnp.float32(W - 1))
    we = (bx >= W - 1) & ~hit_pad
    they = (bx <= 0) & ~hit_enemy
    scored = we | they
    reward = jnp.where(we, 1.0, jnp.where(they, -1.0, 0.0))
    rng, k = jax.random.split(s["rng"])
    angle = jax.random.uniform(k, (), jnp.float32, -0.7, 0.7)
    serve_vx = jnp.where(we, -1.5, 1.5) * jnp.cos(angle)
    bx = jnp.where(scored, W / 2, bx)
    by = jnp.where(scored, H / 2, by)
    vx = jnp.where(scored, serve_vx, vx)
    vy = jnp.where(scored, 1.5 * jnp.sin(angle), vy)
    return dict(
        s, ball_x=bx, ball_y=by, ball_vx=jnp.clip(vx, -3.0, 3.0),
        ball_vy=jnp.clip(vy, -3.0, 3.0), paddle_y=paddle_y,
        enemy_y=enemy_y, score_us=s["score_us"] + we.astype(jnp.int32),
        score_them=s["score_them"] + they.astype(jnp.int32),
        just_scored=scored | s["just_scored"], rng=rng,
        reward_acc=s["reward_acc"] + reward)


def agent_step(s, action, max_episode_steps: int):
    """One agent step of one lane: ``(state, reward, done)``, the state
    of a finished episode already reset."""
    cost = (FRAMESKIP + jnp.where(s["just_scored"], SERVE_FRAMES, 0)
            + jnp.where(s["t"] == 0, RESET_FRAMES, 0))
    cost = jnp.clip(cost, MIN_COST, MAX_COST)
    s = dict(s, reward_acc=jnp.zeros_like(s["reward_acc"]),
             just_scored=jnp.bool_(False))
    for i in range(MAX_COST):
        s = jax.tree.map(lambda n, o, i=i: jnp.where(i < cost, n, o),
                         frame(s, action), s)
    s = dict(s, t=s["t"] + 1)
    reward = s["reward_acc"]
    terminated = (s["score_us"] >= WIN_SCORE) | (s["score_them"] >= WIN_SCORE)
    done = terminated | ((s["t"] >= max_episode_steps) & ~terminated)
    rng, reset_key = jax.random.split(s["rng"])
    s = dict(s, rng=rng, ep_return=s["ep_return"] + reward)
    s = jax.tree.map(lambda f, o: jnp.where(done, f, o), fresh_lane(reset_key),
                     s)
    return s, reward, done


# ------------------------------------------------------------ pipeline
def render(s):
    """(N,) lane states -> (N, 210, 160, 3) uint8 screens."""
    sy = jnp.float32(RGB_H / H)
    sx = jnp.float32(RGB_W / W)
    ys = jnp.arange(RGB_H, dtype=jnp.float32)[:, None]
    xs = jnp.arange(RGB_W, dtype=jnp.float32)[None, :]
    e = lambda k: s[k].astype(jnp.float32)[:, None, None]  # noqa: E731
    half = jnp.float32(PADDLE_LEN / 2)
    ball = ((jnp.abs(ys - e("ball_y") * sy) <= sy)
            & (jnp.abs(xs - e("ball_x") * sx) <= sx))
    pad = ((jnp.abs(ys - e("paddle_y") * sy) <= half * sy)
           & (xs >= jnp.float32(RGB_W) - 3.0 * sx))
    enemy = ((jnp.abs(ys - e("enemy_y") * sy) <= half * sy)
             & (xs <= 2.0 * sx))
    planes = [jnp.where(ball, BALL[c], jnp.where(
        pad, AGENT[c], jnp.where(enemy, ENEMY[c], BG[c])))
        for c in range(3)]
    return jnp.stack(planes, -1).astype(jnp.uint8)


def luma(rgb):
    c = rgb.astype(jnp.int32)
    y = (9798 * c[..., 0] + 19235 * c[..., 1] + 3735 * c[..., 2]
         + (1 << 14)) >> 15
    return y.astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def area_weights(n_in: int, n_out: int, shift: int = 8) -> np.ndarray:
    """``(n_out, n_in)`` int weights: each output row's box coverage of
    the input, quantised to ``2**shift`` in all (largest remainders
    rounded up, ties to the lower index)."""
    scale = n_in / n_out
    out = np.zeros((n_out, n_in), np.int64)
    for i in range(n_out):
        lo, hi = i * scale, (i + 1) * scale
        w = np.zeros(n_in)
        for j in range(int(np.floor(lo)), min(int(np.ceil(hi)), n_in)):
            w[j] = max(0.0, min(hi, j + 1.0) - max(lo, float(j))) / scale
        q = w / w.sum() * (1 << shift)
        base = np.floor(q).astype(np.int64)
        order = np.argsort(-(q - base), kind="stable")
        base[order[:(1 << shift) - int(base.sum())]] += 1
        out[i] = base
    return out


def area_resize(img, out_h: int = 84, out_w: int = 84):
    """(N, H, W) uint8 -> (N, out_h, out_w) uint8: rows, round, then
    columns, round (every sum is an integer below 2**24, so the f32
    products are exact)."""
    hp = jax.lax.Precision.HIGHEST
    a = jnp.asarray(area_weights(img.shape[-2], out_h), jnp.float32)
    b = jnp.asarray(area_weights(img.shape[-1], out_w), jnp.float32)
    t = jnp.einsum("oh,nhw->now", a, img.astype(jnp.float32), precision=hp)
    t = ((t.astype(jnp.int32) + 128) >> 8).astype(jnp.float32)
    o = jnp.einsum("pw,now->nop", b, t, precision=hp)
    return ((o.astype(jnp.int32) + 128) >> 8).astype(jnp.uint8)


def checksum(obs):
    """One uint32 per row: sum of its elements times (position + 1) x
    ``CHECKSUM_MUL``, modulo 2**32."""
    x = obs.reshape(obs.shape[0], -1).astype(jnp.uint32)
    w = (jnp.arange(1, x.shape[1] + 1, dtype=jnp.uint32)
         * jnp.uint32(CHECKSUM_MUL))
    return jnp.sum(x * w, axis=1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnums=(3,))
def replay(state, stack, actions, max_episode_steps: int):
    """Every lane of a rollout from its state and frame stack under the
    actions ``(T, N)``.  Returns the stacks the policy saw ``(T, N, 4,
    84, 84)``, the rewards (clipped) and dones served after each
    action, the checksum of each stack served after an action, and the
    last stack served."""
    step = jax.vmap(functools.partial(agent_step,
                                      max_episode_steps=max_episode_steps))

    def body(carry, a):
        s, st = carry
        s, reward, done = step(s, a)
        f = area_resize(luma(render(s)))[:, None]
        pushed = jnp.concatenate([st[:, 1:], f], 1)
        new = jnp.where(done[:, None, None, None],
                        jnp.broadcast_to(f, pushed.shape), pushed)
        out = (st, jnp.clip(reward, -1.0, 1.0), done, checksum(new))
        return (s, new), out

    (_, last), (seen, rew, done, sums) = jax.lax.scan(
        body, (state, stack), actions)
    return seen, rew, done, sums, last


# -------------------------------------------------------------- policy
@jax.custom_vjp
def f8(x):
    """The control's operand rounding: a round trip through
    ``float8_e4m3fn`` (3 mantissa bits, subnormals down to 2**-9,
    range to 448, beyond any operand here).  Its gradient passes
    straight through: the backward products take the rounded operands
    of the forward pass and float32 cotangents."""
    return x.astype(jnp.float8_e4m3fn).astype(x.dtype)


f8.defvjp(lambda x: (f8(x), None), lambda _, g: (g,))


def forward(params, obs, rnd=None, precision=CHECK_PRECISION):
    """Nature CNN: ``(B, 4, 84, 84)`` uint8 -> ``(logits, value)``, every
    product at ``precision``."""
    rnd = rnd or (lambda x: x)
    x = jnp.transpose(obs.astype(jnp.float32) / 255.0, (0, 2, 3, 1))
    for name, stride in (("conv1", 4), ("conv2", 2), ("conv3", 1)):
        p = params[name]
        x = jax.lax.conv_general_dilated(
            rnd(x), rnd(p["w"]), (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        x = jax.nn.relu(x + p["b"])
    x = x.reshape(x.shape[0], -1)
    dense = lambda p, x: (jnp.dot(rnd(x), rnd(p["w"]), precision=precision)  # noqa: E731
                          + p["b"])
    x = jax.nn.relu(dense(params["fc"], x))
    return dense(params["pi"], x), dense(params["v"], x)[:, 0]


@functools.partial(jax.jit, static_argnums=(3, 4))
def forward_steps(params, seen, t0, steps: int, control: bool = False):
    """``forward`` over the stacks of ``steps`` rollout steps from step
    ``t0`` of ``seen`` ``(T, N, 4, 84, 84)``: ``(steps * N, A)`` logits
    and ``(steps * N,)`` values."""
    x = jax.lax.dynamic_slice_in_dim(seen, t0, steps)
    return forward(params, x.reshape((-1,) + seen.shape[2:]),
                   f8 if control else None)


@functools.partial(jax.jit, static_argnums=(4, 5))
def gae(rewards, values, dones, last_values, gamma: float, lam: float):
    """``(T, N)`` advantages and returns, back in time down each lane's
    column."""
    def back(carry, x):
        adv, v_next = carry
        r, v, d = x
        nd = 1.0 - d.astype(jnp.float32)
        delta = r + gamma * v_next * nd - v
        adv = delta + gamma * lam * nd * adv
        return (adv, v), adv

    _, advs = jax.lax.scan(back, (jnp.zeros_like(last_values), last_values),
                           (rewards, values, dones), reverse=True)
    return advs, advs + values


def _sample_losses(params, batch, adv, ppo: dict, rnd, precision):
    """The summed per-sample PPO loss over a block."""
    logits, v = forward(params, batch["obs"], rnd, precision)
    ls = jax.nn.log_softmax(logits)
    logp = jnp.take_along_axis(ls, batch["actions"][:, None], 1)[:, 0]
    ent = -jnp.sum(jax.nn.softmax(logits) * ls, -1)
    ratio = jnp.exp(logp - batch["logp"])
    c = ppo["clip"]
    pg = jnp.maximum(-adv * ratio, -adv * jnp.clip(ratio, 1 - c, 1 + c))
    v_clip = batch["values"] + jnp.clip(v - batch["values"], -c, c)
    vf = 0.5 * jnp.maximum((v - batch["ret"]) ** 2, (v_clip - batch["ret"]) ** 2)
    return jnp.sum(pg + ppo["vf_coef"] * vf - ppo["ent_coef"] * ent)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def block_grad(params, seen, data, rows, adv_mean, adv_std, ppo: tuple,
               control: bool, precision):
    """The gradient of the summed loss over the rollout rows ``rows``
    (flat step-major indices into ``seen`` ``(T, N, ...)`` and ``data``
    ``(T * N,)``), advantages normalised by the minibatch's moments."""
    n = seen.shape[1]
    batch = {k: v[rows] for k, v in data.items()}
    batch["obs"] = seen[rows // n, rows % n]
    adv = (batch.pop("adv") - adv_mean) / (adv_std + 1e-8)
    return jax.grad(_sample_losses)(params, batch, adv, dict(ppo),
                                    f8 if control else None, precision)


@jax.jit
def clipped_mean(grad_sum, rows, max_grad_norm):
    """A minibatch's summed gradient over its ``rows``, clipped to a
    global norm."""
    g = jax.tree.map(lambda x: x / rows, grad_sum)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, max_grad_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda x: x * scale, g)


@jax.jit
def adam_step(params, mu, nu, g, lr, count):
    """One Adam step on the gradient ``g`` (b1 0.9, b2 0.999, eps 1e-5,
    bias-corrected at step ``count`` from 1): ``(params, mu, nu)``."""
    b1, b2, eps = ADAM_B1, 0.999, 1e-5
    cf = count.astype(jnp.float32)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / (1 - b1 ** cf))
                                  / (jnp.sqrt(v / (1 - b2 ** cf)) + eps)),
        params, mu, nu)
    return params, mu, nu


def learning_rate(ppo: dict, step: int, total_minibatch_steps: int):
    """The rate of minibatch step ``step`` (from 0), annealed linearly to
    0 over ``total_minibatch_steps``."""
    frac = np.float32(1.0) - np.clip(
        np.float32(step) / np.float32(total_minibatch_steps),
        np.float32(0.0), np.float32(1.0))
    return np.float32(ppo["lr"]) * (frac if ppo["anneal_lr"]
                                    else np.float32(1.0))


def minibatch_grad(params, seen, data, idx, ppo: dict, block: int,
                   control: bool = False, precision=CHECK_PRECISION):
    """The clipped mean gradient of the PPO loss over the minibatch
    ``idx`` (flat step-major rows of the rollout), summed over blocks of
    ``block`` rows, every product at ``precision``.  ``seen`` holds the
    rollout's stacks ``(T, N, ...)`` and ``data`` the flat ``(T * N,)``
    actions, logp, values, adv and ret."""
    loss_key = tuple(sorted((k, float(ppo[k])) for k in
                            ("clip", "vf_coef", "ent_coef")))
    adv = data["adv"][idx]
    g = None
    for j in range(0, idx.shape[0], block):
        gb = block_grad(params, seen, data, idx[j:j + block], adv.mean(),
                        adv.std(), loss_key, control, precision)
        g = gb if g is None else jax.tree.map(jnp.add, g, gb)
    return clipped_mean(g, np.float32(idx.shape[0]),
                        np.float32(ppo["max_grad_norm"]))
