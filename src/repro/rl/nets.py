"""Actor-critic policy networks (pure JAX, shared-trunk, paper §4.2 style:
Nature-CNN for Atari-like pixel obs, ELU MLP for state obs — matching the
rl_games/CleanRL configurations in the paper's appendix tables)."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.specs import EnvSpec


def _dense(key, din, dout, scale=None):
    scale = scale if scale is not None else math.sqrt(2.0 / din)
    k1, _ = jax.random.split(key)
    return {
        "w": jax.random.normal(k1, (din, dout), jnp.float32) * scale,
        "b": jnp.zeros((dout,), jnp.float32),
    }


def _apply_dense(p, x):
    return x @ p["w"] + p["b"]


def _conv(key, cin, cout, kh, kw):
    scale = math.sqrt(2.0 / (cin * kh * kw))
    return {
        "w": jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * scale,
        "b": jnp.zeros((cout,), jnp.float32),
    }


def _apply_conv(p, x, stride, lhs="NHWC"):
    out = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding="VALID",
        dimension_numbers=(lhs, "HWIO", "NHWC"),
    )
    return out + p["b"]


class ActorCritic:
    """Discrete or continuous actor-critic over an EnvSpec."""

    def __init__(self, spec: EnvSpec, hidden: tuple[int, ...] = (256, 128, 64)):
        self.spec = spec
        self.hidden = hidden
        self.pixel = len(spec.obs_spec.shape) == 3
        self.discrete = jnp.issubdtype(jnp.dtype(spec.act_spec.dtype), jnp.integer)
        if self.discrete:
            self.act_dim = spec.num_actions
        else:
            self.act_dim = int(spec.act_spec.shape[0])

    def init(self, key: jax.Array) -> dict[str, Any]:
        ks = jax.random.split(key, 10)
        p: dict[str, Any] = {}
        if self.pixel:
            p["conv1"] = _conv(ks[0], self.spec.obs_spec.shape[0], 32, 8, 8)
            p["conv2"] = _conv(ks[1], 32, 64, 4, 4)
            p["conv3"] = _conv(ks[2], 64, 64, 3, 3)
            trunk_in = 64 * 7 * 7
            p["fc"] = _dense(ks[3], trunk_in, 512)
            feat = 512
        else:
            feat = int(self.spec.obs_spec.shape[0])
            for i, h in enumerate(self.hidden):
                p[f"mlp{i}"] = _dense(ks[i], feat, h)
                feat = h
        p["pi"] = _dense(ks[7], feat, self.act_dim, scale=0.01)
        p["v"] = _dense(ks[8], feat, 1, scale=1.0)
        if not self.discrete:
            p["log_std"] = jnp.zeros((self.act_dim,), jnp.float32)
        return p

    def trunk(self, p: dict[str, Any], obs: jnp.ndarray,
              batch_minor: bool = False) -> jnp.ndarray:
        """Features of a batch of observations.  With ``batch_minor``, a
        pixel net's ``obs`` is conv1's operand already made: ``obs /
        255`` in bfloat16, laid out ``(H, W, C, N)``
        (``kernels/minibatch_obs``); widening it to float32 is exact,
        and conv1 rounds it back at ``Precision.DEFAULT``."""
        if self.pixel:
            if batch_minor:
                x, lhs = obs.astype(jnp.float32), "HWCN"
            else:
                x = obs.astype(jnp.float32) / 255.0
                x, lhs = jnp.transpose(x, (0, 2, 3, 1)), "NHWC"
            x = jax.nn.relu(_apply_conv(p["conv1"], x, 4, lhs))
            x = jax.nn.relu(_apply_conv(p["conv2"], x, 2))
            x = jax.nn.relu(_apply_conv(p["conv3"], x, 1))
            x = x.reshape(x.shape[0], -1)
            return jax.nn.relu(_apply_dense(p["fc"], x))
        x = obs.astype(jnp.float32)
        for i in range(len(self.hidden)):
            x = jax.nn.elu(_apply_dense(p[f"mlp{i}"], x))
        return x

    def forward(self, p: dict[str, Any], obs: jnp.ndarray,
                batch_minor: bool = False):
        """Returns (logits_or_mean, value)."""
        feat = self.trunk(p, obs, batch_minor)
        pi = _apply_dense(p["pi"], feat)
        v = _apply_dense(p["v"], feat)[..., 0]
        return pi, v

    # ---------------- distribution ops ----------------------------- #
    def sample(self, p, obs, key):
        """Returns (action, logp, value, logits_or_mean)."""
        pi, v = self.forward(p, obs)
        if self.discrete:
            a = jax.random.categorical(key, pi)
            logp = jax.nn.log_softmax(pi)[jnp.arange(a.shape[0]), a]
            return a.astype(self.spec.act_spec.dtype), logp, v, pi
        std = jnp.exp(p["log_std"])
        noise = jax.random.normal(key, pi.shape)
        a = pi + std * noise
        logp = -0.5 * jnp.sum(
            ((a - pi) / std) ** 2 + 2 * p["log_std"] + jnp.log(2 * jnp.pi), -1
        )
        return a.astype(jnp.float32), logp, v, pi

    def logp_entropy(self, p, obs, actions, batch_minor: bool = False):
        pi, v = self.forward(p, obs, batch_minor)
        if self.discrete:
            ls = jax.nn.log_softmax(pi)
            logp = ls[jnp.arange(actions.shape[0]), actions.astype(jnp.int32)]
            ent = -jnp.sum(jax.nn.softmax(pi) * ls, -1)
            return logp, ent, v
        std = jnp.exp(p["log_std"])
        logp = -0.5 * jnp.sum(
            ((actions - pi) / std) ** 2 + 2 * p["log_std"] + jnp.log(2 * jnp.pi), -1
        )
        ent = jnp.sum(p["log_std"] + 0.5 * jnp.log(2 * jnp.pi * jnp.e)) * jnp.ones(
            actions.shape[0]
        )
        return logp, ent, v
