"""In-engine transform pipeline — batched obs/reward preprocessing fused
into every engine's hot path.

EnvPool's Atari numbers rest on running the classic preprocessing stack
(frame stacking, reward clipping, normalization, episodic-life) *inside*
the C++ engine rather than as per-env Python wrappers (paper §3.4; CuLE
makes the same argument for keeping preprocessing on-device).  This
module is that subsystem for the JAX engines: a functional, composable
pipeline applied to every served batch *inside* the engine's jitted
recv, so on the device family the preprocessing lowers into the same
XLA program as the fused multi-substep itself — zero host round-trips,
zero per-env Python.

Contract
--------
A ``Transform`` is a pytree of per-lane state plus pure functions — the
same safety-contract style as ``core/scheduler.py``:

  * ``transform_spec(spec)`` — the spec transformer: returns the
    ``EnvSpec`` as seen downstream, so ``pool.spec.obs_spec`` (shape,
    dtype, bounds) stays truthful after stacking/casting.  Applied at
    pool construction; drivers never see the raw spec.
  * ``init(spec, num_envs)`` — fresh transform state.  ``per_lane``
    transforms return leaves with a leading ``num_envs`` dim (sharded
    to ``(D, N/D, ...)`` exactly like env states); global transforms
    (e.g. ``NormalizeObs`` moments) return fixed-size leaves that are
    replicated per shard and kept identical by collective merges.
  * ``apply(state_block, ts, spec, axis_name=None)`` — operates on one
    served SoA block (leading dim M): per-lane state rows are gathered
    by the engine for the served lanes, transformed alongside the
    ``TimeStep``, and scattered back.  Pure, static-shaped, safe under
    ``jit`` / ``vmap`` / ``lax.scan`` / ``shard_map``.  The only
    permitted communication is a fixed-size collective on *statistics*
    (cost-matrix style — never env data): ``NormalizeObs`` ``psum``\\ s
    its per-block moment sums over ``axis_name`` when the engine runs
    inside a mesh, which keeps every shard's replicated moments
    identical and the merged moments mesh-size-invariant.
  * ``on_reset`` semantics ride on EnvPool auto-reset: when a served
    step has ``done=True`` its obs is already the next episode's first
    observation, so stateful transforms re-initialize that lane's state
    from it in the same ``apply`` call (``FrameStack`` refills the
    stack with the first frame, exactly like a wrapper would on
    ``reset()``).  A per-lane ``fresh`` latch handles the pool's own
    first serve after ``init``.
  * ``np_init`` / ``np_apply`` — the numpy mirror: ``ThreadEnvPool``,
    ``ForLoopEnv`` and ``SubprocessEnv`` apply the IDENTICAL pipeline
    host-side (same formulas, same f32 arithmetic), so transformed
    streams are bitwise-identical across device and host engines for
    the deterministic transforms (stack / clip / cast).

The pipeline applies exactly once per served result, in list order, to
the *raw* merged block (device engines store raw results and transform
at serve time).  The policy-visible consequence: transforms
never change per-env trajectories (reward/done as produced by the env,
engine scheduling, auto-reset points) — only the *served view* of them.

Shipped transforms: ``FrameStack(k)``, ``RewardClip``, ``ObsCast``
(cast + affine scale), ``EpisodicLife``, ``NormalizeObs`` (running
mean/var, psum-merged across a sharded mesh), and the image family
``Grayscale`` / ``Resize(h, w)`` / ``Crop`` backed by the
``kernels/image`` Pallas family (compiled on TPU, bit-identical jnp
fallback elsewhere; integer fixed-point math, so the numpy mirrors are
bitwise too — the full classic Atari path ``Grayscale -> Resize(84,84)
-> FrameStack(4) -> RewardClip`` ships as the ``PongClassic-v5``
preset).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import numpy as np

from repro.core.specs import ArraySpec, EnvSpec, TimeStep
from repro.utils.pytree import tree_gather


def _jnp():
    import jax.numpy as jnp

    return jnp


# --------------------------------------------------------------------- #
# base contract
# --------------------------------------------------------------------- #
class Transform:
    """One preprocessing stage (see module docstring for the contract)."""

    name: str = "identity"
    # True: state leaves carry a leading num_envs dim and the engine
    # gathers/scatters the served rows.  False: state is pool-global
    # (fixed size, shard-replicated) and passed through whole.
    per_lane: bool = False

    # ---------------- spec transformer ---------------- #
    def transform_spec(self, spec: EnvSpec) -> EnvSpec:
        return spec

    # ---------------- jax path ---------------- #
    def init(self, spec: EnvSpec, num_envs: int) -> Any:
        """Fresh transform state (pytree; () for stateless)."""
        return ()

    def apply(self, state: Any, ts: TimeStep, spec: EnvSpec,
              axis_name: str | None = None) -> tuple[Any, TimeStep]:
        """Transform one served block; ``spec`` is this stage's INPUT
        spec (the env spec with all upstream transforms applied)."""
        return state, ts

    # ---------------- numpy mirror (host engines) ---------------- #
    def np_init(self, spec: EnvSpec, num_envs: int) -> Any:
        return ()

    def np_apply(self, state: Any, out: dict[str, np.ndarray],
                 spec: EnvSpec) -> tuple[Any, dict[str, np.ndarray]]:
        return state, out


def _bcast(mask, like):
    """Reshape a (M,) mask against a (M, ...) array (np or jnp)."""
    return mask.reshape(mask.shape + (1,) * (like.ndim - mask.ndim))


# --------------------------------------------------------------------- #
# FrameStack
# --------------------------------------------------------------------- #
class FrameStack(Transform):
    """Stack the last ``k`` served observations per lane (oldest first —
    the classic DQN/ALE wrapper layout).  On auto-reset (``done``) and
    on the lane's first serve, the stack is refilled by broadcasting the
    episode's first observation, exactly like ``gym.wrappers.FrameStack``
    after ``reset()``."""

    name = "frame_stack"
    per_lane = True

    def __init__(self, k: int = 4):
        if k < 1:
            raise ValueError(f"FrameStack needs k >= 1, got {k}")
        self.k = int(k)

    def transform_spec(self, spec: EnvSpec) -> EnvSpec:
        o = spec.obs_spec
        return dataclasses.replace(
            spec, obs_spec=dataclasses.replace(o, shape=(self.k,) + o.shape)
        )

    def init(self, spec: EnvSpec, num_envs: int) -> Any:
        jnp = _jnp()
        o = spec.obs_spec
        return {
            "buf": jnp.zeros((num_envs, self.k) + o.shape, o.dtype),
            "fresh": jnp.ones((num_envs,), jnp.bool_),
        }

    def apply(self, state, ts, spec, axis_name=None):
        jnp = _jnp()
        obs = ts.obs
        pushed = jnp.concatenate([state["buf"][:, 1:], obs[:, None]], axis=1)
        bcast = jnp.broadcast_to(obs[:, None], pushed.shape)
        reset = state["fresh"] | ts.done
        buf = jnp.where(_bcast(reset, pushed), bcast, pushed)
        new = {"buf": buf, "fresh": jnp.zeros_like(state["fresh"])}
        return new, ts.replace(obs=buf)

    def np_init(self, spec, num_envs):
        o = spec.obs_spec
        return {
            "buf": np.zeros((num_envs, self.k) + o.shape, o.dtype),
            "fresh": np.ones((num_envs,), np.bool_),
        }

    def np_apply(self, state, out, spec):
        obs = np.asarray(out["obs"])
        pushed = np.concatenate([state["buf"][:, 1:], obs[:, None]], axis=1)
        bcast = np.broadcast_to(obs[:, None], pushed.shape)
        reset = state["fresh"] | np.asarray(out["done"], np.bool_)
        buf = np.where(_bcast(reset, pushed), bcast, pushed)
        state = {"buf": buf, "fresh": np.zeros_like(state["fresh"])}
        out = dict(out)
        out["obs"] = buf
        return state, out


# --------------------------------------------------------------------- #
# RewardClip
# --------------------------------------------------------------------- #
class RewardClip(Transform):
    """Clip the per-step reward to ``[lo, hi]`` (DQN-style; EnvPool's
    ``reward_clip``).  ``episode_return`` stays the RAW return — the
    engine reports true episode scores while the agent trains on the
    clipped signal."""

    name = "reward_clip"

    def __init__(self, lo: float = -1.0, hi: float = 1.0):
        self.lo = float(lo)
        self.hi = float(hi)

    def apply(self, state, ts, spec, axis_name=None):
        jnp = _jnp()
        return state, ts.replace(reward=jnp.clip(ts.reward, self.lo, self.hi))

    def np_apply(self, state, out, spec):
        out = dict(out)
        out["reward"] = np.clip(
            np.asarray(out["reward"], np.float32), self.lo, self.hi
        )
        return state, out


# --------------------------------------------------------------------- #
# ObsCast — dtype cast + affine scale
# --------------------------------------------------------------------- #
class ObsCast(Transform):
    """Cast observations to ``dtype`` and apply ``obs * scale + offset``
    (e.g. ``ObsCast(jnp.float32, scale=1/255)`` for uint8 pixels).  The
    arithmetic is plain f32 IEEE ops so the numpy mirror is bitwise-
    identical to the device path."""

    name = "obs_cast"

    def __init__(self, dtype: Any = np.float32, scale: float = 1.0,
                 offset: float = 0.0):
        self.dtype = np.dtype(dtype)
        self.scale = float(scale)
        self.offset = float(offset)

    def _bounds(self, o: ArraySpec) -> tuple[float | None, float | None]:
        lo = None if o.minimum is None else o.minimum * self.scale + self.offset
        hi = None if o.maximum is None else o.maximum * self.scale + self.offset
        if lo is not None and hi is not None and lo > hi:   # negative scale
            lo, hi = hi, lo
        return lo, hi

    def transform_spec(self, spec: EnvSpec) -> EnvSpec:
        o = spec.obs_spec
        lo, hi = self._bounds(o)
        return dataclasses.replace(
            spec,
            obs_spec=dataclasses.replace(
                o, dtype=self.dtype, minimum=lo, maximum=hi
            ),
        )

    def _cast(self, xp, obs):
        obs = obs.astype(self.dtype)
        if self.scale != 1.0:
            obs = obs * xp.asarray(self.scale, self.dtype)
        if self.offset != 0.0:
            obs = obs + xp.asarray(self.offset, self.dtype)
        return obs

    def apply(self, state, ts, spec, axis_name=None):
        return state, ts.replace(obs=self._cast(_jnp(), ts.obs))

    def np_apply(self, state, out, spec):
        out = dict(out)
        out["obs"] = self._cast(np, np.asarray(out["obs"]))
        return state, out


# --------------------------------------------------------------------- #
# EpisodicLife
# --------------------------------------------------------------------- #
class EpisodicLife(Transform):
    """Mark a *life loss* as episode end for the agent without resetting
    the underlying env (EnvPool's ``episodic_life``).  The engine envs
    carry no life counter, so the life-loss signal is ``reward <
    threshold`` (a point conceded in the Pong-like env).  Only the
    ``done``/``terminated`` flags served to the agent change; the env
    keeps playing the same rally and the engine's auto-reset points are
    untouched.  Place BEFORE ``FrameStack`` to also restart the stack on
    life loss (the DQN wrapper order)."""

    name = "episodic_life"

    def __init__(self, threshold: float = 0.0):
        self.threshold = float(threshold)

    def apply(self, state, ts, spec, axis_name=None):
        lost = ts.reward < self.threshold
        return state, ts.replace(
            done=ts.done | lost, terminated=ts.terminated | lost
        )

    def np_apply(self, state, out, spec):
        lost = np.asarray(out["reward"], np.float32) < self.threshold
        out = dict(out)
        out["done"] = np.asarray(out["done"], np.bool_) | lost
        out["terminated"] = np.asarray(out["terminated"], np.bool_) | lost
        return state, out


# --------------------------------------------------------------------- #
# image transforms (kernels/image: Pallas on TPU, jnp fallback off-TPU;
# integer fixed-point math -> device path == numpy mirror, bitwise)
# --------------------------------------------------------------------- #
class Grayscale(Transform):
    """RGB -> luma (the ALE/OpenCV coefficients in 15-bit fixed point).
    Spec rule: drops the trailing channel dim, ``(..., H, W, 3) uint8 ->
    (..., H, W) uint8``.  Stateless and integer-exact, so every engine
    (and the host numpy mirror) emits the identical stream."""

    name = "grayscale"

    def __init__(self, backend: str = "auto"):
        from repro.kernels.backend import resolve_backend

        resolve_backend(backend)   # validate eagerly
        self.backend = backend

    def transform_spec(self, spec: EnvSpec) -> EnvSpec:
        o = spec.obs_spec
        if len(o.shape) < 3 or o.shape[-1] != 3:
            raise ValueError(
                f"Grayscale wants (..., H, W, 3) observations; got {o.shape}"
            )
        if np.dtype(o.dtype) != np.uint8:
            raise ValueError(
                f"Grayscale wants uint8 observations; got {o.dtype}"
            )
        return dataclasses.replace(
            spec, obs_spec=dataclasses.replace(o, shape=o.shape[:-1])
        )

    def apply(self, state, ts, spec, axis_name=None):
        from repro.kernels.image.ops import grayscale

        return state, ts.replace(obs=grayscale(ts.obs, backend=self.backend))

    def np_apply(self, state, out, spec):
        from repro.kernels.image.ref import grayscale_np

        out = dict(out)
        out["obs"] = grayscale_np(np.asarray(out["obs"]))
        return state, out


class Resize(Transform):
    """Fixed-point resampling of the trailing (H, W) dims to ``(h, w)``
    (``area`` — the ALE/EnvPool downsampler — or ``bilinear``).  Spec
    rule: replaces the last two dims, ``(..., H, W) uint8 ->
    (..., h, w) uint8``; apply ``Grayscale`` first for RGB streams.
    Stateless, integer-exact across all backends and the numpy mirror."""

    name = "resize"

    def __init__(self, h: int, w: int, method: str = "area",
                 backend: str = "auto"):
        from repro.kernels.backend import resolve_backend
        from repro.kernels.image.ref import RESIZE_METHODS

        if h < 1 or w < 1:
            raise ValueError(f"Resize needs h, w >= 1; got ({h}, {w})")
        if method not in RESIZE_METHODS:
            raise ValueError(
                f"unknown resize method {method!r}; known: {RESIZE_METHODS}"
            )
        resolve_backend(backend)
        self.h, self.w = int(h), int(w)
        self.method = method
        self.backend = backend

    def transform_spec(self, spec: EnvSpec) -> EnvSpec:
        o = spec.obs_spec
        if len(o.shape) < 2:
            raise ValueError(
                f"Resize wants (..., H, W) observations; got {o.shape}"
            )
        if np.dtype(o.dtype) != np.uint8:
            raise ValueError(f"Resize wants uint8 observations; got {o.dtype}")
        return dataclasses.replace(
            spec,
            obs_spec=dataclasses.replace(
                o, shape=o.shape[:-2] + (self.h, self.w)
            ),
        )

    def apply(self, state, ts, spec, axis_name=None):
        from repro.kernels.image.ops import resize

        return state, ts.replace(
            obs=resize(ts.obs, self.h, self.w, self.method,
                       backend=self.backend)
        )

    def np_apply(self, state, out, spec):
        from repro.kernels.image.ref import resize_np

        out = dict(out)
        out["obs"] = resize_np(np.asarray(out["obs"]), self.h, self.w,
                               self.method)
        return state, out


class Crop(Transform):
    """Static-window crop of the trailing (H, W) dims.  Spec rule:
    ``(..., H, W) -> (..., height, width)`` with the window validated
    against the input spec at construction time."""

    name = "crop"

    def __init__(self, top: int, left: int, height: int, width: int,
                 backend: str = "auto"):
        from repro.kernels.backend import resolve_backend

        resolve_backend(backend)
        self.top, self.left = int(top), int(left)
        self.height, self.width = int(height), int(width)
        self.backend = backend

    def transform_spec(self, spec: EnvSpec) -> EnvSpec:
        from repro.kernels.image.ref import check_crop

        o = spec.obs_spec
        if len(o.shape) < 2:
            raise ValueError(
                f"Crop wants (..., H, W) observations; got {o.shape}"
            )
        check_crop(o.shape[-2], o.shape[-1], self.top, self.left,
                   self.height, self.width)
        return dataclasses.replace(
            spec,
            obs_spec=dataclasses.replace(
                o, shape=o.shape[:-2] + (self.height, self.width)
            ),
        )

    def apply(self, state, ts, spec, axis_name=None):
        from repro.kernels.image.ops import crop

        return state, ts.replace(
            obs=crop(ts.obs, self.top, self.left, self.height, self.width,
                     backend=self.backend)
        )

    def np_apply(self, state, out, spec):
        from repro.kernels.image.ref import crop_reference

        out = dict(out)
        out["obs"] = crop_reference(np.asarray(out["obs"]), self.top,
                                    self.left, self.height, self.width)
        return state, out


# --------------------------------------------------------------------- #
# NormalizeObs
# --------------------------------------------------------------------- #
class NormalizeObs(Transform):
    """Normalize observations by running mean/std (the classic MuJoCo
    preprocessing).  State is pool-global running moments in the
    Welford/Chan parallel form (count, mean, M2 — per-element f32; the
    naive Σx²−mean² form loses the variance to f32 cancellation), of
    fixed obs-spec size.

    Sharded pools merge each served block's contribution with
    fixed-size ``lax.psum``\\ s of the per-shard batch statistics over
    the mesh axis (statistics only, never env data — the cost-matrix
    collective style), so every shard's replicated moments stay
    identical and the running moments are mesh-size-invariant (up to
    f32 summation order).  The block is normalized with the moments
    *including* it.
    """

    name = "normalize_obs"

    def __init__(self, eps: float = 1e-8, clip: float | None = 10.0):
        self.eps = float(eps)
        self.clip = None if clip is None else float(clip)

    def transform_spec(self, spec: EnvSpec) -> EnvSpec:
        o = spec.obs_spec
        lim = self.clip
        return dataclasses.replace(
            spec,
            obs_spec=dataclasses.replace(
                o, dtype=np.dtype(np.float32),
                minimum=None if lim is None else -lim,
                maximum=lim,
            ),
        )

    def init(self, spec: EnvSpec, num_envs: int) -> Any:
        jnp = _jnp()
        shape = spec.obs_spec.shape
        return {
            "count": jnp.zeros((), jnp.float32),
            "mean": jnp.zeros(shape, jnp.float32),
            "m2": jnp.zeros(shape, jnp.float32),
        }

    def apply(self, state, ts, spec, axis_name=None):
        import jax.numpy as jnp
        from jax import lax

        x = ts.obs.astype(jnp.float32)
        nb = jnp.float32(x.shape[0])
        bsum = x.sum(axis=0)
        if axis_name is not None:
            # fixed-size collectives on statistics only (never env data)
            nb = lax.psum(nb, axis_name)
            bsum = lax.psum(bsum, axis_name)
        bmean = bsum / nb
        d2 = ((x - bmean) ** 2).sum(axis=0)
        if axis_name is not None:
            d2 = lax.psum(d2, axis_name)
        # Chan's parallel batch merge of (count, mean, M2)
        total = state["count"] + nb
        delta = bmean - state["mean"]
        mean = state["mean"] + delta * (nb / total)
        m2 = state["m2"] + d2 + delta * delta * (state["count"] * nb / total)
        var = jnp.maximum(m2 / total, 0.0)
        norm = (x - mean) / jnp.sqrt(var + self.eps)
        if self.clip is not None:
            norm = jnp.clip(norm, -self.clip, self.clip)
        return {"count": total, "mean": mean, "m2": m2}, ts.replace(obs=norm)

    def np_init(self, spec, num_envs):
        shape = spec.obs_spec.shape
        return {
            "count": np.zeros((), np.float32),
            "mean": np.zeros(shape, np.float32),
            "m2": np.zeros(shape, np.float32),
        }

    def np_apply(self, state, out, spec):
        x = np.asarray(out["obs"], np.float32)
        nb = np.float32(x.shape[0])
        bmean = (x.sum(axis=0) / nb).astype(np.float32)
        d2 = ((x - bmean) ** 2).sum(axis=0).astype(np.float32)
        total = np.float32(state["count"] + nb)
        delta = bmean - state["mean"]
        mean = (state["mean"] + delta * (nb / total)).astype(np.float32)
        m2 = (state["m2"] + d2
              + delta * delta * (state["count"] * nb / total)).astype(np.float32)
        var = np.maximum(m2 / total, 0.0)
        norm = (x - mean) / np.sqrt(var + np.float32(self.eps))
        if self.clip is not None:
            norm = np.clip(norm, -self.clip, self.clip)
        out = dict(out)
        out["obs"] = norm.astype(np.float32)
        return {"count": total, "mean": mean, "m2": m2}, out


# --------------------------------------------------------------------- #
# the pipeline
# --------------------------------------------------------------------- #
class TransformPipeline:
    """An ordered list of transforms bound to one env spec + engine
    context.  Engines hold one pipeline and call:

      * ``init(num_envs)`` (device) / ``np_init(num_envs)`` (host) —
        the per-pool transform state tuple (lives on ``PoolState``
        alongside ``SchedState`` for the device family);
      * ``gather(tf_state, idx)`` / ``scatter(tf_state, idx, block)`` —
        per-lane state rows for one served block (global states pass
        through whole);
      * ``apply(block, ts)`` / ``np_apply(out_dict)`` — the fused
        per-serve transformation, applied exactly once per served
        result in list order.
    """

    def __init__(self, transforms: Sequence[Transform], spec: EnvSpec,
                 axis_name: str | None = None):
        self.transforms = tuple(transforms)
        for t in self.transforms:
            if not isinstance(t, Transform):
                raise TypeError(
                    f"transforms must be Transform instances, got {t!r}"
                )
        self.axis_name = axis_name
        self.in_spec = spec
        # chained per-stage input specs; out_spec is what drivers see
        self.stage_specs: tuple[EnvSpec, ...] = ()
        s = spec
        stage_specs = []
        for t in self.transforms:
            stage_specs.append(s)
            s = t.transform_spec(s)
            if s.act_spec is not spec.act_spec:
                raise ValueError(
                    f"transform {t.name!r} must not change act_spec"
                )
        self.stage_specs = tuple(stage_specs)
        self.out_spec = s

    def __bool__(self) -> bool:
        return bool(self.transforms)

    def __len__(self) -> int:
        return len(self.transforms)

    # ---------------- jax path (device engines) ---------------- #
    def init(self, num_envs: int) -> tuple:
        return tuple(
            t.init(s, num_envs)
            for t, s in zip(self.transforms, self.stage_specs)
        )

    def gather(self, tf_state: tuple, idx: Any) -> tuple:
        return tuple(
            tree_gather(s, idx) if t.per_lane else s
            for t, s in zip(self.transforms, tf_state)
        )

    def scatter(self, tf_state: tuple, idx: Any, block: tuple) -> tuple:
        import jax

        out = []
        for t, full, blk in zip(self.transforms, tf_state, block):
            if t.per_lane:
                out.append(jax.tree.map(
                    lambda f, b: f.at[idx].set(b), full, blk
                ))
            else:
                out.append(blk)
        return tuple(out)

    def apply(self, block: tuple, ts: TimeStep) -> tuple[tuple, TimeStep]:
        new = []
        for t, s, spec in zip(self.transforms, block, self.stage_specs):
            s, ts = t.apply(s, ts, spec, axis_name=self.axis_name)
            new.append(s)
        return tuple(new), ts

    # ---------------- numpy mirror (host engines) ---------------- #
    def np_init(self, num_envs: int) -> list:
        return [
            t.np_init(s, num_envs)
            for t, s in zip(self.transforms, self.stage_specs)
        ]

    def np_apply(self, tf_state: list, out: dict[str, np.ndarray]
                 ) -> tuple[list, dict[str, np.ndarray]]:
        """Apply the pipeline to one host recv block in place of the
        device path: gather per-lane rows by ``env_id``, transform,
        scatter back."""
        import jax

        ids = np.asarray(out["env_id"], np.int64)

        def scatter(full, blk):
            full[ids] = blk
            return full

        new_state = list(tf_state)
        for i, (t, s, spec) in enumerate(
            zip(self.transforms, tf_state, self.stage_specs)
        ):
            if t.per_lane:
                # generic pytree gather/scatter, mirroring the device
                # path — any np-array pytree state works, not just dicts
                blk = jax.tree.map(lambda v: v[ids], s)
                blk, out = t.np_apply(blk, out, spec)
                new_state[i] = jax.tree.map(scatter, s, blk)
            else:
                new_state[i], out = t.np_apply(s, out, spec)
        return new_state, out


def resolve_transforms(transforms: Sequence[Transform] | None,
                       default: Sequence[Transform] = ()
                       ) -> tuple[Transform, ...]:
    """``None`` selects the task's registered default pipeline; an
    explicit sequence (including ``[]`` / ``()`` for raw) replaces it."""
    if transforms is None:
        return tuple(default)
    return tuple(transforms)


__all__ = [
    "Crop",
    "EpisodicLife",
    "FrameStack",
    "Grayscale",
    "NormalizeObs",
    "ObsCast",
    "Resize",
    "RewardClip",
    "Transform",
    "TransformPipeline",
    "resolve_transforms",
]
