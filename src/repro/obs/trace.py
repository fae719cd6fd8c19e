"""Fenced host spans on the profiler's clock, and the names of the
pool's phases inside the device program.

JAX dispatch is asynchronous: ``time.time()`` around a jitted call
measures dispatch, and the compute silently leaks into whichever span
blocks next.  ``rl/ppo.py::train_host`` solved this per-bucket by
closing each timing bucket only after ``jax.block_until_ready`` on that
stage's outputs; this module generalizes that discipline into ONE
reusable implementation:

    tr = Tracer()
    with tr.span("inference") as sp:
        a, logp, v, _ = sample(params, obs, key)
        sp.fence((a, logp, v))      # span closes AFTER the compute
    with tr.span("env_step"):
        out = pool.step(a, ids)     # host-blocking: no fence needed

    tr.totals()                     # {"inference": 1.2, ...} seconds

Spans nest (they are plain context managers).  ``totals()`` aggregates
wall seconds per span name across all threads: exactly the paper's
Fig-4 buckets when the spans are named ``env_step``/``inference``/
``train``/``other``.  Each span also opens a
``jax.profiler.TraceAnnotation`` of its name for its whole duration,
fence included, so a running ``jax.profiler`` session records it in the
same ``.xplane.pb`` as the device's operations, on one clock.  With no
session running the annotation records nothing.

The device side: the pool's phases run inside one XLA program, so a
host span cannot split them.  Each phase is wrapped in
``jax.named_scope`` with one of the names below; the scope reaches the
compiled HLO as the ``op_name`` metadata of every operation it emits
(``jit(collect)/collect/while/body/pool.gather/gather``) and from there
the profiler's ``tf_op`` stat.  Scopes are metadata only: they change
no operation, fusion or number.  A profile reader assigns each device
operation to the innermost of these names on its path; a fusion carries
its root instruction's path, so it counts under its root's phase.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable

import jax

# device phases of the pool (core/engine.py, core/scheduler.py,
# obs/telemetry.py) and of the collect loop (core/xla_loop.py)
SEND = "pool.send"              # action scatter, step cost
SELECT = "pool.select"          # the scheduler: select, enqueue, complete
GATHER = "pool.gather"          # served lanes' state, action, result rows
ENV_STEP = "pool.env_step"      # the batched env's step and observe
WRITEBACK = "pool.writeback"    # scatter of the stepped states and results
# no engine emits TICK; it stays because bench/lib/phases.py mirrors SCOPES
TICK = "pool.tick"
TELEMETRY = "pool.telemetry"    # in-graph counters
TRANSFORM = "pool.transform"    # the transform pipeline at serve time
COLLECT = "collect"             # the collect scan, outside the pool
POLICY = "collect.policy"       # the policy inside the collect scan
# the fused PPO step (rl/ppo.py::make_train_step)
PPO_COLLECT = "ppo.collect"
PPO_GAE = "ppo.gae"
PPO_UPDATE = "ppo.update"

SCOPES = (SEND, SELECT, GATHER, ENV_STEP, WRITEBACK, TICK, TELEMETRY,
          TRANSFORM, COLLECT, POLICY, PPO_COLLECT, PPO_GAE, PPO_UPDATE)


def _fence(payload: Any) -> None:
    """Block until every array in ``payload`` is computed; non-jax
    payloads (numpy, python) pass through jax's own no-op handling."""
    jax.block_until_ready(payload)


class Span:
    """One open span.  ``fence(x)`` registers outputs the span must
    block on before closing (the Fig-4 bucket discipline)."""

    __slots__ = ("_payload",)

    def __init__(self) -> None:
        self._payload: Any = None

    def fence(self, payload: Any) -> Any:
        self._payload = payload
        return payload


class _SpanCtx:
    __slots__ = ("_tracer", "_name", "_payload", "_span", "_t0", "_note")

    def __init__(self, tracer: "Tracer", name: str, fence: Any) -> None:
        self._tracer = tracer
        self._name = name
        self._payload = fence
        self._span: Span | None = None
        self._t0 = 0.0
        self._note = None

    def __enter__(self) -> Span:
        self._span = Span()
        self._note = jax.profiler.TraceAnnotation(self._name)
        self._note.__enter__()
        self._t0 = self._tracer._clock()
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        try:
            payload = self._span._payload
            if payload is None:
                payload = self._payload
            if payload is not None and exc_type is None:
                _fence(payload)
            self._tracer._close(self._name, self._t0, self._tracer._clock())
        finally:
            self._note.__exit__(exc_type, exc, tb)


class Tracer:
    """Fenced spans: wall seconds per span name, summed over threads."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self._clock = clock
        self._lock = threading.Lock()
        self._totals: dict[str, float] = {}

    def span(self, name: str, fence: Any = None) -> _SpanCtx:
        """Context manager for one fenced span.  ``fence`` (or a later
        ``sp.fence(...)`` call on the yielded handle) supplies the
        outputs to ``block_until_ready`` before the span closes; omit
        it for host-blocking work."""
        return _SpanCtx(self, name, fence)

    def _close(self, name: str, t0: float, t1: float) -> None:
        with self._lock:
            self._totals[name] = self._totals.get(name, 0.0) + (t1 - t0)

    def totals(self) -> dict[str, float]:
        """Aggregate wall seconds per span name (all threads) — the
        Fig-4 profile buckets."""
        with self._lock:
            return dict(self._totals)


__all__ = ["SCOPES", "Span", "Tracer"]
