"""Mesh construction + multi-host launch entry points (harness contract).

FUNCTIONS, not module-level constants — and **no module-level jax
import**: ``force_host_device_count`` must be callable BEFORE the first
``import jax`` anywhere in the process (XLA parses
``--xla_force_host_platform_device_count`` once, at backend init, and
the device count is locked afterwards).  Every entry point imports jax
lazily, so ``from repro.launch import mesh`` is always safe as a
process's first line.

Multi-host model (ROADMAP open item: SRL/Spreeze-style scale-out):

  * each process runs the SAME driver program (multi-controller SPMD);
  * ``initialize_multihost()`` wires the processes into one jax
    runtime — afterwards ``jax.devices()`` is the GLOBAL device list
    and ``make_env_mesh(D)`` builds the 1-D env mesh over it, so a
    ``MeshEnvPool`` built on that mesh spans processes with zero
    engine changes (see ``core/protocol.py`` for the contract);
  * on CPU the cross-process collective backend is gloo — selected
    here because it must be configured before the backend initializes.
"""

from __future__ import annotations

import os
import re
import sys

# coordinator address recorded by initialize_multihost() so BENCH
# provenance headers (bench_meta) can attribute multi-host artifacts.
_COORDINATOR: str | None = None

_DEVICE_COUNT_FLAG = "--xla_force_host_platform_device_count"


def force_host_device_count(n: int, platform: str | None = "cpu") -> None:
    """Simulate ``n`` host devices: the ONE set-before-import helper.

    Replaces any inherited ``--xla_force_host_platform_device_count``
    in ``XLA_FLAGS`` (subprocess checkers inherit the parent's
    environment) and pins ``JAX_PLATFORMS`` so a stray accelerator
    plugin can't shadow the simulated mesh.  Must run before jax is
    imported anywhere in the process — raises if it's too late, because
    failing silently would run every downstream mesh assertion at the
    wrong device count.
    """
    if "jax" in sys.modules:
        raise RuntimeError(
            "force_host_device_count() must be called before jax is "
            "imported: XLA locks the simulated device count at backend "
            "init (import repro.launch.mesh first — it never imports jax)"
        )
    flags = re.sub(_DEVICE_COUNT_FLAG + r"=\S+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = " ".join(
        [f"{_DEVICE_COUNT_FLAG}={int(n)}"] + flags.split())
    if platform is not None:
        os.environ["JAX_PLATFORMS"] = platform


def initialize_multihost(
    coordinator: str,
    num_processes: int,
    process_id: int,
    *,
    local_device_count: int | None = None,
) -> tuple[int, int]:
    """Join this process into a multi-host jax runtime.

    ``coordinator`` is ``host:port`` of process 0 (loopback
    ``127.0.0.1:<port>`` in CI).  ``local_device_count`` optionally
    calls :func:`force_host_device_count` first (so a worker's whole
    preamble is this one call).  Selects the gloo CPU collective
    backend — the config must land before the first backend touch, and
    it is ignored on real accelerators.  Returns
    ``(process_id, process_count)`` as reported by the joined runtime;
    afterwards ``jax.devices()`` is global and ``make_env_mesh`` spans
    processes.
    """
    if local_device_count is not None:
        force_host_device_count(local_device_count)
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes),
        process_id=int(process_id),
    )
    global _COORDINATOR
    _COORDINATOR = coordinator
    return jax.process_index(), jax.process_count()


def multihost_info() -> dict:
    """Provenance fields for BENCH artifact headers (``bench_meta``).

    Backfill-safe: single-process runs (or a process that never
    imported jax) report ``process_count=1, process_id=0,
    coordinator=None`` — exactly what every pre-multihost artifact
    implicitly was.
    """
    info = {"process_count": 1, "process_id": 0, "coordinator": _COORDINATOR}
    if "jax" in sys.modules:
        import jax

        try:
            info["process_count"] = int(jax.process_count())
            info["process_id"] = int(jax.process_index())
        except Exception:  # backend not initializable — keep defaults
            pass
    return info


def make_env_mesh(num_shards: int | None = None, axis_name: str = "env"):
    """1-D env mesh over the first ``num_shards`` GLOBAL devices.

    The single definition lives with the engine
    (``core/engine.py::make_env_mesh``); after
    :func:`initialize_multihost` the device list it enumerates is the
    global one, so the returned mesh spans processes.
    """
    from repro.core.engine import make_env_mesh as _make

    return _make(num_shards, axis_name)


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules place
    arrays with ``with_sharding_constraint``, which refuses the
    ``Explicit`` axes ``make_mesh`` defaults to."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(devices: int | None = None):
    """Tiny mesh over however many devices exist (tests)."""
    import jax

    n = devices or len(jax.devices())
    model = 2 if n % 2 == 0 and n > 1 else 1
    return _auto_mesh((n // model, model), ("data", "model"))


# TPU v5e hardware model (roofline constants; harness spec)
PEAK_FLOPS_BF16 = 197e12     # per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
