"""Engine-wide observability: in-graph counters, metrics, trace spans.

Three pieces, consumed by every engine row of the matrix:

  * ``obs.telemetry`` — the ``Telemetry`` pytree of in-graph counters
    riding on ``PoolState`` (plus the ``HostTelemetry`` numpy mirror),
    surfaced via ``pool.stats()``;
  * ``obs.metrics``   — the unified registry (counters / gauges /
    fixed-bucket histograms, labeled series, JSON export) every
    reporting surface publishes through;
  * ``obs.trace``     — fenced host spans: the ``block_until_ready``
    bucket discipline as a reusable context manager, each span also a
    ``jax.profiler`` annotation; and the ``jax.named_scope`` names of
    the pool's phases inside the device program.
"""

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    publish_history,
    publish_pool_stats,
    publish_serve_stats,
)
from repro.obs.telemetry import (
    WAIT_EDGES,
    HostTelemetry,
    Telemetry,
    init_telemetry,
    snapshot_device,
    stats_to_jsonable,
)
from repro.obs.trace import Span, Tracer

__all__ = [
    "WAIT_EDGES",
    "Counter",
    "Gauge",
    "Histogram",
    "HostTelemetry",
    "MetricsRegistry",
    "Span",
    "Telemetry",
    "Tracer",
    "init_telemetry",
    "publish_history",
    "publish_pool_stats",
    "publish_serve_stats",
    "snapshot_device",
    "stats_to_jsonable",
]
