"""Device time per recv of the pool's in-graph counters
(``pool.telemetry``), per chip."""

from bench.lib import phases

SCOPES = ("pool.telemetry",)


def read(trace, counts):
    ph = phases.of(trace)
    if not counts.get("recvs") or ph is None or not ph.has_scopes():
        return None
    return 1e6 * ph.scope_s(*SCOPES) / len(trace.ops) / counts["recvs"]
