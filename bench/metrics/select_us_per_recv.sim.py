"""Device time per recv of the scheduler's phase (``pool.select``:
selection, enqueue, completion), per chip."""

from bench.lib import phases

SCOPES = ("pool.select",)


def read(trace, counts):
    ph = phases.of(trace)
    if not counts.get("recvs") or ph is None or not ph.has_scopes():
        return None
    return 1e6 * ph.scope_s(*SCOPES) / len(trace.ops) / counts["recvs"]
