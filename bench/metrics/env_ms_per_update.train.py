"""Device time per update of the pool in the training cells: the
engine's, scheduler's, telemetry's and transform pipeline's phases
(``pool.*``), per chip, in milliseconds."""

from bench.lib import phases

SCOPES = tuple(s for s in phases.SCOPES if s.startswith("pool."))


def read(trace, counts):
    ph = phases.of(trace)
    if not counts.get("updates") or ph is None or not ph.has_scopes():
        return None
    return 1e3 * ph.scope_s(*SCOPES) / len(trace.ops) / counts["updates"]
