"""Device time per update of the PPO epochs (``ppo.update``) in the
training cells, per chip, in milliseconds."""

from bench.lib import phases

SCOPES = ("ppo.update",)


def read(trace, counts):
    ph = phases.of(trace)
    if not counts.get("updates") or ph is None or not ph.has_scopes():
        return None
    return 1e3 * ph.scope_s(*SCOPES) / len(trace.ops) / counts["updates"]
