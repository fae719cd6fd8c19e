"""Share of the device's operation time on no phase the program names
(``bench/lib/phases.py``'s ``SCOPES``): how much of the device time the
phase metrics leave unattributed."""

from bench.lib import phases


def read(trace, counts):
    ph = phases.of(trace)
    if ph is None or not ph.has_scopes():
        return None
    total = ph.op_s()
    if total <= 0:
        return None
    return 100.0 * ph.scope_s("") / total
