"""Device time per recv of the batched env around the ``env_step``
kernel (``pool.env_step`` less the ``env_multi_step`` operations: the
masks, casts and observe around the kernel), per chip."""

from bench.lib import phases

SCOPES = ("pool.env_step",)
KERNELS = ("env_multi_step",)


def read(trace, counts):
    ph = phases.of(trace)
    if not counts.get("recvs") or ph is None or not ph.has_scopes():
        return None
    t = ph.scope_s(*SCOPES, without=KERNELS)
    return 1e6 * t / len(trace.ops) / counts["recvs"]
