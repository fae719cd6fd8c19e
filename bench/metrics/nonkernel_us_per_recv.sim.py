"""Device time per recv outside the ``env_step`` kernel and the
collectives, per chip: the engine's gather and scatter, the scheduler's
selection, observation and bookkeeping."""

KERNELS = ("env_multi_step",)


def read(trace, counts):
    if not counts.get("recvs") or not trace.ops:
        return None
    chips = len(trace.ops)
    other = (trace.busy_s() - (trace.kernel_s(*KERNELS)
                               + trace.collective_s()) / chips)
    return 1e6 * other / counts["recvs"]
