"""Share of the traced window in which the chips ran no operation, in
the simulation cells: 1 - busy / window, busy being the union of the
device operations' intervals, averaged over the chips."""


def read(trace, counts):
    return trace.idle_pct()
