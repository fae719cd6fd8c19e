"""Reduction of a JAX profiler trace to device busy time, kernel time and
collective time.

``load(path)`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
and returns a ``Trace``: the device operations of each chip and the host
annotations, all on the profiler's one clock.  Everything after loading
is plain arithmetic over ``Event`` tuples, so a test can build a trace by
hand and check the numbers exactly.

Definitions (one place, read by every per-layer metric):

  * busy time of a chip: the length of the union of the intervals of its
    device operations and program runs inside the window;
  * idle share: 1 - busy / window, averaged over the chips used;
  * kernel time: the summed durations of the operations whose own name
    (the HLO instruction's, which a Pallas kernel takes from its jitted
    wrapper) contains the kernel's name;
  * collective time: the summed durations of collective operations
    (all-gather, all-reduce, reduce-scatter, all-to-all,
    collective-permute), and its exposed part: the time in which a
    collective runs and no other operation does.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Iterable, NamedTuple

# the lines of a TPU plane that hold one event per executed HLO operation
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "allgather", "allreduce")
WINDOW = "bench_window"
# control-flow operations whose events span the operations they run
CONTAINERS = ("while", "conditional", "call")


class Event(NamedTuple):
    name: str               # the HLO instruction's own name ("fusion.12")
    start_ns: float
    dur_ns: float
    text: str = ""          # the whole instruction, for reading its kind

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Device operations per chip, XLA module runs per chip, host events,
    and the traced window (start and end, ns)."""

    ops: dict[str, list[Event]]
    modules: dict[str, list[Event]]
    host: list[Event]
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    # ---------------------------------------------------------------- #
    def clipped(self, events: Iterable[Event]) -> list[Event]:
        lo, hi = self.window
        out = []
        for e in events:
            s, t = max(e.start_ns, lo), min(e.end_ns, hi)
            if t > s:
                out.append(e._replace(start_ns=s, dur_ns=t - s))
        return out

    def busy_s(self) -> float:
        """Seconds in which an operation or a program ran, averaged over
        the chips."""
        if not self.ops:
            return 0.0
        return sum(union_ns(self.clipped(v + self.modules.get(chip, [])))
                   for chip, v in self.ops.items()) * 1e-9 / len(self.ops)

    def idle_pct(self) -> float | None:
        if not self.ops or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def kernel_s(self, *names: str) -> float:
        """Device seconds of the operations that name one of ``names``,
        summed over the chips (not averaged)."""
        return sum(e.dur_ns for v in self.ops.values()
                   for e in self.clipped(v) if matches(e, names)) * 1e-9

    def kernel_count(self, *names: str) -> int:
        return sum(1 for v in self.ops.values()
                   for e in self.clipped(v) if matches(e, names))

    def collective_s(self) -> float:
        return self.kernel_s(*COLLECTIVES)

    def exposed_collective_s(self) -> float:
        """Seconds, summed over the chips, in which a collective ran and
        no other operation did."""
        total = 0.0
        for v in self.ops.values():
            ev = self.clipped(v)
            coll = [e for e in ev if matches(e, COLLECTIVES)]
            other = [e for e in ev
                     if not matches(e, COLLECTIVES) and not container(e)]
            total += union_ns(coll) - overlap_ns(coll, other)
        return total * 1e-9

    def module_s(self, *names: str) -> float:
        """Device seconds of the XLA module runs that name one of
        ``names``, averaged over the chips."""
        if not self.modules:
            return 0.0
        return sum(e.dur_ns for v in self.modules.values()
                   for e in self.clipped(v) if matches(e, names)
                   ) * 1e-9 / len(self.modules)

    # ---------------------------------------------------------------- #
    def top_ops(self, k: int = 10) -> list[list]:
        """The ``k`` kinds of operation (instruction names without their
        number, loops left out) with the most device time, averaged over
        the chips: ``[[name, seconds], ...]``."""
        tot: dict[str, float] = {}
        for v in self.ops.values():
            for e in self.clipped(v):
                if container(e):
                    continue
                kind = e.name.rsplit(".", 1)[0]
                tot[kind] = tot.get(kind, 0.0) + e.dur_ns
        n = max(len(self.ops), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / n] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> list[list]:
        """The ``k`` longest gaps in which the first chip ran nothing,
        each named by the host event that overlaps it most:
        ``[[name, seconds], ...]``."""
        if not self.ops:
            return []
        chip = sorted(self.ops)[0]
        gaps = complement(self.clipped(self.ops[chip]
                                       + self.modules.get(chip, [])),
                          self.window)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        host = [e for e in self.host if e.name != WINDOW]
        out = []
        for lo, hi in gaps[:k]:
            best, best_ns = "no host event", 0.0
            for e in host:
                ov = min(hi, e.end_ns) - max(lo, e.start_ns)
                if ov > best_ns:
                    best, best_ns = e.name, ov
            out.append([best, (hi - lo) * 1e-9])
        return out


# --------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------- #
def merged(events: Iterable[Event]) -> list[tuple[float, float]]:
    iv = sorted((e.start_ns, e.end_ns) for e in events if e.dur_ns > 0)
    out: list[list[float]] = []
    for s, t in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def union_ns(events: Iterable[Event]) -> float:
    return sum(t - s for s, t in merged(events))


def overlap_ns(a: Iterable[Event], b: Iterable[Event]) -> float:
    """Length of (union of a) ∩ (union of b)."""
    ua, ub = merged(a), merged(b)
    i = j = 0
    total = 0.0
    while i < len(ua) and j < len(ub):
        lo = max(ua[i][0], ub[j][0])
        hi = min(ua[i][1], ub[j][1])
        if hi > lo:
            total += hi - lo
        if ua[i][1] < ub[j][1]:
            i += 1
        else:
            j += 1
    return total


def complement(events: Iterable[Event], window: tuple[float, float]
               ) -> list[tuple[float, float]]:
    lo, hi = window
    gaps, t = [], lo
    for s, e in merged(events):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def matches(e: Event, names: Iterable[str]) -> bool:
    return any(n in e.name for n in names)


def container(e: Event) -> bool:
    return e.name.split(".", 1)[0] in CONTAINERS


# --------------------------------------------------------------------- #
# loading
# --------------------------------------------------------------------- #
def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _op(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> name ``fusion.12``."""
    head = name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    """Read an ``.xplane.pb``: device operations and modules from the
    planes whose names start with ``device_prefix``, host events from
    the ``/host:`` planes.  The window is the ``bench_window`` host
    annotation; without one, the span of all device operations."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[str, list[Event]] = {}
    modules: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(device_prefix):
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops.setdefault(name, []).extend(
                        Event(_op(e.name), e.start_ns, e.duration_ns, e.name)
                        for e in line.events)
                elif line.name in MODULE_LINES:
                    modules.setdefault(name, []).extend(
                        Event(e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in line.events)
    wins = [e for e in host if e.name == WINDOW]
    if wins:
        w = max(wins, key=lambda e: e.dur_ns)
        window = (w.start_ns, w.end_ns)
    else:
        allev = [e for v in ops.values() for e in v]
        window = ((min(e.start_ns for e in allev),
                   max(e.end_ns for e in allev)) if allev else (0.0, 0.0))
    return Trace(ops=ops, modules=modules, host=host, window=window)
