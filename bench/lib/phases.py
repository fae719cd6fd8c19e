"""Device time by the program's own phases, read from the same
``.xplane.pb`` as ``bench/lib/trace.py``'s ``Trace``.

The program names its phases with ``jax.named_scope`` (``SCOPES``).  The
compiler keeps the scope path as each operation's ``op_name``, and the
profiler records it as the ``tf_op`` stat of the operation's event
metadata.  ``jax.profiler.ProfileData`` does not expose event metadata,
so ``op_paths`` reads it from the protobuf's wire format with the
standard library alone.

Definitions (read by the phase metrics of ``bench/metrics/``):

  * operation time: the summed durations, within the window, of the
    operations that are not loops or calls (whose events span the
    operations they run), summed over the chips;
  * an operation's phase: the last name of ``SCOPES`` on its ``tf_op``
    path, or none.  A fusion carries the path of its root instruction,
    so a fusion that spans two phases counts under its root's.
    Operations the compiler adds (copies between layouts) carry no path
    and count as on no phase;
  * a phase's time: the operation time, collectives left out, of the
    operations whose phase it is.

The phases, the operations on no phase and the collectives partition
the operation time.  ``of(trace)`` finds the ``.xplane.pb`` a ``Trace``
was loaded from: the newest under the harness's trace directory whose
operations are the trace's, event for event.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from pathlib import Path
from typing import Iterable, Iterator

from bench.lib import trace as tracelib

ROOT = Path(__file__).resolve().parents[2]
# where bench/run.py records the traces of --trace 1 runs
TRACE_DIR = ROOT / ".bench_cache" / "trace"
# the phases the program names with jax.named_scope (repro/obs/trace.py)
SCOPES = ("pool.send", "pool.select", "pool.gather", "pool.env_step",
          "pool.writeback", "pool.tick", "pool.telemetry", "pool.transform",
          "collect", "collect.policy", "ppo.collect", "ppo.gae",
          "ppo.update")


@functools.lru_cache(maxsize=4096)
def innermost(path: str) -> str:
    """The last name of ``SCOPES`` on an ``op_name`` path, or ``""``."""
    for part in reversed(path.split("/")):
        if part in SCOPES:
            return part
    return ""


@dataclasses.dataclass
class Phases:
    """A ``Trace`` and the ``tf_op`` path of each of its operations:
    ``paths[chip][i]`` belongs to ``trace.ops[chip][i]``."""

    trace: tracelib.Trace
    paths: dict[str, list[str]]
    # (window, {(phase, op kind): ns}) of the last ``phase_ns`` call
    _phases: tuple | None = dataclasses.field(default=None, repr=False,
                                              compare=False)

    def _clipped(self) -> Iterator[tuple[tracelib.Event, str, float]]:
        """Each operation that is not a loop or call, its path and its
        nanoseconds within the window."""
        lo, hi = self.trace.window
        for chip, events in self.trace.ops.items():
            for e, path in zip(events, self.paths[chip]):
                ns = min(e.end_ns, hi) - max(e.start_ns, lo)
                if ns > 0 and not tracelib.container(e):
                    yield e, path, ns

    def op_s(self) -> float:
        """Operation time, seconds summed over the chips."""
        return sum(ns for _, _, ns in self._clipped()) * 1e-9

    def phase_ns(self) -> dict[tuple[str, str], float]:
        """Nanoseconds, summed over the chips, of the operations that
        are not loops, calls or collectives, by phase (``""`` for none)
        and kind of operation (its name without its number)."""
        if self._phases is None or self._phases[0] != self.trace.window:
            tot: dict[tuple[str, str], float] = {}
            for e, path, ns in self._clipped():
                if tracelib.matches(e, tracelib.COLLECTIVES):
                    continue
                key = (innermost(path), e.name.rsplit(".", 1)[0])
                tot[key] = tot.get(key, 0.0) + ns
            self._phases = (self.trace.window, tot)
        return self._phases[1]

    def has_scopes(self) -> bool:
        """True when some operation runs inside a phase of ``SCOPES``:
        a program that names no phase gives its phase metrics no value."""
        return any(p for p, _ in self.phase_ns())

    def scope_s(self, *names: str, without: Iterable[str] = ()) -> float:
        """Seconds, summed over the chips, of the phases ``names``
        (``""`` names the operations on no phase), leaving out the
        operations whose name contains one of ``without``."""
        without = tuple(without)
        return sum(ns for (p, kind), ns in self.phase_ns().items()
                   if p in names and not any(w in kind for w in without)
                   ) * 1e-9


def attach(trace: tracelib.Trace, paths: dict[str, list[str]]) -> Phases:
    """Give ``trace`` its operations' paths, kept on the trace itself
    (``Trace`` has no field for them); ``of(trace)`` returns them."""
    if set(paths) != set(trace.ops) or any(
            len(paths[c]) != len(v) for c, v in trace.ops.items()):
        raise ValueError("the paths do not match the trace's operations")
    trace.phases = Phases(trace, paths)
    return trace.phases


def from_file(trace: tracelib.Trace, xplane: str,
              device_prefix: str = "/device:TPU:") -> Phases | None:
    """The paths of ``trace``'s operations, read from ``xplane``; None
    unless the file holds the same operations in the same order."""
    with open(xplane, "rb") as f:
        rows = op_paths(f.read(), device_prefix)
    if set(rows) != set(trace.ops):
        return None
    for chip, events in trace.ops.items():
        if [e.text for e in events] != [text for text, _ in rows[chip]]:
            return None
    return attach(trace, {c: [p for _, p in r] for c, r in rows.items()})


def of(trace: tracelib.Trace) -> Phases | None:
    """The ``Phases`` of ``trace``: those attached to it, else
    those of the newest ``.xplane.pb`` under ``TRACE_DIR`` that holds
    its operations; None where no file does."""
    if isinstance(getattr(trace, "phases", None), Phases):
        return trace.phases
    files = sorted(TRACE_DIR.glob("**/*.xplane.pb"), key=os.path.getmtime,
                   reverse=True)
    for path in files:
        try:
            found = from_file(trace, str(path))
        except (OSError, ValueError, IndexError):
            continue
        if found is not None:
            return found
    return None


def load(path: str) -> tracelib.Trace:
    """``tracelib.load`` with the operations' paths attached."""
    trace = tracelib.load(path)
    if from_file(trace, path) is None:
        raise ValueError(f"{path}: the metadata paths do not match "
                         f"ProfileData's operations")
    return trace


# --------------------------------------------------------------------- #
# the XSpace protobuf, read from its wire format (tensorflow's
# xplane.proto): only the fields that give each operation its tf_op path
# --------------------------------------------------------------------- #
def _varint(buf: bytes, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[tuple[int, object]]:
    """``(field number, value)`` of each field of the message in
    ``buf[i:end]``: an int, or for a length-delimited field the
    ``(start, end)`` of its bytes."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map(buf: bytes, span) -> tuple[int, tuple[int, int]]:
    """A protobuf map entry's ``(key, span of the value)``."""
    key, val = 0, (0, 0)
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _tf_op(buf: bytes, span, stat_id: int | None,
           stat_names: dict[int, str]) -> tuple[str, str]:
    """An XEventMetadata's ``(name, path)``: the path is its ``tf_op``
    stat without the trailing ``:<type>``, or ``""``."""
    name, path = "", ""
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 5 and stat_id is not None:
            mid, value = 0, None
            for g, w in _fields(buf, *v):
                if g == 1:
                    mid = w
                elif g == 5:
                    value = _text(buf, w)
                elif g == 7:
                    value = stat_names.get(w, "")
            if mid == stat_id and value is not None:
                path = value.rpartition(":")[0] if ":" in value else value
    return name, path


def op_paths(data: bytes, device_prefix: str = "/device:TPU:"
             ) -> dict[str, list[tuple[str, str]]]:
    """For each plane whose name starts with ``device_prefix``: the
    ``(metadata name, tf_op path)`` of every event of its lines in
    ``OP_LINES``, in file order, which is the order of ``Trace.ops``.
    Reads XSpace.planes; XPlane.name, lines, event_metadata,
    stat_metadata; XLine.name, events; XEvent.metadata_id;
    XEventMetadata.name, stats; XStatMetadata.name; XStat.metadata_id,
    str_value, ref_value."""
    out: dict[str, list[tuple[str, str]]] = {}
    for f, plane in _fields(data, 0, len(data)):
        if f != 1:
            continue
        name, lines, ev_md, st_md = "", [], [], {}
        for g, v in _fields(data, *plane):
            if g == 2:
                name = _text(data, v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                ev_md.append(v)
            elif g == 5:
                sid, val = _map(data, v)
                for h, w in _fields(data, *val):
                    if h == 2:
                        st_md[sid] = _text(data, w)
        if not name.startswith(device_prefix):
            continue
        stat_id = next((k for k, n in st_md.items() if n == "tf_op"), None)
        md = {}
        for span in ev_md:
            mid, val = _map(data, span)
            md[mid] = _tf_op(data, val, stat_id, st_md)
        for span in lines:
            lname, events = "", []
            for g, v in _fields(data, *span):
                if g == 2:
                    lname = _text(data, v)
                elif g == 4:
                    events.append(v)
            if lname not in tracelib.OP_LINES:
                continue
            rows = out.setdefault(name, [])
            for lo, hi in events:
                if data[lo] == 0x08:     # metadata_id, written first
                    mid = _varint(data, lo + 1)[0]
                else:
                    mid = next((w for h, w in _fields(data, lo, hi)
                                if h == 1), 0)
                rows.append(md.get(mid, ("", "")))
    return out
