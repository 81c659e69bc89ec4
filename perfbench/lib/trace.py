"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, per-program device time, and the
idle gaps of the device attributed to what the host was doing.

Only ``jax.profiler.ProfileData`` is needed to read the file.  On a TPU the
trace has one plane per chip (``/device:TPU:<i>``) whose line ``XLA Ops``
holds one event per operation that ran and whose line ``XLA Modules`` holds
one event per program execution; host threads are lines of ``/host:CPU`` and
carry the benchmark's own spans (``jax.profiler.TraceAnnotation``) on the
same clock.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]           # (name, start_s, duration_s)

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclass
class Trace:
    # per device plane, in plane order
    ops: List[List[Event]] = field(default_factory=list)
    modules: List[List[Event]] = field(default_factory=list)
    # host spans of the names asked for, any thread
    spans: List[Event] = field(default_factory=list)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, span_names: Iterable[str]) -> Trace:
    from jax.profiler import ProfileData

    want = set(span_names)
    trace = Trace()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE) and \
                plane.name[len(DEVICE_PLANE):].isdigit():
            lines = {ln.name: ln for ln in plane.lines}
            for name, dest in ((OPS_LINE, trace.ops),
                               (MODULES_LINE, trace.modules)):
                ln = lines.get(name)
                dest.append([] if ln is None else sorted(
                    ((e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                     for e in ln.events), key=lambda ev: ev[1]))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name in want:
                        trace.spans.append(
                            (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    trace.spans.sort(key=lambda ev: ev[1])
    return trace


def union_intervals(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals of the events, ascending."""
    out: List[List[float]] = []
    for _name, start, dur in sorted(events, key=lambda ev: ev[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    """The events cut to the window [t0, t1]."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_seconds(events: Sequence[Event]) -> float:
    return sum(b - a for a, b in union_intervals(events))


def program_time(modules: Sequence[Event], needle: str) -> Tuple[float, int]:
    """(summed device seconds, executions) of the programs whose name
    contains ``needle``."""
    hit = [dur for name, _s, dur in modules if needle in name]
    return sum(hit), len(hit)


def top_ops(events: Sequence[Event], n: int = 10, width: int = 100
            ) -> List[Tuple[str, float]]:
    """Device seconds by operation, names cut to ``width`` characters (an
    operation's name is its whole HLO line)."""
    total: Dict[str, float] = {}
    for name, _s, dur in events:
        total[name[:width]] = total.get(name[:width], 0.0) + dur
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def idle_gaps(events: Sequence[Event], spans: Sequence[Event], t0: float,
              t1: float, outside: str = "outside_spans", n: int = 10
              ) -> List[Tuple[str, float]]:
    """The device's idle seconds inside [t0, t1], summed by what the host was
    doing meanwhile: every moment of a gap between device operations goes to
    the INNERMOST (latest started) host span open at that moment, or to
    ``outside`` where none is."""
    busy = union_intervals(clip(events, t0, t1))
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    spans = sorted(spans, key=lambda ev: ev[1])
    starts = [s for _n, s, _d in spans]
    cuts = sorted({c for _n, s, d in spans for c in (s, s + d)})
    total: Dict[str, float] = {}
    for a, b in gaps:
        lo, hi = bisect.bisect_right(cuts, a), bisect.bisect_left(cuts, b)
        edges = [a] + cuts[lo:hi] + [b]
        for x, y in zip(edges, edges[1:]):
            mid, owner = 0.5 * (x + y), outside
            for name, s, d in reversed(spans[:bisect.bisect_right(starts, mid)]):
                if s + d > mid:
                    owner = name
                    break
            total[owner] = total.get(owner, 0.0) + (y - x)
    return sorted(total.items(), key=lambda kv: -kv[1])[:n]
