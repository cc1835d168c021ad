"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

The window is the host annotation :data:`WINDOW` that the harness opens
around the measured jobs.  Device operations are the events on the
``XLA Ops`` line of each ``/device:`` plane; busy time is the union of
their intervals inside the window, averaged over the devices.  All times
are nanoseconds on the trace's own clock unless a name says otherwise.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OP_LINE = "XLA Ops"

Interval = Tuple[float, float]


@dataclasses.dataclass
class DeviceTrace:
    """What the reduction keeps: the window and, per device, its ops."""

    window: Interval  # (start_ns, end_ns) of the harness's window
    ops: Dict[str, List[Tuple[str, float, float]]]  # plane -> (name, start, end)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, plane: str) -> List[Interval]:
        return union([(s, e) for _, s, e in self.ops[plane]], self.window)

    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return None
        total = sum(length(self.busy_intervals(p)) for p in self.ops)
        return total / len(self.ops) / 1e9

    def op_seconds(self, pattern: Optional[re.Pattern] = None) -> Dict[str, float]:
        """Device seconds per op inside the window, averaged over the
        devices and keyed by the op's short name (its HLO text up to
        `` = ``); ``pattern`` keeps the ops whose full text it matches."""
        out: Dict[str, float] = {}
        lo, hi = self.window
        for events in self.ops.values():
            for name, s, e in events:
                if pattern is not None and not pattern.search(name):
                    continue
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    short = name.split(" = ", 1)[0]
                    out[short] = out.get(short, 0.0) + d / 1e9 / len(self.ops)
        return out

    def idle_gaps(self) -> List[Interval]:
        """Intervals of the window in which the first device ran nothing."""
        if not self.ops:
            return []
        plane = sorted(self.ops)[0]
        gaps, cursor = [], self.window[0]
        for s, e in self.busy_intervals(plane):
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < self.window[1]:
            gaps.append((cursor, self.window[1]))
        return gaps


def union(intervals: List[Interval], clip: Optional[Interval] = None) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``, clipped to ``clip``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if clip is not None:
            s, e = max(s, clip[0]), min(e, clip[1])
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def find(log_dir: str) -> str:
    """The one ``.xplane.pb`` file a profiler session wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def reduce(path: str) -> DeviceTrace:
    """Read ``path`` and keep the window and the device ops."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window = None
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            events = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    events += [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None:
        raise RuntimeError(f"no {WINDOW!r} annotation in {path}")
    return DeviceTrace(window=window, ops=ops)
