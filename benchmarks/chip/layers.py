"""What a per-layer metric reader is given, and interval arithmetic for it.

A reader in ``metrics/<name>.py`` defines ``read(layers) -> float | None``.
It returns ``None`` where it finds nothing to read; the harness then leaves
the metric out of the result line.
"""
from __future__ import annotations

import dataclasses
import re
from typing import List, Optional, Tuple

from xplane import DeviceTrace, length, union

Interval = Tuple[float, float]


@dataclasses.dataclass
class Layers:
    """One traced window.

    ``spans`` are the program's stage spans as ``(name, start_s, end_s)``
    in seconds from the window's start, on the host's clock; ``device`` is
    the profiler trace of the same window, or ``None`` without one.
    """

    window_s: float
    spans: List[Tuple[str, float, float]]
    device: Optional[DeviceTrace] = None

    def intervals(self, pattern: str) -> List[Interval]:
        """Union of the spans whose whole name matches ``pattern``."""
        rx = re.compile(pattern)
        return union(
            [(s, e) for name, s, e in self.spans if rx.fullmatch(name)],
            (0.0, self.window_s),
        )

    def share(self, include: str, exclude: Optional[str] = None) -> Optional[float]:
        """Percent of the window covered by ``include`` spans and not by
        ``exclude`` spans; ``None`` where no ``include`` span is open."""
        inc = self.intervals(include)
        if not inc:
            return None
        exc = self.intervals(exclude) if exclude else []
        return 100.0 * length(subtract(inc, exc)) / self.window_s


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """``a`` minus ``b``; both sorted and disjoint."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out
