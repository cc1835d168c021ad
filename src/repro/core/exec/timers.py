"""Shared wall-clock timing for the execution engine and benchmarks.

All timing in this repo goes through ``time.perf_counter`` — it is
monotonic and has the highest available resolution, whereas ``time.time()``
has coarse granularity on some platforms and jumps under clock adjustment,
which makes microsecond-scale measurements meaningless.

Two layers:

- :func:`time_s` / :func:`time_us` time one callable (used by the
  ``benchmarks/run.py`` micro-benches and ``benchmarks/bench.py``).
- Pipeline stage instrumentation: the workload driver and the experiment
  scorer wrap their phases in ``with stage("trace_gen"): ...``; a caller
  wanting the breakdown activates collection with ``with collect_stages()
  as times: ...``.  With no collector active ``stage`` is a no-op, so the
  hot path pays nothing.  :func:`record` feeds the same collector with
  durations (or counts) measured out-of-band — overlap windows and
  scheduler decisions, which have no single ``with`` block to wrap.

``stage``/``collect_stages``/``record`` are now thin re-exports of
:mod:`repro.core.obs.spans`: the same stage names double as structured
spans when a tracer is active, with the flat stage-dict semantics —
including the no-op fast path — unchanged.  See docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import time
from typing import Callable

from repro.core.obs.spans import collect_stages, record, stage

__all__ = ["collect_stages", "record", "stage", "time_s", "time_us"]


def time_s(fn: Callable[[], object], repeats: int = 1, warmup: int = 0) -> float:
    """Mean wall-clock seconds per call of ``fn`` over ``repeats`` calls."""
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def time_us(fn: Callable[[], object], repeats: int = 3) -> float:
    """Mean microseconds per call, after one warmup (compile) call."""
    return time_s(fn, repeats=repeats, warmup=1) * 1e6
