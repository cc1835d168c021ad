"""The trace reduction, on a one-second traced window of the
``amc-sweep`` cell recorded on a TPU v5e (two jobs, one chip)."""
from pathlib import Path

import pytest

import run_cell
import xplane
from layers import Layers

TRACE = Path(run_cell.__file__).resolve().parent / "testdata" / "amc-sweep-1s.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return xplane.reduce(str(TRACE))


def test_the_window_and_the_device_are_found(trace):
    assert list(trace.ops) == ["/device:TPU:0"]
    assert trace.window_s == pytest.approx(1.438194761, abs=1e-9)
    assert len(trace.ops["/device:TPU:0"]) == 16


def test_busy_time_is_the_union_of_op_intervals(trace):
    busy = trace.busy_s()
    assert busy == pytest.approx(0.002240336, abs=1e-12)
    gaps = trace.idle_gaps()
    assert sum(e - s for s, e in gaps) / 1e9 == pytest.approx(trace.window_s - busy)
    assert all(e > s for s, e in gaps)


def test_kernel_time_is_read_by_name(trace):
    cell = run_cell.load_cell(run_cell.CHECKOUT, "bfs-amazon-scaled.amc-sweep")
    layers = Layers(window_s=trace.window_s, spans=[], device=trace)
    kernel = cell.reader("cache_kernel_pct")(layers)
    assert kernel == pytest.approx(100 * 0.002186336 / trace.window_s)
    idle = cell.reader("device_idle_pct")(layers)
    assert idle == pytest.approx(100 * (1 - 0.002240336 / trace.window_s))
    ops = trace.op_seconds()
    assert max(ops, key=ops.get) == "%lru_hits_carry.1"


def test_a_trace_without_the_window_is_refused(tmp_path):
    with pytest.raises(RuntimeError):
        xplane.find(str(tmp_path))
