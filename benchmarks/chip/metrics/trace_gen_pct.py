"""Trace generation: the workload's graphs, app runs and trace emission
(``apps/``, ``core/driver.py``, ``stream/``), less the cache passes and
scoring nested in them, as a share of the window."""

INCLUDE = r"trace_gen|trace_emit|trace_epoch|update_apply"
EXCLUDE = r"cache_pass\[.*\]|score"


def read(layers):
    return layers.share(INCLUDE, EXCLUDE)
