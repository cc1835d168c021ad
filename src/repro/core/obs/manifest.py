"""Run manifest: the provenance block attached to every trace.

Answers "what code, what configuration, what machine produced this
run?" — the questions BENCH archaeology has had to reconstruct from
commit timestamps so far.  Captured once per run and attached to
``ExperimentResult.telemetry`` and BENCH schema v8 documents.

Everything repo-specific is imported lazily inside :func:`run_manifest`:
this module is imported by ``repro.core.obs`` which is imported by the
stage-timer shim, so an eager import of the driver here would cycle.
"""

from __future__ import annotations

import os
import platform
import subprocess
import sys
from typing import Optional

_GIT_SHA: Optional[str] = None
_GIT_PROBED = False


def git_sha() -> Optional[str]:
    """HEAD sha of the repo containing this file (cached; None outside
    a git checkout or without a git binary)."""
    global _GIT_SHA, _GIT_PROBED
    if _GIT_PROBED:
        return _GIT_SHA
    _GIT_PROBED = True
    try:
        _GIT_SHA = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True,
            text=True,
            timeout=10,
        ).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        _GIT_SHA = None
    return _GIT_SHA


def run_manifest(sched: Optional[dict] = None, **extra) -> dict:
    """Provenance snapshot: git sha, resolved engine/emitter, schema
    versions, interpreter/platform, the JAX device the run drove, and
    (when the caller has one) the scheduler's ``SchedDecision`` record
    plus free-form extras."""
    import jax

    from repro.apps.trace import current_emitter
    from repro.core.driver import TRACE_CODE_VERSION
    from repro.core.exec.artifacts import ARTIFACT_SCHEMA
    from repro.core.obs.spans import TRACE_SCHEMA
    from repro.memsim.engine import current_engine

    doc = {
        "git_sha": git_sha(),
        "engine": current_engine(),
        "emitter": current_emitter(),
        "trace_code_version": TRACE_CODE_VERSION,
        "artifact_schema": ARTIFACT_SCHEMA,
        "trace_schema": TRACE_SCHEMA,
        "python": sys.version.split()[0],
        "platform": platform.platform(),
        "device": {
            "platform": jax.devices()[0].platform,
            "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        },
        "pid": os.getpid(),
    }
    if sched is not None:
        doc["sched"] = sched
    doc.update(extra)
    return doc


__all__ = ["git_sha", "run_manifest"]
