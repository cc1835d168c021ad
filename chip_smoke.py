#!/usr/bin/env python3
"""Run the simulator's main path once on one TPU chip and check what it says.

Usage (from the root of a checkout, on a machine with a TPU)::

    python chip_smoke.py

Everything runs in this one process, through the entry points a user calls
(``Experiment(...).run``), with the cache engine resolved by default:

- **Phase A, grid.** ``pgd`` and ``bfs`` on the Table VII-shaped
  ``amazon`` graph, AMC against VLDP, under the ``SCALED`` hierarchy.
- **Phase B, paper scale.** ``bfs`` on ``road-8m`` (2.1M vertices, about
  32.5M accesses) through the sharded scorer under the unscaled Table VI
  hierarchy (``PAPER``), AMC and next-line.
- **Identity.** The default engine's L1/L2/LLC hit masks equal the
  ``reference`` engine's on a 2^20-access prefix of phase A's demand trace
  (``SCALED``) and on the first shard of phase B (``PAPER``).
- **Engines.** Warm demand+score wall seconds of phase A's ``pgd`` workload
  under the ``pallas``, ``fused`` and ``set_parallel`` engines, with the
  engine-independent prefetch streams generated once beforehand.

Each phase prints one ``[chip_smoke]`` line with its wall seconds (the device
queue drained before every timestamp), accesses simulated, accesses/s, rows,
engine and peak device bytes.  These are not benchmark claims.  The last line
of stdout is ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time

IDENTITY_PREFIX = 1 << 20
SHARD_ACCESSES = 1 << 22
PHASE_B_DATASET = "road-8m"


def _clock() -> float:
    """Wall time after the device queue has drained.

    Work is issued in order on the chip, so blocking on a fresh trivial
    computation waits for everything issued before it.
    """
    import jax
    import jax.numpy as jnp

    jax.block_until_ready(jnp.zeros((), jnp.int32) + 1)
    return time.perf_counter()


def _peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _emit(phase: str, **fields) -> None:
    print(f"[chip_smoke] {phase} {json.dumps(fields, sort_keys=True)}", flush=True)


def _check_rows(rows: list, expected: int, phase: str) -> list:
    """Rows are the expected cells with finite, in-range metrics."""
    assert len(rows) == expected, f"{phase}: {len(rows)} rows, expected {expected}"
    out = []
    for r in rows:
        for key in ("speedup", "coverage", "accuracy"):
            assert math.isfinite(r[key]), f"{phase}: {key} not finite in {r}"
        assert r["speedup"] > 0, f"{phase}: speedup {r['speedup']}"
        assert 0.0 <= r["coverage"] <= 1.0 and 0.0 <= r["accuracy"] <= 1.0, r
        out.append(
            {
                "cell": f"{r['kernel']}/{r['dataset']}/{r['prefetcher']}",
                "speedup": r["speedup"],
                "coverage": r["coverage"],
                "accuracy": r["accuracy"],
            }
        )
    return out


def phase_a():
    """The AMC-vs-VLDP grid on ``amazon`` at the SCALED hierarchy."""
    from repro.core import Experiment
    from repro.memsim import current_engine
    from repro.memsim.config import SCALED

    t0 = _clock()
    result = Experiment(
        kernels=["pgd", "bfs"],
        datasets=["amazon"],
        prefetchers=["amc", "vldp"],
        hierarchy=SCALED,
    ).run(workers=1)
    seconds = _clock() - t0
    accesses = sum(w.num_accesses for w in result.workloads.values())
    _emit(
        "phase A",
        seconds=seconds,
        accesses=accesses,
        accesses_per_s=accesses / seconds,
        rows=_check_rows(result.rows(), 4, "phase A"),
        engine=current_engine(),
        peak_bytes_in_use=_peak_bytes(),
    )
    return result


def phase_b(artifacts_dir: str, dataset: str = PHASE_B_DATASET):
    """``bfs`` on ``dataset`` through the sharded scorer at PAPER."""
    from repro.core import ArtifactCache, Experiment, WorkloadCache, WorkloadSpec
    from repro.core.exec.sharded import ShardedSpec
    from repro.memsim import current_engine
    from repro.memsim.config import PAPER

    spec = ShardedSpec(
        base=WorkloadSpec("bfs", dataset, hierarchy=PAPER),
        shard_accesses=SHARD_ACCESSES,
    )
    artifacts = ArtifactCache(artifacts_dir)
    t0 = _clock()
    result = Experiment(
        workloads=[spec],
        prefetchers=["amc", "nextline2"],
        cache=WorkloadCache(artifacts=artifacts),
    ).run(workers=1)
    seconds = _clock() - t0
    manifest = artifacts.load_manifest(spec)
    accesses = int(manifest["num_accesses"])
    _emit(
        "phase B",
        dataset=dataset,
        shards=len(manifest["shard_sizes"]),
        seconds=seconds,
        accesses=accesses,
        accesses_per_s=accesses / seconds,
        rows=_check_rows(result.rows(), 2, "phase B"),
        engine=current_engine(),
        peak_bytes_in_use=_peak_bytes(),
    )
    return spec, artifacts


def identity(blocks, iter_id, cfg, label: str) -> None:
    """Default-engine demand masks equal the reference engine's."""
    import numpy as np

    from repro.memsim import current_engine, simulate_demand, use_engine

    t0 = _clock()
    got = simulate_demand(blocks, iter_id, cfg)
    t1 = _clock()
    with use_engine("reference"):
        ref = simulate_demand(blocks, iter_id, cfg)
    t2 = _clock()
    same = {
        level: bool(np.array_equal(getattr(got, level), getattr(ref, level)))
        for level in ("l1_hit", "l2_hit", "llc_hit")
    }
    _emit(
        "identity",
        geometry=label,
        accesses=len(blocks),
        engine=current_engine(),
        engine_seconds=t1 - t0,
        reference_seconds=t2 - t1,
        equal=same,
    )
    assert all(same.values()), f"identity failed at {label}: {same}"


def engine_seconds(workload, prefetchers) -> None:
    """Warm demand+score seconds of one workload under each engine.

    Prefetch streams do not depend on the cache engine, so they are
    generated once (timed on their own) and replayed to every engine.
    """
    from repro.core.experiment import score_prefetchers_batched
    from repro.memsim import simulate_demand, use_engine

    t0 = _clock()
    streams = [(name, gen(workload)) for name, gen in prefetchers]
    generate_seconds = _clock() - t0
    replay = [(name, lambda _w, s=stream: s) for name, stream in streams]
    cfg = workload.spec.hierarchy
    seconds = {}
    for engine in ("pallas", "fused", "set_parallel"):
        with use_engine(engine):
            for _ in range(2):  # the first pass compiles; the second is timed
                t0 = _clock()
                simulate_demand(workload.block, workload.iter_id, cfg)
                score_prefetchers_batched(workload, replay)
                seconds[engine] = _clock() - t0
    _emit(
        "engines",
        workload=f"{workload.kernel}/{workload.dataset}",
        accesses=workload.num_accesses,
        generate_seconds=generate_seconds,
        demand_score_seconds=seconds,
    )


def main() -> int:
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform {device.platform!r}); "
            "this script checks the chip path and runs only on a TPU",
            file=sys.stderr,
        )
        return 1
    root = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.core.exec.compile_cache import use_compile_cache
    from repro.memsim.config import PAPER, SCALED

    use_compile_cache()
    result = phase_a()
    pgd = result.workload("pgd", "amazon")
    identity(
        pgd.block[:IDENTITY_PREFIX], pgd.iter_id[:IDENTITY_PREFIX], SCALED, "SCALED"
    )
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        spec, artifacts = phase_b(tmp)
        shard = artifacts.load_shard(spec, 0)
        identity(shard["block"], shard["iter_id"], PAPER, "PAPER")
    from repro.core.registry import resolve_prefetchers

    engine_seconds(pgd, resolve_prefetchers(["amc", "vldp"]))
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": device.platform,
                    "kind": device.device_kind,
                    "count": len(jax.devices()),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
