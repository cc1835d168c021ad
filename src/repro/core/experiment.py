"""Declarative experiment API: one call evaluates a (kernel x dataset x
prefetcher) grid.

This is the unified front door over the paper's evaluation methodology
(§VI-§VII): declare *what* to evaluate —

    result = Experiment(
        kernels=["pgd", "bfs"],
        datasets=["comdblp", "amazon"],
        prefetchers=["amc", "vldp", "rnr"],
    ).run()
    result.metrics(kernel="pgd", dataset="comdblp", prefetcher="amc").speedup

— and the builder owns the *how*: workload construction through
:class:`~repro.core.driver.WorkloadSpec` (Algorithm-1 session wiring
included), a :class:`WorkloadCache` so each trace is built once and reused
across every prefetcher (and across experiments sharing the cache), registry
resolution of prefetcher names, and composite (next-line + X) scoring of
every grid cell.  The structured :class:`ExperimentResult` returns tidy
per-cell rows ready for JSON dumps or figure assembly.

Scoring one stream is :func:`score_prefetcher` — the single code path for
every caller (grid cells, stream epochs, ad-hoc scoring), so results are
comparable everywhere.  Kernel names — including direction variants like
``bfs_do`` and ``pgd_pull`` — resolve through the declarative kernel
registry (:mod:`repro.apps.registry`); dataset and prefetcher names through
theirs.
"""
from __future__ import annotations

import dataclasses
import json
import time
from collections.abc import Mapping, Sequence as _SequenceABC
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.driver import WorkloadSpec, WorkloadTrace, make_session
from repro.core.exec.artifacts import ArtifactCache
from repro.core.exec.timers import record, stage
from repro.core.obs import spans as obs
from repro.core.registry import Prefetcher, resolve_prefetchers
from repro.memsim import (
    SCALED,
    HierarchyConfig,
    PrefetchMetrics,
    current_engine,
    evaluate,
    simulate_with_prefetch,
    simulate_with_prefetch_batch,
)


def score_prefetcher(
    workload: WorkloadTrace, name: str, generate: Prefetcher
) -> PrefetchMetrics:
    """Score one prefetcher in the composite (next-line + X) configuration."""
    with obs.span(
        "score_cell",
        prefetcher=name,
        kernel=workload.spec.kernel,
        dataset=workload.spec.dataset,
    ), stage("score"):
        with obs.span(f"score.generate[{name}]"):
            stream = generate(workload)
        blocks, pos, issuer = _composite_stream(workload, stream)
        outcome = simulate_with_prefetch(
            workload.profile,
            blocks,
            pos,
            pf_issuer=issuer,
            metadata_bytes=stream.metadata_bytes,
        )
        with obs.span("score.evaluate"):
            m = evaluate(
                name,
                workload.profile,
                outcome,
                baseline_outcome=workload.nl_outcome,
                eval_from_pos=workload.eval_from_pos,
                issuer=1,
            )
        m.info = stream.info  # attach prefetcher-side stats
    return m


def _composite_stream(workload: WorkloadTrace, stream):
    """The next-line stream (issuer 0) followed by ``stream`` (issuer 1):
    blocks, positions and issuer ids of the composite configuration."""
    with obs.span("prefetch.merge"):
        blocks = np.concatenate([workload.nl_blocks, stream.blocks])
        pos = np.concatenate([workload.nl_pos, stream.pos])
        issuer = np.concatenate(
            [
                np.zeros(len(workload.nl_blocks), np.int8),
                np.ones(len(stream.blocks), np.int8),
            ]
        )
    return blocks, pos, issuer


def score_prefetchers_batched(
    workload: WorkloadTrace, pairs: Sequence[Tuple[str, Prefetcher]]
) -> List[PrefetchMetrics]:
    """Score a family of prefetchers against one workload in one dispatch.

    Under the ``fused`` engine every prefetcher's merged L2 stream joins a
    single vmapped L2→LLC scan (:func:`simulate_with_prefetch_batch`), so
    the per-prefetcher ``score_cache_pass`` launches collapse into one
    batched launch; other engines — and single-member families — fall back
    to looping :func:`score_prefetcher`.  Metrics are bit-identical to the
    loop either way (test-asserted), so callers may mix paths freely.
    """
    if len(pairs) <= 1 or current_engine() != "fused":
        return [score_prefetcher(workload, n, g) for n, g in pairs]
    with obs.span(
        "score_batch",
        prefetchers=",".join(n for n, _ in pairs),
        kernel=workload.spec.kernel,
        dataset=workload.spec.dataset,
    ), stage("score"):
        items, metas, infos = [], [], []
        for name, gen in pairs:
            # Per-cell child span over the prefetcher-specific compute
            # (stream generation — table training etc.); the joint
            # simulate/evaluate time stays on the parent batch span.
            with obs.span(
                "score_cell",
                prefetcher=name,
                kernel=workload.spec.kernel,
                dataset=workload.spec.dataset,
                batched=True,
            ), obs.span(f"score.generate[{name}]"):
                stream = gen(workload)
            items.append(_composite_stream(workload, stream))
            metas.append(stream.metadata_bytes)
            infos.append(stream.info)
        outcomes = simulate_with_prefetch_batch(workload.profile, items, metas)
        out = []
        for (name, _), outcome, info in zip(pairs, outcomes, infos):
            with obs.span("score.evaluate"):
                m = evaluate(
                    name,
                    workload.profile,
                    outcome,
                    baseline_outcome=workload.nl_outcome,
                    eval_from_pos=workload.eval_from_pos,
                    issuer=1,
                )
            m.info = info
            out.append(m)
    return out


def _retarget_trace(trace: WorkloadTrace, spec) -> WorkloadTrace:
    """A content-identical trace re-bound to ``spec``.

    Arrays are shared (they are bit-identical by construction of the
    content key); the spec and its derived AMC session are fresh, exactly
    as :func:`repro.core.exec.artifacts._unpack` rebinds a loaded
    artifact — so scoring a reused trace equals scoring a re-emission.
    """
    return dataclasses.replace(
        trace, spec=spec, session=make_session(spec, trace.cfg_trace)
    )


class WorkloadCache:
    """Build-once cache of :class:`WorkloadTrace` keyed by ``WorkloadSpec``.

    Each workload in an :class:`Experiment` is built once and scored by
    every prefetcher; pass the same cache instance to several experiments
    to reuse builds across them too.

    ``artifacts`` optionally backs the in-memory store with the on-disk
    :class:`~repro.core.exec.artifacts.ArtifactCache`: misses consult the
    artifact store before building, and fresh builds are persisted there —
    so repeat sweeps and parallel runs skip rebuilds across processes.

    Content-keyed specs (those exposing ``content_key()``, e.g. stream
    epoch specs) additionally deduplicate *within* the in-memory store:
    two distinct specs whose traces are determined by identical content —
    epochs a churn model left unchanged, the same epoch reached through
    different stream parameters — share one build, retargeted per spec
    (``reuses`` counts these alias hits).
    """

    def __init__(self, artifacts: Optional[ArtifactCache] = None):
        self._store: Dict[WorkloadSpec, WorkloadTrace] = {}
        self._by_content: Dict[str, WorkloadTrace] = {}
        self.artifacts = artifacts
        self.builds = 0
        self.hits = 0
        self.loads = 0  # artifact-cache (disk) hits
        self.reuses = 0  # in-memory content-alias hits (distinct specs)

    def get_or_build(self, spec: WorkloadSpec) -> WorkloadTrace:
        if spec in self._store:
            self.hits += 1
            obs.inc("workload_cache.hits")
            return self._store[spec]
        content = getattr(spec, "content_key", None)
        ck = (
            json.dumps(content(), sort_keys=True) if callable(content) else None
        )
        with obs.span(
            "get_or_build", kernel=spec.kernel, dataset=spec.dataset
        ) as sp:
            trace = (
                self.artifacts.load(spec) if self.artifacts is not None else None
            )
            if trace is not None:
                self.loads += 1
                obs.inc("workload_cache.loads")
                if sp:
                    sp.attrs["cache"] = "load"
            elif ck is not None and ck in self._by_content:
                trace = _retarget_trace(self._by_content[ck], spec)
                self.reuses += 1
                obs.inc("workload_cache.reuses")
                if sp:
                    sp.attrs["cache"] = "reuse"
            if trace is None:
                self.builds += 1
                obs.inc("workload_cache.builds")
                if sp:
                    sp.attrs["cache"] = "build"
                t0 = time.perf_counter()
                trace = spec.build()
                if self.artifacts is not None:
                    self.artifacts.save(spec, trace)
                    self.artifacts.record_cost(
                        spec, build_s=time.perf_counter() - t0
                    )
            if ck is not None:
                self._by_content.setdefault(ck, trace)
            self._store[spec] = trace
            return trace

    def evict(self, spec: WorkloadSpec) -> None:
        """Drop the in-memory entry (the artifact, if any, stays on disk).

        Lets long sweeps bound peak memory at one trace: process a
        workload, write its results, evict, move on.
        """
        self._store.pop(spec, None)

    def __len__(self) -> int:
        return len(self._store)


class _LazyWorkloads(Mapping):
    """``ExperimentResult.workloads`` view that materializes traces on
    first access (artifact-cache load, else rebuild).

    After a parallel run the built traces live in the artifact store, not
    in the parent process; loading all of them eagerly would charge every
    grid run for workloads the caller never reads.  Keys are present up
    front (iteration, ``len``, membership are free); values materialize
    through the experiment's workload cache on demand — including via
    ``dict(...)``/``.items()``, which go through ``__getitem__``.
    """

    def __init__(self, loader, specs):
        self._specs = list(specs)
        self._keys = set(self._specs)
        self._loader = loader

    def __getitem__(self, spec):
        if spec not in self._keys:
            raise KeyError(spec)
        return self._loader(spec)

    def __contains__(self, spec):  # the Mapping mixin would materialize
        return spec in self._keys

    def __iter__(self):
        return iter(self._specs)

    def __len__(self):
        return len(self._specs)


class _PipelinedTraces(_SequenceABC):
    """Sequence view over a stream's epoch traces that blocks on each
    epoch's *background build* on first access, then loads it through the
    workload cache — the handoff between the spawn pool and the in-parent
    lifecycle scorer.  Indexing epoch 0 does not wait for epochs 1..E, so
    scoring overlaps the remaining builds."""

    def __init__(self, pipeline, specs, cache: WorkloadCache):
        self._pipeline = pipeline
        self._specs = list(specs)
        self._cache = cache

    def __len__(self) -> int:
        return len(self._specs)

    def __getitem__(self, i: int) -> WorkloadTrace:
        spec = self._specs[i]  # IndexError here ends Sequence iteration
        self._pipeline.wait(spec)
        return self._cache.get_or_build(spec)


@dataclasses.dataclass(frozen=True)
class CellResult:
    """One grid cell: a prefetcher scored on one workload.

    Stream cells (from a :class:`repro.stream.protocol.StreamSpec`
    workload) additionally carry the epoch index and, for lifecycle-aware
    prefetchers, the table-lifecycle policy; serving cells (from a
    :class:`repro.serve.protocol.ServeSpec`) carry the tenant index and,
    for AMC-family prefetchers, the table mode.  All stay ``None`` for
    plain workload cells so the legacy row schema is unchanged.
    """

    kernel: str
    dataset: str
    prefetcher: str
    seed: int
    metrics: PrefetchMetrics
    spec: Optional[WorkloadSpec] = None  # full workload identity
    epoch: Optional[int] = None  # stream cells only
    lifecycle: Optional[str] = None  # stream cells with carried tables
    tenant: Optional[int] = None  # serving cells only
    table_mode: Optional[str] = None  # serving cells, AMC family


@dataclasses.dataclass
class ExperimentResult:
    """Structured result over the full evaluation grid.

    ``workloads`` is keyed by the full :class:`WorkloadSpec` (specs
    differing only in hierarchy or element sizes stay distinct); filter
    cells by ``spec=`` when kernel/dataset/seed alone are ambiguous.
    """

    cells: List[CellResult]
    # A plain dict after a serial run; a lazy Mapping after a parallel run.
    workloads: Mapping[WorkloadSpec, WorkloadTrace]
    # The cost model's scheduling decision (a SchedDecision dict) when the
    # run resolved ``workers=None`` itself; None when the caller forced a
    # worker count.
    sched: Optional[dict] = None
    # Epoch traces served from the content-addressed cache instead of
    # being re-emitted (delta-aware reuse; counts stream epochs only).
    trace_reuse: int = 0
    # Run telemetry (see docs/OBSERVABILITY.md): the run manifest (git
    # sha, resolved engine/emitter, schema versions, SchedDecision),
    # workload-cache counters, and — when a tracer was active — the trace
    # id tying this result to its merged RunTrace.
    telemetry: Optional[dict] = None

    def select(self, **filters) -> List[CellResult]:
        """Cells matching all given kernel/dataset/prefetcher/seed filters."""
        out = self.cells
        for field, want in filters.items():
            out = [c for c in out if getattr(c, field) == want]
        return out

    def metrics(self, **filters) -> PrefetchMetrics:
        """The unique cell's metrics matching the filters (error otherwise)."""
        hits = self.select(**filters)
        if len(hits) != 1:
            raise KeyError(
                f"filters {filters} matched {len(hits)} cells, expected 1"
            )
        return hits[0].metrics

    def suite(self, kernel: str, dataset: str, seed: int = 0) -> Dict[str, PrefetchMetrics]:
        """Legacy-shaped ``{prefetcher: metrics}`` view of one workload cell."""
        cells = self.select(kernel=kernel, dataset=dataset, seed=seed)
        if not cells:
            raise KeyError(
                f"({kernel}, {dataset}, seed={seed}) matched no cells; "
                f"workloads run: {sorted(set((c.kernel, c.dataset, c.seed) for c in self.cells))}"
            )
        out: Dict[str, PrefetchMetrics] = {}
        for c in cells:
            if c.prefetcher in out:
                raise KeyError(
                    f"({kernel}, {dataset}, seed={seed}) matched multiple "
                    "workload specs; use select(spec=...) to disambiguate"
                )
            out[c.prefetcher] = c.metrics
        return out

    def rows(self) -> List[dict]:
        """Tidy per-cell rows: grid coordinates + flattened metrics.

        Stream cells gain ``epoch`` (and ``lifecycle``) columns; serving
        cells gain ``tenant`` (and ``table_mode``); plain cells keep the
        exact legacy schema.
        """
        out = []
        for c in self.cells:
            row = dict(
                kernel=c.kernel,
                dataset=c.dataset,
                prefetcher=c.prefetcher,
                seed=c.seed,
            )
            if c.epoch is not None:
                row["epoch"] = c.epoch
                row["lifecycle"] = c.lifecycle
            if c.tenant is not None:
                row["tenant"] = c.tenant
                row["table_mode"] = c.table_mode
            row.update(c.metrics.row())
            out.append(row)
        return out

    def workload(self, kernel: str, dataset: str, seed: int = 0) -> WorkloadTrace:
        """The unique built trace for (kernel, dataset, seed); with several
        specs sharing those coordinates, index ``workloads`` by spec."""
        hits = [
            s
            for s in self.workloads
            if (s.kernel, s.dataset, s.seed) == (kernel, dataset, seed)
        ]
        if len(hits) != 1:
            raise KeyError(
                f"({kernel}, {dataset}, seed={seed}) matched {len(hits)} "
                "workloads; index result.workloads by WorkloadSpec instead"
            )
        return self.workloads[hits[0]]


class Experiment:
    """Declarative builder for a prefetcher-evaluation grid.

    Either give ``kernels`` + ``datasets`` (the cross product is taken, once
    per seed) or pass explicit ``workloads=[WorkloadSpec(...), ...]``.
    ``prefetchers`` accepts registry names, :class:`PrefetcherSpec` objects,
    ``(name, generator)`` pairs, or a mapping — see
    :func:`repro.core.registry.resolve_prefetchers`.
    """

    def __init__(
        self,
        kernels: Optional[Sequence[str]] = None,
        datasets: Optional[Sequence[str]] = None,
        prefetchers: Iterable = ("amc",),
        hierarchy: HierarchyConfig = SCALED,
        seeds: Sequence[int] = (0,),
        workloads: Optional[Sequence[WorkloadSpec]] = None,
        cache: Optional[WorkloadCache] = None,
    ):
        if workloads is not None:
            if kernels is not None or datasets is not None:
                raise ValueError("pass either workloads= or kernels=+datasets=")
            if hierarchy is not SCALED or tuple(seeds) != (0,):
                raise ValueError(
                    "hierarchy=/seeds= apply to the kernels=+datasets= grid; "
                    "with workloads=, declare them on each WorkloadSpec"
                )
            # Multi-epoch stream scenarios (repro.stream.protocol.StreamSpec)
            # and multi-tenant serving scenarios (repro.serve.protocol.
            # ServeSpec) mix freely with plain workloads; they expand into
            # per-epoch / per-tenant workload specs at run time and score
            # through their protocol modules (duck-typed so those modules
            # load lazily).
            self.stream_specs = [
                w for w in workloads if getattr(w, "is_stream", False)
            ]
            self.serve_specs = [
                w for w in workloads if getattr(w, "is_serve", False)
            ]
            self.workload_specs = [
                w
                for w in workloads
                if not getattr(w, "is_stream", False)
                and not getattr(w, "is_serve", False)
            ]
        else:
            self.stream_specs = []
            self.serve_specs = []
            if not kernels or not datasets:
                raise ValueError("kernels= and datasets= must both be non-empty")
            self.workload_specs = [
                WorkloadSpec(kernel=k, dataset=d, hierarchy=hierarchy, seed=s)
                for k in kernels
                for d in datasets
                for s in seeds
            ]
        # Fail fast on typo'd names at declaration time, not first build.
        for spec in self.workload_specs + self.stream_specs + self.serve_specs:
            spec.validate_names()
        self.prefetchers: List[Tuple[str, Prefetcher]] = resolve_prefetchers(
            prefetchers
        )
        self.cache = cache if cache is not None else WorkloadCache()

    @property
    def prefetcher_names(self) -> List[str]:
        return [name for name, _ in self.prefetchers]

    @property
    def grid(self) -> List[Tuple[WorkloadSpec, str]]:
        """The full (workload, prefetcher) evaluation grid, in run order."""
        return [
            (spec, name)
            for spec in self.workload_specs
            for name in self.prefetcher_names
        ]

    def run(
        self,
        verbose: bool = False,
        workers: Optional[int] = None,
        pipeline: bool = True,
    ) -> ExperimentResult:
        """Build every workload (cached) and score every grid cell.

        ``workers=N`` (N >= 2) opts into the parallel execution engine:
        cells are sharded across a spawned process pool, grouped by
        workload so each trace is built once, with built traces persisted
        in the workload artifact cache.  Cell ordering and every metric
        are bit-identical to the serial path.  ``workers=1`` forces the
        serial reference implementation; the default (``workers=None``)
        consults the scheduler's cost model
        (:func:`repro.core.exec.scheduler.plan_execution`): task costs are
        estimated from artifact-cache metadata (spec-derived on a cold
        cache), and a pool is spawned only when its predicted time —
        spawn overhead plus the load-balanced makespan — beats running
        in-process.  On a single core, under memory pressure, or with
        unpicklable ad-hoc prefetchers (which cannot cross the spawn
        boundary) the run degrades to serial with no pool at all.  The
        decision is surfaced as ``result.sched``.  On any JAX backend but
        ``cpu`` this process holds the chip: ``workers=None`` resolves
        serial and ``workers>1`` raises, since spawned children could not
        reach the device.

        The persistent JAX compilation cache is placed by
        :func:`repro.core.exec.compile_cache.use_compile_cache`.

        ``pipeline`` selects the overlapped schedule (score tasks
        dispatched as their builds complete) over the legacy phased
        materialize-all-then-score-all schedule; both are bit-identical
        to serial, the flag exists for the bench's A/B comparison.

        Stream workloads expand into per-epoch traces (built/cached like
        any workload — under ``workers=N`` the epochs of every stream are
        materialized across the pool and handed to the scorer as each
        build lands) and are scored *in the parent* by the stream
        protocol, whose cross-epoch table lifecycle is inherently
        sequential; stream results are therefore byte-identical between
        serial and parallel runs too.  Serving workloads follow the same
        contract: per-tenant traces materialize across the pool, the
        interleaved shared-LLC scoring runs in the parent.  Epoch traces
        are content-keyed, so epochs whose graph the churn model left
        unchanged are *reused* rather than re-emitted
        (``result.trace_reuse`` counts them).
        """
        with obs.span(
            "experiment_run",
            workloads=len(self.workload_specs),
            streams=len(self.stream_specs),
            serves=len(self.serve_specs),
            prefetchers=self.prefetcher_names,
        ):
            result = self._run_impl(verbose, workers, pipeline)
        result.telemetry = self._telemetry(result.sched)
        return result

    def _run_impl(
        self, verbose: bool, workers: Optional[int], pipeline: bool
    ) -> ExperimentResult:
        from repro.core.exec.compile_cache import use_compile_cache

        use_compile_cache()
        sched = None
        if workers is None:
            sched = self._plan_schedule()
            record(f"sched_decision[{sched.mode}]")
            workers = sched.workers
        if workers > 1:
            if self.workload_specs:
                result = self._run_parallel(workers, verbose, pipeline)
            else:  # stream/serve-only grid: no cells to shard, only builds
                result = ExperimentResult(cells=[], workloads={})
            if self.stream_specs:
                self._append_stream_cells(result, verbose, workers=workers)
            if self.serve_specs:
                self._append_serve_cells(result, verbose, workers=workers)
            result.sched = sched.as_dict() if sched is not None else None
            return result
        cells: List[CellResult] = []
        traces: Dict[WorkloadSpec, WorkloadTrace] = {}
        for spec in self.workload_specs:
            if getattr(spec, "is_sharded", False):
                # Sharded cells stream from the on-disk shard store (never a
                # whole WorkloadTrace), so they always need an artifact cache
                # — attach the default one exactly as the parallel path does.
                from repro.core.exec import sharded

                if self.cache.artifacts is None:
                    self.cache.artifacts = ArtifactCache()
                for name, m in sharded.score_sharded(
                    spec, self.prefetchers, self.cache.artifacts
                ):
                    cells.append(
                        CellResult(
                            kernel=spec.kernel,
                            dataset=spec.dataset,
                            prefetcher=name,
                            seed=spec.seed,
                            metrics=m,
                            spec=spec,
                        )
                    )
                    if verbose:
                        print(
                            f"[{spec.kernel}/{spec.dataset}] {name}: "
                            f"speedup {m.speedup:.2f} coverage {m.coverage:.2f} "
                            f"accuracy {m.accuracy:.2f}"
                        )
                continue
            w = self.cache.get_or_build(spec)
            traces[spec] = w
            t0 = time.perf_counter()
            metrics = score_prefetchers_batched(w, self.prefetchers)
            if self.cache.artifacts is not None and self.prefetchers:
                self.cache.artifacts.record_cost(
                    spec,
                    score_s_per_prefetcher=(
                        (time.perf_counter() - t0) / len(self.prefetchers)
                    ),
                )
            for (name, gen), m in zip(self.prefetchers, metrics):
                cells.append(
                    CellResult(
                        kernel=spec.kernel,
                        dataset=spec.dataset,
                        prefetcher=name,
                        seed=spec.seed,
                        metrics=m,
                        spec=spec,
                    )
                )
                if verbose:
                    print(
                        f"[{spec.kernel}/{spec.dataset}] {name}: "
                        f"speedup {m.speedup:.2f} coverage {m.coverage:.2f} "
                        f"accuracy {m.accuracy:.2f}"
                    )
        result = ExperimentResult(cells=cells, workloads=traces)
        if self.stream_specs:
            self._append_stream_cells(result, verbose, workers=None)
        if self.serve_specs:
            self._append_serve_cells(result, verbose, workers=None)
        result.sched = sched.as_dict() if sched is not None else None
        return result

    def _telemetry(self, sched: Optional[dict]) -> dict:
        """Provenance + counters block for ``ExperimentResult.telemetry``."""
        from repro.core.obs.manifest import run_manifest

        doc = {
            "manifest": run_manifest(sched=sched),
            "workload_cache": {
                "hits": self.cache.hits,
                "builds": self.cache.builds,
                "loads": self.cache.loads,
                "reuses": self.cache.reuses,
            },
        }
        tracer = obs.current_tracer()
        if tracer is not None:
            doc["trace_id"] = tracer.trace_id
        return doc

    def _plan_schedule(self):
        """Resolve ``workers=None`` through the scheduler's cost model.

        Every independent build in the run — plain workloads, stream
        epochs, serve tenants — is costed against the artifact store;
        :func:`repro.core.exec.scheduler.plan_execution` then picks
        serial in-process execution or a pipelined pool sized from the
        predicted makespan.  A JAX backend other than ``cpu`` forces
        serial (this process holds the chip), and so do unpicklable ad-hoc
        prefetchers (``workers=N`` rejects them loudly, but a *default*
        must tolerate them)."""
        import os
        import pickle

        from repro.core.exec import scheduler  # lazy: avoids import cycle

        forced = scheduler.device_decision()
        if forced is not None:
            return forced
        try:
            for _, gen in self.prefetchers:
                pickle.dumps(gen)
        except Exception:
            return scheduler.SchedDecision(
                mode="serial",
                workers=1,
                est_serial_s=0.0,
                est_pool_s=None,
                reason=(
                    "unpicklable ad-hoc prefetchers cannot cross the "
                    "spawn boundary"
                ),
                cores=os.cpu_count() or 1,
                n_tasks=0,
                measured_frac=0.0,
            )
        specs = list(self.workload_specs)
        for s in self.stream_specs:
            specs.extend(s.epoch_specs())
        for s in self.serve_specs:
            specs.extend(s.tenant_workloads())
        artifacts = (
            self.cache.artifacts
            if self.cache.artifacts is not None
            else ArtifactCache()
        )
        return scheduler.plan_execution(specs, len(self.prefetchers), artifacts)

    def _auto_workers(self) -> int:
        """The worker count ``workers=None`` resolves to (see
        :meth:`_plan_schedule`); kept as the stable introspection point."""
        return self._plan_schedule().workers

    def _append_stream_cells(
        self, result: ExperimentResult, verbose: bool, workers: Optional[int]
    ) -> None:
        """Score every stream scenario and fold its per-epoch cells in.

        Parallel runs hand epochs off as they materialize: the lifecycle
        scorer starts on epoch 0 while later epochs are still building in
        the pool (:class:`~repro.core.exec.scheduler.MaterializePipeline`
        + :class:`_PipelinedTraces`), instead of waiting for all builds.
        Either path counts delta-aware reuse — unique epoch specs whose
        trace came from the content-addressed cache (or an in-memory
        content alias) rather than a fresh emission — into
        ``result.trace_reuse``; the count is identical serial vs pooled.
        """
        from repro.stream import protocol  # lazy: the protocol imports us

        epoch_specs = {
            es: None for spec in self.stream_specs for es in spec.epoch_specs()
        }
        builds_before = self.cache.builds
        pipeline = None
        if workers is not None and workers > 1:
            # Epochs are independent *builds*: fan them across the pool,
            # then walk the lifecycle sequentially in the parent, pulling
            # each epoch as its build lands.
            from repro.core.exec import scheduler

            if self.cache.artifacts is None:
                self.cache.artifacts = ArtifactCache()
            pipeline = scheduler.MaterializePipeline(
                list(epoch_specs),
                workers=workers,
                artifacts=self.cache.artifacts,
            )
        try:
            for spec in self.stream_specs:
                if pipeline is not None:
                    traces: Sequence = _PipelinedTraces(
                        pipeline, spec.epoch_specs(), self.cache
                    )
                else:
                    traces = [
                        self.cache.get_or_build(es) for es in spec.epoch_specs()
                    ]
                for cell in protocol.score_stream(spec, self.prefetchers, traces):
                    result.cells.append(
                        CellResult(
                            kernel=spec.kernel,
                            dataset=spec.dataset,
                            prefetcher=cell.prefetcher,
                            seed=spec.seed,
                            metrics=cell.metrics,
                            spec=cell.spec,
                            epoch=cell.epoch,
                            lifecycle=cell.lifecycle,
                        )
                    )
                    if verbose:
                        m = cell.metrics
                        print(
                            f"[{spec.kernel}/{spec.dataset}@e{cell.epoch}] "
                            f"{cell.prefetcher}: speedup {m.speedup:.2f} "
                            f"coverage {m.coverage:.2f} accuracy {m.accuracy:.2f}"
                        )
        finally:
            if pipeline is not None:
                pipeline.close()
        if pipeline is not None:
            result.trace_reuse += pipeline.n_specs - pipeline.n_built
        else:
            result.trace_reuse += len(epoch_specs) - (
                self.cache.builds - builds_before
            )
        if isinstance(result.workloads, dict):
            for spec in self.stream_specs:
                for es in spec.epoch_specs():
                    result.workloads[es] = self.cache.get_or_build(es)
        else:
            result.workloads = _LazyWorkloads(
                self.cache.get_or_build,
                list(result.workloads) + list(epoch_specs),
            )

    def _append_serve_cells(
        self, result: ExperimentResult, verbose: bool, workers: Optional[int]
    ) -> None:
        """Score every serving scenario and fold its per-tenant cells in."""
        from repro.serve import protocol  # lazy: the protocol imports us

        tenant_specs = {
            ws: None
            for spec in self.serve_specs
            for ws in spec.tenant_workloads()
        }
        if workers is not None and workers > 1:
            # Tenants are independent *builds*: materialize them across
            # the pool, then run the interleaved scoring in the parent.
            from repro.core.exec import scheduler

            if self.cache.artifacts is None:
                self.cache.artifacts = ArtifactCache()
            scheduler.materialize_specs(
                list(tenant_specs),
                workers=workers,
                artifacts=self.cache.artifacts,
            )
        for spec in self.serve_specs:
            traces = [
                self.cache.get_or_build(ws) for ws in spec.tenant_workloads()
            ]
            for cell in protocol.score_serve(spec, self.prefetchers, traces):
                ws = cell.spec
                result.cells.append(
                    CellResult(
                        kernel=ws.kernel,
                        dataset=ws.dataset,
                        prefetcher=cell.prefetcher,
                        seed=ws.seed,
                        metrics=cell.metrics,
                        spec=ws,
                        tenant=cell.tenant,
                        table_mode=cell.table_mode,
                    )
                )
                if verbose:
                    m = cell.metrics
                    mode = cell.table_mode or "stateless"
                    print(
                        f"[{ws.kernel}/{ws.dataset}@t{cell.tenant}] "
                        f"{cell.prefetcher}/{mode}: speedup {m.speedup:.2f} "
                        f"coverage {m.coverage:.2f} accuracy {m.accuracy:.2f}"
                    )
        if isinstance(result.workloads, dict):
            for ws in tenant_specs:
                result.workloads[ws] = self.cache.get_or_build(ws)
        else:
            known = set(result.workloads)
            result.workloads = _LazyWorkloads(
                self.cache.get_or_build,
                list(result.workloads)
                + [ws for ws in tenant_specs if ws not in known],
            )

    def _run_parallel(
        self, workers: int, verbose: bool, pipeline: bool = True
    ) -> ExperimentResult:
        from repro.core.exec import scheduler  # lazy: avoids import cycle

        if self.cache.artifacts is None:
            # Workers share builds through the artifact store; attach the
            # default one so the in-process cache sees the same artifacts.
            self.cache.artifacts = ArtifactCache()
        metrics, prebuilt = scheduler.run_grid(
            self.workload_specs,
            self.prefetchers,
            workers=workers,
            artifacts=self.cache.artifacts,
            verbose=verbose,
            pipeline=pipeline,
        )
        # Later experiments sharing this cache reuse any parent-side builds.
        for spec, trace in prebuilt.items():
            self.cache._store.setdefault(spec, trace)
        cells = [
            CellResult(
                kernel=spec.kernel,
                dataset=spec.dataset,
                prefetcher=name,
                seed=spec.seed,
                metrics=metrics[(spec, name)],
                spec=spec,
            )
            for spec in self.workload_specs
            for name in self.prefetcher_names
        ]
        # Workers persisted their traces in the artifact store; materialize
        # them lazily so runs that only read metrics never pay the loads.
        # Sharded cells have no whole-trace artifact to load, so they are
        # never part of the workloads mapping (serial runs agree).
        workloads = _LazyWorkloads(
            self.cache.get_or_build,
            dict.fromkeys(
                s
                for s in self.workload_specs
                if not getattr(s, "is_sharded", False)
            ),
        )
        return ExperimentResult(cells=cells, workloads=workloads)


__all__ = [
    "CellResult",
    "Experiment",
    "ExperimentResult",
    "WorkloadCache",
    "score_prefetcher",
    "score_prefetchers_batched",
]
