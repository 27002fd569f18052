"""Policy evaluation kernel and the fixed-grid sweep built on it.

:func:`evaluate` is the reusable kernel: replay any set of policy configs
over one :class:`TelemetryStore`, one :class:`PolicyOutcome` per config.
:func:`run_sweep` is its fixed-grid caller — it assembles a
:class:`Frontier` (energy saved vs performance penalty per config, the
Pareto-optimal subset flagged, per-job CDFs attached) from the default
200-config grid. :func:`repro.whatif.search.search_frontier` is the
*closed-loop* caller: the same kernel inside a budgeted refinement loop
around the Pareto knee.

Execution model: the store's shards are partitioned by host label (each
(job, host, device) stream lives entirely under one host label, so
partitions hold disjoint streams); each partition streams its shards once.
By default (``batched=True``) the whole grid rides one
:class:`~repro.whatif.replay.BatchedPolicyReplayer` per partition: the grid
is grouped into family batches and every stream segment is classified,
run-length-encoded and baseline-integrated ONCE for all configs, each
family evaluated as a ``(n_configs, n_samples)`` block — the sweep is
O(rows + configs), not O(rows x configs). ``batched=False`` keeps one
:class:`~repro.whatif.replay.PolicyReplayer` per config (sharing only
grouping + classification via :func:`repro.whatif.replay.replay_chunk`);
it is the reference oracle the batched path is verified bit-identical
against. Either way peak memory is one shard + per-stream carry state.
With ``workers > 1`` partitions run in a process pool and the replayers are
merged (disjoint-stream merge); every per-stream computation is identical
and the cross-stream reductions are exact (``math.fsum``) or order-fixed
(sorted stream keys), so ``workers=N`` is **bit-identical** to
``workers=1``.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import TYPE_CHECKING, Iterable, Sequence

import numpy as np

import repro.obs as obs
from repro.core.controller import ControllerConfig, DownscaleMode
from repro.core.imbalance import PoolConfig, PoolPolicy
from repro.telemetry.pipeline import map_shard_partitions
from repro.whatif.policies import (DownscalePolicy, NoOpPolicy, ParkingPolicy,
                                   Policy, PowerCapPolicy)
from repro.whatif.replay import (BatchedPolicyReplayer, PolicyReplayer,
                                 ReplayResult, replay_chunk)

if TYPE_CHECKING:
    from repro.telemetry.storage import TelemetryStore


# --------------------------------------------------------------------------- #
# Default policy grid
# --------------------------------------------------------------------------- #
def default_policy_grid(dense: bool = True) -> list[Policy]:
    """Policy configs spanning the paper's mitigation space.

    ``dense=True`` (default): 200 configs — 1 no-op + 64 Algorithm-1
    downscale (X x Y x mode) + 21 consolidation (k-of-n x resume latency)
    + 114 power caps. The dense parking/cap axes follow the "Model Parking
    Tax" trade-off study; a grid this size is only affordable because the
    config-axis batched replay makes the sweep O(rows + configs).

    ``dense=False``: the legacy 48-config grid (1 + 24 + 6 + 17) that the
    committed ``BENCH_whatif_sweep.json`` baseline measures.
    """
    grid: list[Policy] = [NoOpPolicy()]
    xs = ((0.5, 1.0, 2.0, 3.0, 5.0, 8.0, 10.0, 15.0) if dense
          else (1.0, 2.0, 3.0, 5.0, 8.0, 10.0))
    ys = (1.0, 2.0, 5.0, 10.0) if dense else (2.0, 5.0)
    for x in xs:
        for y in ys:
            for mode in (DownscaleMode.SM_ONLY, DownscaleMode.SM_AND_MEM):
                grid.append(DownscalePolicy(config=ControllerConfig(
                    threshold_x_s=x, cooldown_y_s=y, mode=mode)))
    resumes = (2.0, 5.0, 10.0, 30.0, 60.0) if dense else (5.0, 30.0)
    for k in (1, 2, 3):
        for resume_s in resumes:
            grid.append(ParkingPolicy(
                pool=PoolConfig(n_devices=4, policy=PoolPolicy.CONSOLIDATED,
                                n_active=k),
                resume_latency_s=resume_s))
    if dense:
        for k in (2, 4, 6):
            for resume_s in (5.0, 30.0):
                grid.append(ParkingPolicy(
                    pool=PoolConfig(n_devices=8,
                                    policy=PoolPolicy.CONSOLIDATED,
                                    n_active=k),
                    resume_latency_s=resume_s))
    n_caps = 114 if dense else 17
    for frac in np.linspace(0.25, 0.95, n_caps):
        grid.append(PowerCapPolicy(cap_fraction=round(float(frac), 4)))
    return grid


# --------------------------------------------------------------------------- #
# Frontier report
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class PolicyOutcome:
    """One grid point on the energy/perf trade-off frontier."""

    name: str
    params: dict
    n_jobs: int
    baseline_energy_j: float
    counterfactual_energy_j: float
    energy_saved_j: float
    saved_fraction: float
    penalty_s: float
    penalty_fraction: float
    wake_events: int
    downscale_events: int
    throttled_time_s: float
    exec_idle_energy_fraction_baseline: float
    exec_idle_energy_fraction_cf: float
    #: sorted per-job CDFs (x-axes of the Fig-7-style what-if plots)
    per_job_saved_fraction: tuple[float, ...]
    per_job_penalty_s: tuple[float, ...]
    pareto: bool = False


@dataclasses.dataclass(frozen=True)
class Frontier:
    """Sweep result: one outcome per policy config, Pareto subset flagged.

    Produced by the fixed-grid :func:`run_sweep` / :func:`sweep_frame` and
    by the closed-loop :func:`repro.whatif.search.search_frontier` (whose
    :class:`~repro.whatif.search.SearchResult.frontier` holds every config
    the search evaluated). :func:`repro.whatif.search.find_knee` locates a
    frontier's point of diminishing returns;
    :meth:`best_within_penalty` / :class:`repro.whatif.search.PenaltyBudget`
    answer the budget question directly.

    ``n_runs`` is the run-level IR's compact axis size when the sweep took
    the compact path (0 otherwise): ``n_rows / n_runs`` is the corpus's
    compaction ratio — a direct view of how idle-dominated (and therefore
    run-compressible) the fleet telemetry is.

    ``trace`` is the closed-loop search's eval-by-eval convergence record
    (empty for fixed-grid sweeps): one dict per evaluated config, in
    evaluation order — ``{"i", "round", "family", "saved_fraction",
    "penalty_s"}`` — deliberately containing only deterministic replay
    results (no wall-clock), so frontiers stay **bit-identical** whether
    observability is on or off. Render with
    :func:`repro.whatif.report.format_search_trace`.
    """

    outcomes: tuple[PolicyOutcome, ...]
    n_rows: int
    n_jobs: int
    n_runs: int = 0
    trace: tuple[dict, ...] = ()
    #: rows replayed / rows on disk — 1.0 unless shards were skipped under
    #: ``strict=False`` (see README "Robustness & dirty telemetry")
    coverage: float = 1.0

    @property
    def compaction_ratio(self) -> float:
        return self.n_rows / self.n_runs if self.n_runs else float("nan")

    def pareto_set(self) -> list[PolicyOutcome]:
        return [o for o in self.outcomes if o.pareto]

    def best_within_penalty(self, max_penalty_s: float) -> PolicyOutcome | None:
        """Highest-saving config whose modeled penalty fits the budget."""
        ok = [o for o in self.outcomes if o.penalty_s <= max_penalty_s]
        return max(ok, key=lambda o: o.energy_saved_j) if ok else None


def pareto_flags(saved: Sequence[float], penalty: Sequence[float]) -> list[bool]:
    """Non-dominated points for (maximize saved, minimize penalty).

    Point ``j`` dominates ``i`` iff ``saved[j] >= saved[i]`` and
    ``penalty[j] <= penalty[i]``, one of them strictly. So exact duplicates
    are both kept, ``-0.0`` equals ``0.0``, and a point with NaN in either
    coordinate is never dominated and never dominates. One lexicographic
    sort (penalty ascending, then saving descending) and a running maximum
    of saving over lower penalties: O(n log n), comparisons only, so the
    flags are exact.
    """
    with obs.span("whatif.pareto", n=len(saved)):
        s = np.asarray(saved, dtype=np.float64)
        p = np.asarray(penalty, dtype=np.float64)
        flags = np.ones(len(s), dtype=bool)
        order = np.flatnonzero(~(np.isnan(s) | np.isnan(p)))
        if order.size:
            order = order[np.lexsort((-s[order], p[order]))]
            so, po = s[order], p[order]
            first = np.concatenate(([True], po[1:] != po[:-1]))
            group = np.cumsum(first) - 1
            best = so[first]  # each equal-penalty group's highest saving
            dominated = so < best[group]
            # any strictly lower penalty with a saving as high; the first
            # group has none (a running maximum from -inf would mark a
            # -inf saving there dominated)
            below = np.maximum.accumulate(best)[np.maximum(group - 1, 0)]
            dominated |= (group > 0) & (below >= so)
            flags[order] = ~dominated
    return flags.tolist()


def assemble_frontier(outcomes: Sequence[PolicyOutcome],
                      n_rows: int = 0, n_runs: int = 0,
                      trace: Sequence[dict] = (),
                      coverage: float = 1.0) -> Frontier:
    """Build a :class:`Frontier` from already-evaluated outcomes, recomputing
    the Pareto flags over exactly this set (any flags carried in are
    discarded). The closed-loop search accumulates outcomes across
    refinement rounds and re-assembles after every round (passing its
    convergence ``trace``)."""
    flags = pareto_flags([o.energy_saved_j for o in outcomes],
                         [o.penalty_s for o in outcomes])
    flagged = tuple(dataclasses.replace(o, pareto=f)
                    for o, f in zip(outcomes, flags))
    n_jobs = max((o.n_jobs for o in flagged), default=0)
    return Frontier(outcomes=flagged, n_rows=n_rows, n_jobs=n_jobs,
                    n_runs=n_runs, trace=tuple(trace), coverage=coverage)


def _outcome(result: ReplayResult) -> PolicyOutcome:
    saved_cdf = tuple(sorted(float(j.saved_fraction) for j in result.jobs))
    penalty_cdf = tuple(sorted(float(j.penalty_s) for j in result.jobs))
    return PolicyOutcome(
        name=result.policy_name,
        params=result.policy_params,
        n_jobs=len(result.jobs),
        baseline_energy_j=result.baseline.total_energy_j,
        counterfactual_energy_j=result.counterfactual.total_energy_j,
        energy_saved_j=result.energy_saved_j,
        saved_fraction=result.saved_fraction,
        penalty_s=result.penalty_s,
        penalty_fraction=result.penalty_fraction,
        wake_events=result.wake_events,
        downscale_events=result.downscale_events,
        throttled_time_s=result.throttled_time_s,
        exec_idle_energy_fraction_baseline=result.baseline.exec_idle_energy_fraction,
        exec_idle_energy_fraction_cf=result.counterfactual.exec_idle_energy_fraction,
        per_job_saved_fraction=saved_cdf,
        per_job_penalty_s=penalty_cdf,
    )


def _assemble(results: list[ReplayResult], n_rows: int,
              n_runs: int = 0) -> Frontier:
    return assemble_frontier([_outcome(r) for r in results], n_rows, n_runs)


# --------------------------------------------------------------------------- #
# Evaluation kernel and its fixed-grid caller
# --------------------------------------------------------------------------- #
def _replay_partition(
    root: str,
    shard_files: list[str],
    policies: Sequence[Policy],
    mmap: bool,
    replayer_kwargs: dict,
    strict: bool = True,
    verify: bool = False,
) -> tuple[list[PolicyReplayer], list[dict]]:
    """Stream one shard subset through every policy's replayer (worker body;
    must stay module-level picklable). The reference oracle path."""
    from repro.telemetry.storage import TelemetryStore
    store = TelemetryStore(root)
    replayers = [PolicyReplayer(p, **replayer_kwargs) for p in policies]
    skips: list[dict] = []
    for name in shard_files:
        frame = store.read_shard_or_skip(name, skips, mmap=mmap,
                                         strict=strict, verify=verify)
        if frame is not None:
            replay_chunk(replayers, frame)
    return replayers, skips


def _replay_partition_batched(
    root: str,
    shard_files: list[str],
    policies: Sequence[Policy],
    mmap: bool,
    replayer_kwargs: dict,
    strict: bool = True,
    verify: bool = False,
) -> tuple[BatchedPolicyReplayer, list[dict]]:
    """Stream one shard subset through the config-axis batched replayer
    (worker body; must stay module-level picklable)."""
    from repro.telemetry.storage import TelemetryStore
    store = TelemetryStore(root)
    replayer = BatchedPolicyReplayer(policies, **replayer_kwargs)
    skips: list[dict] = []
    for name in shard_files:
        frame = store.read_shard_or_skip(name, skips, mmap=mmap,
                                         strict=strict, verify=verify)
        if frame is not None:
            replayer.update(frame)
    return replayer, skips


def _ir_skips(ir_obj, hosts: Iterable[str] | None) -> list[dict]:
    """The IR's recorded shard skips, filtered to the replayed host set."""
    if not ir_obj.skipped:
        return []
    host_set = set(hosts) if hosts is not None else None
    return [dict(s) for s in ir_obj.skipped
            if host_set is None or s.get("host") in host_set]


def _merge_skips(*skip_lists: Sequence[dict]) -> list[dict]:
    """Concatenate skip-record lists, deduplicating by shard file (the IR
    and a row-fallback recursion may both report the same bad shard)."""
    seen: set = set()
    out: list[dict] = []
    for lst in skip_lists:
        for s in lst:
            key = s.get("file")
            if key in seen:
                continue
            seen.add(key)
            out.append(s)
    return out


def _coverage_of(store: "TelemetryStore", hosts: Iterable[str] | None,
                 skips: Sequence[dict]) -> float:
    """Rows replayed / rows on disk for the host selection (1.0 when no
    shards were skipped or the store is empty)."""
    if not skips:
        return 1.0
    expected = store.rows_on_disk(hosts)
    if expected <= 0:
        return 1.0
    return max(0.0, 1.0 - sum(float(s.get("rows", 0)) for s in skips)
               / expected)


def _evaluate(
    configs: Sequence[Policy],
    store: "TelemetryStore",
    workers: int = 1,
    hosts: Iterable[str] | None = None,
    mmap: bool = False,
    batched: bool = True,
    replayer_kwargs: dict | None = None,
    compact: bool | None = None,
    ir=None,
    strict: bool = True,
    verify: bool = False,
    fault=None,
) -> tuple[list[ReplayResult], int, int, list[dict]]:
    """Kernel body shared by :func:`evaluate` / :func:`run_sweep`: one
    :class:`ReplayResult` per config in input order, plus the replayed
    job-attributed row count, (when the compact path ran) the IR's run
    count, and the shard skip records of a ``strict=False`` replay.

    ``compact=None`` resolves to ``batched`` — the row-exact reference
    paths (``batched=False`` / ``compact=False``) stay byte-for-byte what
    they were. With the compact path on, configs the IR supports replay
    against the run axis (:func:`repro.whatif.replay.replay_ir`); the rest
    — custom policies, mismatched thresholds, unsupported composites —
    stream the store through the row path, and an irregularly-sampled
    store falls back entirely (a ``compact -> row`` fallback in the
    degradation ladder).
    """
    configs = list(configs)
    replayer_kwargs = replayer_kwargs or {}
    if compact is None:
        compact = batched

    if compact:
        from repro.whatif import ir as ir_mod
        from repro.whatif.replay import replay_ir

        classifier = replayer_kwargs.get("classifier", None)
        dt_s = replayer_kwargs.get("dt_s", 1.0)
        if ir is not None:
            ir_obj = ir
        else:
            from repro.core.states import DEFAULT_CLASSIFIER
            cfg = ir_mod.ir_config_for(
                configs, classifier or DEFAULT_CLASSIFIER, dt_s)
            ir_obj = None
            if any(ir_mod.ir_supported(p, cfg) for p in configs):
                try:
                    ir_obj = ir_mod.get_ir(store, cfg, workers=workers,
                                           mmap=mmap, strict=strict,
                                           verify=verify, fault=fault)
                except ir_mod.IRUnsupportedError:
                    ir_obj = None       # e.g. irregular sampling: use rows
                    obs.fallback("compact", "row", "ir_unsupported")
        if ir_obj is not None:
            sup = [i for i, p in enumerate(configs)
                   if ir_mod.ir_supported(p, ir_obj.config)]
            if sup:
                ir_kwargs = {k: v for k, v in replayer_kwargs.items()
                             if k in ("platform_of", "min_job_duration_s",
                                      "min_interval_s", "classifier", "dt_s")}
                obs.counter("repro_replay_configs_total", float(len(sup)),
                            path="compact",
                            help="policy configs replayed, by execution path")
                sup_results = replay_ir(
                    ir_obj, [configs[i] for i in sup], hosts=hosts,
                    workers=workers, fault=fault, **ir_kwargs)
                skips = _ir_skips(ir_obj, hosts)
                results: list[ReplayResult | None] = [None] * len(configs)
                for i, res in zip(sup, sup_results):
                    results[i] = res
                rest = [i for i in range(len(configs)) if results[i] is None]
                if rest:
                    obs.counter("repro_replay_row_fallback_configs_total",
                                float(len(rest)),
                                help="configs the IR could not cover "
                                     "(row-path fallback)")
                    rest_results, _, _, rest_skips = _evaluate(
                        [configs[i] for i in rest], store, workers=workers,
                        hosts=hosts, mmap=mmap, batched=batched,
                        replayer_kwargs=replayer_kwargs, compact=False,
                        strict=strict, verify=verify, fault=fault)
                    for i, res in zip(rest, rest_results):
                        results[i] = res
                    skips = _merge_skips(skips, rest_skips)
                selected = ir_obj.select(hosts)
                n_rows = sum(s.n_rows for s in selected)
                n_runs = sum(s.n_runs for s in selected)
                return results, n_rows, n_runs, skips

    if batched:
        obs.counter("repro_replay_configs_total", float(len(configs)),
                    path="row_batched",
                    help="policy configs replayed, by execution path")
        replayer, skips = map_shard_partitions(
            store, hosts, workers, _replay_partition_batched,
            (configs, mmap, replayer_kwargs, strict, verify),
            merge=lambda a, b: a.merge(b), stage="sweep", fault=fault)
        n_rows = replayer.n_rows          # finalize() resets the counter
        return replayer.finalize(), n_rows, 0, skips

    def merge_lists(a: list[PolicyReplayer], b: list[PolicyReplayer]):
        for dst, src in zip(a, b):
            dst.merge(src)
        return a

    obs.counter("repro_replay_configs_total", float(len(configs)),
                path="row_serial",
                help="policy configs replayed, by execution path")
    replayers, skips = map_shard_partitions(
        store, hosts, workers, _replay_partition,
        (configs, mmap, replayer_kwargs, strict, verify),
        merge=merge_lists, stage="sweep", fault=fault)
    n_rows = replayers[0].n_rows if replayers else 0
    return [r.finalize() for r in replayers], n_rows, 0, skips


def resolve_backend(backend: str) -> str:
    """Resolve an ``evaluate``/``run_sweep`` ``backend`` argument.

    ``"numpy"`` (the default and the bit-exactness oracle), ``"jax"`` (the
    :mod:`repro.whatif.backend` accelerator path), or ``"auto"`` — jax when
    importable, numpy otherwise, so scripts stay portable to machines
    without the jax toolchain.
    """
    if backend == "auto":
        try:
            import repro.whatif.backend  # noqa: F401  (probe only)
        except ImportError:
            return "numpy"
        return "jax"
    if backend not in ("numpy", "jax"):
        raise ValueError(
            f"unknown backend {backend!r}; use 'numpy', 'jax' or 'auto'")
    return backend


def _evaluate_outcomes(
    configs: Sequence[Policy],
    store: "TelemetryStore",
    workers: int = 1,
    hosts: Iterable[str] | None = None,
    mmap: bool = False,
    batched: bool = True,
    replayer_kwargs: dict | None = None,
    compact: bool | None = None,
    ir=None,
    backend: str = "numpy",
    dist=None,
    strict: bool = True,
    verify: bool = False,
    fault=None,
) -> tuple[list[PolicyOutcome], int, int, list[dict]]:
    """Observability wrapper around :func:`_evaluate_outcomes_impl`: every
    evaluate call runs under a ``whatif.evaluate`` span, with its wall time
    and per-family config counts recorded when :mod:`repro.obs` is
    enabled. Pure pass-through otherwise — outcomes are bit-identical with
    obs on or off."""
    configs = list(configs)
    t0 = time.perf_counter()
    with obs.span("whatif.evaluate", configs=len(configs), backend=backend):
        out = _evaluate_outcomes_impl(
            configs, store, workers=workers, hosts=hosts, mmap=mmap,
            batched=batched, replayer_kwargs=replayer_kwargs,
            compact=compact, ir=ir, backend=backend, dist=dist,
            strict=strict, verify=verify, fault=fault)
    if obs.enabled():
        obs.observe("repro_replay_seconds", time.perf_counter() - t0,
                    help="wall time of evaluate calls")
        for fam, n in collections.Counter(p.name for p in configs).items():
            obs.counter("repro_replay_family_configs_total", float(n),
                        family=fam,
                        help="policy configs replayed, by policy family")
    return out


def _evaluate_outcomes_impl(
    configs: Sequence[Policy],
    store: "TelemetryStore",
    workers: int = 1,
    hosts: Iterable[str] | None = None,
    mmap: bool = False,
    batched: bool = True,
    replayer_kwargs: dict | None = None,
    compact: bool | None = None,
    ir=None,
    backend: str = "numpy",
    dist=None,
    strict: bool = True,
    verify: bool = False,
    fault=None,
) -> tuple[list[PolicyOutcome], int, int, list[dict]]:
    """:func:`_evaluate` lifted to outcomes, with backend dispatch.

    ``backend="jax"`` routes every IR-capable config through
    :func:`repro.whatif.backend.replay_ir_outcomes` — the jit'd
    ``(n_configs, n_runs)`` evaluators, config axis optionally sharded
    over ``dist`` (a :class:`repro.distributed.context.DistContext` from
    :func:`repro.whatif.backend.config_mesh`) — and the rest through the
    NumPy row path; stores without a usable IR fall back to NumPy
    entirely. The NumPy path remains the oracle: time/count metrics are
    bit-identical across backends, energies/penalties <= 1e-9 relative
    (tests/test_whatif_backend.py).

    Degradation ladder: only declared conditions step down, and each is
    counted. Configs the IR cannot host replay on the row path; a store
    the IR cannot hold (irregular sampling) degrades ``compact -> row``;
    a device out of memory is counted as a ``jax -> numpy`` fallback and
    the same configs replay through the NumPy compact kernel. Any other
    error from the jax backend (a compile or programming error) raises,
    so ``backend="jax"`` never runs NumPy unseen. The NumPy oracle
    contract makes every rung result-equivalent, so degradations change
    latency, never answers.
    """
    configs = list(configs)
    replayer_kwargs = replayer_kwargs or {}
    backend = resolve_backend(backend)
    if backend == "jax" and (compact is None or compact):
        from repro.whatif import ir as ir_mod

        classifier = replayer_kwargs.get("classifier", None)
        dt_s = replayer_kwargs.get("dt_s", 1.0)
        if ir is not None:
            ir_obj = ir
        else:
            from repro.core.states import DEFAULT_CLASSIFIER
            cfg = ir_mod.ir_config_for(
                configs, classifier or DEFAULT_CLASSIFIER, dt_s)
            ir_obj = None
            if any(ir_mod.ir_supported(p, cfg) for p in configs):
                try:
                    ir_obj = ir_mod.get_ir(store, cfg, workers=workers,
                                           mmap=mmap, strict=strict,
                                           verify=verify, fault=fault)
                except ir_mod.IRUnsupportedError:
                    ir_obj = None       # e.g. irregular sampling: use rows
                    obs.fallback("compact", "row", "ir_unsupported")
        if ir_obj is not None:
            sup = [i for i, p in enumerate(configs)
                   if ir_mod.ir_supported(p, ir_obj.config)]
            if sup:
                ir_kwargs = {k: v for k, v in replayer_kwargs.items()
                             if k in ("platform_of", "min_job_duration_s",
                                      "min_interval_s", "classifier", "dt_s")}
                from repro.whatif import backend as jax_backend
                try:
                    sup_out, n_rows, n_runs = jax_backend.replay_ir_outcomes(
                        ir_obj, [configs[i] for i in sup], hosts=hosts,
                        dist=dist, **ir_kwargs)
                except jax_backend.DeviceError as e:
                    if "RESOURCE_EXHAUSTED" not in str(e):
                        raise
                    obs.fallback("jax", "numpy", "device_oom")
                    sup_out = None
                if sup_out is not None:
                    obs.counter("repro_replay_configs_total",
                                float(len(sup)), path="jax",
                                help="policy configs replayed, by execution "
                                     "path")
                    skips = _ir_skips(ir_obj, hosts)
                    outcomes: list[PolicyOutcome | None] = \
                        [None] * len(configs)
                    for i, out in zip(sup, sup_out):
                        outcomes[i] = out
                    rest = [i for i in range(len(configs))
                            if outcomes[i] is None]
                    if rest:
                        obs.counter(
                            "repro_replay_row_fallback_configs_total",
                            float(len(rest)),
                            help="configs the IR could not cover "
                                 "(row-path fallback)")
                        rest_results, _, _, rest_skips = _evaluate(
                            [configs[i] for i in rest], store,
                            workers=workers, hosts=hosts, mmap=mmap,
                            batched=batched,
                            replayer_kwargs=replayer_kwargs, compact=False,
                            strict=strict, verify=verify, fault=fault)
                        for i, res in zip(rest, rest_results):
                            outcomes[i] = _outcome(res)
                        skips = _merge_skips(skips, rest_skips)
                    return outcomes, n_rows, n_runs, skips
        # nothing for the accelerator to do (or it ran out of memory)
    results, n_rows, n_runs, skips = _evaluate(
        configs, store, workers=workers, hosts=hosts, mmap=mmap,
        batched=batched, replayer_kwargs=replayer_kwargs, compact=compact,
        ir=ir, strict=strict, verify=verify, fault=fault)
    return [_outcome(r) for r in results], n_rows, n_runs, skips


def evaluate(
    configs: Sequence[Policy],
    store: "TelemetryStore",
    workers: int = 1,
    hosts: Iterable[str] | None = None,
    mmap: bool = False,
    batched: bool = True,
    compact: bool | None = None,
    ir=None,
    backend: str = "numpy",
    dist=None,
    strict: bool = True,
    verify: bool = False,
    fault=None,
    **replayer_kwargs,
) -> list[PolicyOutcome]:
    """Evaluate an arbitrary set of policy configs over a store.

    The reusable kernel under both the fixed-grid :func:`run_sweep` and the
    closed-loop :func:`repro.whatif.search.search_frontier`: replays
    ``configs`` (grouped into family batches, one pass per stream segment)
    and returns one :class:`PolicyOutcome` per config, **in input order**,
    with no Pareto flags — Pareto-ness is a property of a *set* of outcomes;
    flag a set with :func:`assemble_frontier`.

    Args:
        configs: policy configs to evaluate (any mix of families).
        store: shard store to replay (simulator output or DES/serving traces).
        workers: process-pool width. Partitions are host-label-disjoint, so
            results are bit-identical for every worker count. Scripts calling
            this with ``workers > 1`` at top level need the standard
            ``if __name__ == "__main__":`` guard (workers re-import main).
        hosts: optional host-label filter.
        mmap: pass ``mmap=True`` to shard reads (zero-copy for ``npy_dir``
            shards; see :meth:`TelemetryStore.iter_shards`).
        batched: evaluate the configs family-by-family along a config axis
            (:class:`BatchedPolicyReplayer`) — one classification / RLE /
            baseline integration per stream segment for the whole set.
            ``batched=False`` runs the per-policy reference path; both are
            bit-identical (tests/test_whatif_batched.py), the batched one is
            the fast default.
        compact: replay against the run-level IR (:mod:`repro.whatif.ir`)
            where the configs support it — the "compact once, replay many"
            fast path, O(runs) per config after a one-off O(rows) build
            that is cached in memory and as a store sidecar. ``None``
            (default) follows ``batched``; time/count metrics match the row
            paths bit-for-bit, energies/penalties to <= 1e-9 relative
            (tests/test_whatif_ir.py). Unsupported configs and
            irregularly-sampled stores fall back to the row path.
        ir: a prebuilt :class:`repro.whatif.ir.RunIR` to replay against
            (skips the cache lookup entirely; the closed-loop search passes
            one IR across all refinement rounds, and
            :func:`repro.telemetry.pipeline.analyze_store` accepts the same
            handle — one compaction serves the whole run-algebra consumer
            family: analyze / sweep / search).
        backend: ``"numpy"`` (default, the oracle), ``"jax"`` (jit'd
            run-level evaluators, :mod:`repro.whatif.backend`) or
            ``"auto"`` (jax when importable). The jax backend accelerates
            IR-capable configs on compact replays; everything else runs
            the NumPy path regardless.
        dist: optional :class:`repro.distributed.context.DistContext`
            sharding the jax backend's config axis over a device mesh
            (see :func:`repro.whatif.backend.config_mesh`); ignored by
            the NumPy backend. Results are mesh-shape-independent.
        strict: ``False`` skips unreadable shards instead of raising —
            results are bit-identical to replaying the clean shard subset
            (README "Robustness & dirty telemetry").
        verify: checksum every shard read against the manifest.
        fault: a :class:`repro.telemetry.pipeline.FaultTolerance` policy
            for the process-pool crash/hang supervisor.
        **replayer_kwargs: forwarded to the replayer
            (``min_job_duration_s``, ``platform_of``, ``classifier``, ...).
    """
    outcomes, _, _, _ = _evaluate_outcomes(
        configs, store, workers=workers, hosts=hosts, mmap=mmap,
        batched=batched, replayer_kwargs=replayer_kwargs, compact=compact,
        ir=ir, backend=backend, dist=dist, strict=strict, verify=verify,
        fault=fault)
    return outcomes


def run_sweep(
    store: "TelemetryStore",
    policies: Sequence[Policy] | None = None,
    workers: int = 1,
    hosts: Iterable[str] | None = None,
    mmap: bool = False,
    batched: bool = True,
    compact: bool | None = None,
    ir=None,
    backend: str = "numpy",
    dist=None,
    strict: bool = True,
    verify: bool = False,
    fault=None,
    **replayer_kwargs,
) -> Frontier:
    """Replay a fixed policy grid over a store and report the trade-off
    frontier — the fixed-grid caller of the :func:`evaluate` kernel.

    ``policies`` defaults to :func:`default_policy_grid` (200 configs). For
    a *budgeted* search of the same knob space instead of a dense dump, see
    :func:`repro.whatif.search.search_frontier`. All other arguments are
    :func:`evaluate`'s; ``run_sweep(compact=False)`` is the retained
    row-exact verification path for the default compact (run-IR) sweep,
    and ``backend="jax"`` runs IR-capable configs on the jit'd run-level
    evaluators (:mod:`repro.whatif.backend`). With ``strict=False`` the
    returned frontier's ``coverage`` reports the fraction of on-disk rows
    actually replayed (< 1.0 when shards were skipped).
    """
    hosts = list(hosts) if hosts is not None else None
    policies = list(default_policy_grid() if policies is None else policies)
    outcomes, n_rows, n_runs, skips = _evaluate_outcomes(
        policies, store, workers=workers, hosts=hosts, mmap=mmap,
        batched=batched, replayer_kwargs=replayer_kwargs, compact=compact,
        ir=ir, backend=backend, dist=dist, strict=strict, verify=verify,
        fault=fault)
    coverage = _coverage_of(store, hosts, skips)
    obs.gauge("repro_coverage_fraction", coverage, stage="sweep",
              help="rows analyzed / rows on disk for the last run")
    return assemble_frontier(outcomes, n_rows, n_runs, coverage=coverage)


def sweep_frame(frame, policies: Sequence[Policy] | None = None,
                batched: bool = True, **replayer_kwargs) -> Frontier:
    """In-memory convenience: sweep a single :class:`TelemetryFrame`
    (e.g. a DES :class:`PoolResult` telemetry) without a store."""
    policies = list(default_policy_grid() if policies is None else policies)
    if batched:
        replayer = BatchedPolicyReplayer(policies, **replayer_kwargs)
        replayer.update(frame)
        n_rows = replayer.n_rows          # finalize() resets the counter
        return _assemble(replayer.finalize(), n_rows)
    replayers = [PolicyReplayer(p, **replayer_kwargs) for p in policies]
    replay_chunk(replayers, frame)
    n_rows = replayers[0].n_rows if replayers else 0
    return _assemble([r.finalize() for r in replayers], n_rows)
