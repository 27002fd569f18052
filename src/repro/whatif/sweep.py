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
import functools
import time
from typing import TYPE_CHECKING, Iterable, Sequence

import numpy as np

import repro.obs as obs
from repro.core.controller import ControllerConfig, DownscaleMode
from repro.core.imbalance import PoolConfig, PoolPolicy
from repro.telemetry.pipeline import map_shard_partitions
from repro.whatif.ir import acquire_ir, ir_supported
from repro.whatif.policies import (DownscalePolicy, NoOpPolicy, ParkingPolicy,
                                   Policy, PowerCapPolicy)
from repro.whatif.replay import (BatchedPolicyReplayer, PolicyReplayer,
                                 ReplayResult, replay_chunk, replay_ir)

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
    committed ``BENCH_whatif_sweep.json`` baseline measures: a host-CPU
    record whose timing floors say nothing about the chip (the chip's
    speed record is ``python3 bench/run.py``, ``PERF.md`` and
    ``PERF_LEDGER.jsonl``).
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


# --------------------------------------------------------------------------- #
# Evaluation kernel and its fixed-grid caller
# --------------------------------------------------------------------------- #
def _row_replayer(policies: Sequence[Policy], batched: bool,
                  replayer_kwargs: dict):
    """Row-path replay state for ``policies`` and the call that feeds it a
    frame: one :class:`BatchedPolicyReplayer` for the whole set
    (``batched``), or one :class:`PolicyReplayer` per config, sharing only
    grouping and classification (the reference oracle)."""
    if batched:
        replayer = BatchedPolicyReplayer(policies, **replayer_kwargs)
        return replayer, replayer.update
    replayers = [PolicyReplayer(p, **replayer_kwargs) for p in policies]
    return replayers, functools.partial(replay_chunk, replayers)


def _merge_rows(a, b):
    """Disjoint-stream merge of two partitions' row-path replay state."""
    if isinstance(a, list):
        for dst, src in zip(a, b):
            dst.merge(src)
        return a
    return a.merge(b)


def _finish_rows(replayer) -> tuple[list[ReplayResult], int]:
    """Results in input order and the replayed job-attributed row count."""
    if isinstance(replayer, list):
        n_rows = replayer[0].n_rows if replayer else 0
        return [r.finalize() for r in replayer], n_rows
    n_rows = replayer.n_rows              # finalize() resets the counter
    return replayer.finalize(), n_rows


def _replay_partition(
    root: str,
    shard_files: list[str],
    policies: Sequence[Policy],
    mmap: bool,
    replayer_kwargs: dict,
    strict: bool,
    verify: bool,
    batched: bool,
) -> tuple[object, list[dict]]:
    """Stream one shard subset through the row path (worker body; must
    stay module-level picklable)."""
    from repro.telemetry.storage import TelemetryStore
    store = TelemetryStore(root)
    replayer, update = _row_replayer(policies, batched, replayer_kwargs)
    skips: list[dict] = []
    for name in shard_files:
        frame = store.read_shard_or_skip(name, skips, mmap=mmap,
                                         strict=strict, verify=verify)
        if frame is not None:
            update(frame)
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
    and the row path may both report the same bad shard)."""
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


def _replay_rows(
    configs: list[Policy],
    store: "TelemetryStore",
    workers: int,
    hosts: Iterable[str] | None,
    mmap: bool,
    batched: bool,
    replayer_kwargs: dict,
    strict: bool,
    verify: bool,
    fault,
) -> tuple[list[ReplayResult], int, list[dict]]:
    """Stream the store through the row path of ``batched``, one partition
    per host label: the results in input order, the replayed
    job-attributed row count and the skip records of a ``strict=False``
    replay."""
    obs.counter("repro_replay_configs_total", float(len(configs)),
                path="row_batched" if batched else "row_serial",
                help="policy configs replayed, by execution path")
    replayer, skips = map_shard_partitions(
        store, hosts, workers, _replay_partition,
        (configs, mmap, replayer_kwargs, strict, verify, batched),
        merge=_merge_rows, stage="sweep", fault=fault)
    return (*_finish_rows(replayer), skips)


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
    """Kernel body of :func:`evaluate`, :func:`run_sweep` and the search:
    one :class:`PolicyOutcome` per config in input order, the replayed
    job-attributed row count, the IR's run count when an IR path ran (0
    otherwise), and the shard skip records of a ``strict=False`` replay.
    Runs under a ``whatif.evaluate`` span; with :mod:`repro.obs` on it
    records the call's wall time and its configs per family. Outcomes are
    bit-identical with obs on or off.

    The one place that decides which engine replays which config:

    1. The IR is used when ``compact`` says so; ``compact=None`` means the
       IR on ``backend="jax"`` and follows ``batched`` on NumPy (where
       ``batched=False`` keeps the row-exact oracle).
    2. The IR is ``ir`` or comes from :func:`repro.whatif.ir.acquire_ir`;
       a store it cannot hold (irregular sampling) steps down
       ``compact -> row``.
    3. Configs the IR hosts replay on the jax backend
       (:func:`repro.whatif.backend.replay_ir_outcomes`, config axis
       optionally sharded over ``dist``, a mesh from
       :func:`repro.whatif.backend.config_mesh`) or on NumPy
       (:func:`repro.whatif.replay.replay_ir`).
    4. The rest (custom policies, foreign thresholds, other composite
       orders), or every config when no IR is used, stream the store
       through the row path of ``batched``.

    Only declared conditions step down, and each is counted: the
    ``compact -> row`` step above, and a device out of memory, counted as
    ``jax -> numpy``, after which the decision is made again as NumPy with
    the same arguments and the IR in hand. Any other error from the jax
    backend (a compile or programming error) raises, so ``backend="jax"``
    never runs NumPy unseen. Every rung holds the NumPy oracle contract
    (times and counts bit-identical, energies and penalties <= 1e-9
    relative; tests/test_whatif_backend.py), so a step down changes
    latency, never answers.
    """
    configs = list(configs)
    replayer_kwargs = replayer_kwargs or {}
    t0 = time.perf_counter()
    with obs.span("whatif.evaluate", configs=len(configs), backend=backend):
        backend = resolve_backend(backend)
        use_ir = compact if compact is not None else (backend == "jax"
                                                      or batched)
        if use_ir and ir is None:
            ir = acquire_ir(store, configs, replayer_kwargs.get("classifier"),
                            replayer_kwargs.get("dt_s", 1.0),
                            workers=workers, mmap=mmap, strict=strict,
                            verify=verify, fault=fault)
        sup = ([i for i, p in enumerate(configs)
                if ir_supported(p, ir.config)]
               if use_ir and ir is not None else [])
        ir_kwargs = {k: v for k, v in replayer_kwargs.items()
                     if k in ("platform_of", "min_job_duration_s",
                              "min_interval_s", "classifier", "dt_s")}
        sup_out = None
        if sup and backend == "jax":
            from repro.whatif import backend as jax_backend
            try:
                sup_out, _, _ = jax_backend.replay_ir_outcomes(
                    ir, [configs[i] for i in sup], hosts=hosts, dist=dist,
                    **ir_kwargs)
            except jax_backend.DeviceError as e:
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                obs.fallback("jax", "numpy", "device_oom")
                if compact is None and not batched:
                    sup = []        # NumPy's reading: the serial row oracle
            else:
                obs.counter("repro_replay_configs_total", float(len(sup)),
                            path="jax",
                            help="policy configs replayed, by execution path")
        if sup and sup_out is None:
            obs.counter("repro_replay_configs_total", float(len(sup)),
                        path="compact",
                        help="policy configs replayed, by execution path")
            sup_out = [_outcome(r) for r in replay_ir(
                ir, [configs[i] for i in sup], hosts=hosts, workers=workers,
                fault=fault, **ir_kwargs)]

        outcomes: list[PolicyOutcome | None] = [None] * len(configs)
        for i, out in zip(sup, sup_out or ()):
            outcomes[i] = out
        rest = [i for i, out in enumerate(outcomes) if out is None]
        n_rows, n_runs, skips = 0, 0, []
        if rest or not sup:
            if sup:
                obs.counter("repro_replay_row_fallback_configs_total",
                            float(len(rest)),
                            help="configs the IR could not cover "
                                 "(row-path fallback)")
            results, n_rows, skips = _replay_rows(
                [configs[i] for i in rest], store, workers, hosts, mmap,
                batched, replayer_kwargs, strict, verify, fault)
            for i, res in zip(rest, results):
                outcomes[i] = _outcome(res)
        if sup:
            selected = ir.select(hosts)
            n_rows = sum(s.n_rows for s in selected)
            n_runs = sum(s.n_runs for s in selected)
            skips = _merge_skips(_ir_skips(ir, hosts), skips)
    if obs.enabled():
        obs.observe("repro_replay_seconds", time.perf_counter() - t0,
                    help="wall time of evaluate calls")
        for fam, n in collections.Counter(p.name for p in configs).items():
            obs.counter("repro_replay_family_configs_total", float(n),
                        family=fam,
                        help="policy configs replayed, by policy family")
    return outcomes, n_rows, n_runs, skips


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
            (default) follows ``batched`` on NumPy and means the IR on
            ``backend="jax"``; time/count metrics match the row
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
        dist: optional 1-D :class:`jax.sharding.Mesh` from
            :func:`repro.whatif.backend.config_mesh`, sharding the jax
            backend's config axis over its devices; ignored by the NumPy
            backend. Results are mesh-shape-independent.
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
    replayer, update = _row_replayer(policies, batched, replayer_kwargs)
    update(frame)
    results, n_rows = _finish_rows(replayer)
    return assemble_frontier([_outcome(r) for r in results], n_rows)
