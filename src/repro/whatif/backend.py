"""JAX execution backend for the run-level replay path.

The run-level IR (:mod:`repro.whatif.ir`) made policy grids O(runs) per
config on one CPU core; this module moves the ``(n_configs, n_runs)``
evaluators onto JAX so dense per-platform grids — the 10^4-config
deadline-aware sweeps of arXiv 2004.08177-style studies — are routine:

* :func:`pack_ir` packs the ragged per-stream run tables into padded,
  **power-of-two bucketed** dense tensors with validity masks.  Streams
  sharing a padded-shape bucket share one compiled kernel, so jit
  retraces O(log n) times (once per distinct bucket), not per stream;
* the ``apply_runs`` kernels of ``NoOpBatch`` / ``DownscaleBatch`` /
  ``ParkingBatch`` / ``PowerCapBatch`` / ``CompositeBatch`` and the
  run-weighted integrator (:meth:`BatchedStreamingIntegrator.update_runs`
  / :func:`integrate_runs`) are ported to ``jax.jit``-compiled functions
  vectorized over ``(n_configs, n_runs)``; the config axis is sharded via
  ``shard_map`` over a :class:`repro.distributed.context.DistContext`
  mesh (:func:`config_mesh`), so multi-device scales near-linearly —
  every per-config op is elementwise along the axis, so sharding needs no
  cross-device communication at all. Every chip of the mesh holds the
  packed tables whole, placed once per mesh; the config-axis arrays go
  straight to their shards (:func:`_place`);
* the PowerCap sorted-power cap-bucket scan runs through
  :func:`repro.kernels.run_replay.cap_bucket_counts` — the Pallas kernel
  on TPU, the vmapped ``searchsorted`` reference elsewhere. It compares
  order-preserving int32 key words built on the host
  (:func:`~repro.kernels.run_replay.order_key_words`), because the TPU
  compiler takes no float64 operand into a Pallas call.

Oracle contract (the NumPy path stays the bit-exactness oracle, enforced
by tests/test_whatif_backend.py over random grids x chunkings x device
counts): **time and count metrics are bit-identical** to
:func:`repro.whatif.replay.replay_ir` — per-state times are integer
sample sums, Algorithm-1 decision sequences reduce to the same trigger
indices (the cooldown ``searchsorted`` is replicated exactly by an
8-probe window around the float-predicted crossing), event and throttle
counts are exact i64 — while **energies and penalties agree to <= 1e-9
relative** (float summation order differs: ``lax.scan`` accumulates
left-to-right where NumPy reduces pairwise).

Host/device split: decisions, gathers and reductions over
``(n_streams, n_configs)`` run on the device; per-stream prefix-sum
construction stays on the host and *shares the StreamIR memos with the
NumPy path* (same arrays bit-for-bit), and the final fleet fold mirrors
:func:`repro.core.energy.merge`'s left fold in sorted-stream order. The
power-cap family sends the device only its integer count; its float64
pricing runs on the host, since a TPU emulates float64 and its emulated
divide can return a float32 quotient.
"""
from __future__ import annotations

import collections.abc
import dataclasses
from typing import Iterable, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import repro.obs as obs
from repro.core.energy import EnergyBreakdown
from repro.core.power_model import ClockLevel, PlatformSpec
from repro.core.states import ClassifierConfig, DEFAULT_CLASSIFIER, DeviceState
from repro.distributed.compat import shard_map
from repro.distributed.context import DistContext
from repro.kernels.run_replay import (cap_bucket_counts, key_words_to_f64,
                                      order_key_words)
from repro.whatif.policies import (CompositeBatch, DownscaleBatch, NoOpBatch,
                                   ParkingBatch, PowerCapBatch,
                                   _NEVER_TRIGGERS, make_batches)
from repro.whatif.replay import _resolve_platform
from repro.whatif.sweep import PolicyOutcome

_DEEP = int(DeviceState.DEEP_IDLE)
_EXEC = int(DeviceState.EXECUTION_IDLE)
_ACTIVE = int(DeviceState.ACTIVE)
_STATES = (_DEEP, _EXEC, _ACTIVE)

class _TraceCountsView(collections.abc.Mapping):
    """Read-only live view of per-kernel jit trace counts.

    Retrace telemetry lives in the ``repro_backend_jit_traces_total``
    counter family of :data:`repro.obs.REGISTRY` (recorded *always-on*:
    the counts are a behavioural contract — the pack_ir property tests
    assert a replay retraces at most once per distinct padding bucket —
    so they bypass the default-off gate). This mapping keeps the
    historical ``dict(TRACE_COUNTS)`` call sites and test assertions
    working over the registry-backed counts.
    """

    _NAME = "repro_backend_jit_traces_total"

    def _snapshot(self) -> dict[str, int]:
        fam = obs.REGISTRY.family(self._NAME)
        if fam is None:
            return {}
        return {dict(key).get("kernel", ""): int(m.value)
                for key, m in fam.metrics.items()}

    def __getitem__(self, name: str) -> int:
        return self._snapshot()[name]

    def __iter__(self):
        return iter(self._snapshot())

    def __len__(self) -> int:
        return len(self._snapshot())

    def __repr__(self) -> str:
        return f"TRACE_COUNTS({self._snapshot()!r})"


#: retrace telemetry: kernel name -> number of jit traces so far. Each
#: kernel body bumps its counter at *trace* time only, so after warmup a
#: replay adds zero.
TRACE_COUNTS = _TraceCountsView()


def _mark_trace(name: str) -> None:
    # always-on: talks to the registry directly, never the gated helpers
    obs.REGISTRY.counter(
        _TraceCountsView._NAME,
        "jit kernel traces, bumped at trace time only", kernel=name).inc()


#: errors the device runtime raises; the sweep's degradation ladder steps
#: down on one of them only, a device out of memory (RESOURCE_EXHAUSTED)
DeviceError = jax.errors.JaxRuntimeError


def _pow2(n: int, floor: int) -> int:
    return max(int(floor), 1 << max(int(n) - 1, 0).bit_length())


#: bytes put on chips by :func:`_place`, once per chip copy, and config
#: lanes launched beyond the real count by :func:`_config_pad`
PLACED_BYTES = ("repro_backend_placed_bytes_total",
                "bytes put on chips, once per chip copy, labelled {devices}")
PAD_CONFIGS = ("repro_backend_pad_configs_total",
               "config lanes launched beyond the real count, labelled "
               "{family}")


# --------------------------------------------------------------------------- #
# Mesh helper
# --------------------------------------------------------------------------- #
def config_mesh(n_devices: int | None = None,
                axis: str = "data") -> DistContext:
    """A 1-D config-axis mesh over the first ``n_devices`` local devices.

    Simulate multi-device on CPU with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the test
    suite runs with 4). ``DistContext(mesh=None)`` — the default
    everywhere — keeps the backend single-device.
    """
    devs = jax.devices()
    n = len(devs) if n_devices is None else min(int(n_devices), len(devs))
    return DistContext(mesh=Mesh(np.array(devs[:n]), (axis,)),
                       batch_axes=(axis,))


def _mesh_of(dist: DistContext | None) -> Mesh | None:
    """The mesh the config axis runs over, or None on one device."""
    return dist.mesh if dist is not None and dist.enabled else None


def _place(hosts: Sequence[np.ndarray], dist: DistContext | None = None,
           config_axis: int | None = None) -> tuple[list[jax.Array], int]:
    """Every host-to-device transfer of the backend: ``jnp.asarray`` on the
    default device without a mesh; with one, each array split over the
    mesh along ``config_axis``, or replicated on every chip when it is
    None. Returns the device arrays and the bytes put on chips, counted
    once per chip copy (``repro_backend_placed_bytes_total``)."""
    mesh = _mesh_of(dist)
    if mesh is None:
        out = [jnp.asarray(a) for a in hosts]
        nbytes = sum(a.nbytes for a in out)
    else:
        spec = (P() if config_axis is None
                else P(*(None,) * config_axis, dist.batch_axes[0]))
        sh = NamedSharding(mesh, spec)
        out = [jax.device_put(a, sh) for a in hosts]
        nbytes = sum(int(np.prod(sh.shard_shape(a.shape))) * a.dtype.itemsize
                     for a in out) * mesh.size
    obs.counter(PLACED_BYTES[0], float(nbytes), help=PLACED_BYTES[1],
                devices=1 if mesh is None else mesh.size)
    return out, nbytes


# --------------------------------------------------------------------------- #
# Packed IR
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class PackedBucket:
    """Streams sharing one padded shape ``(K_pad, R_pad, N_pad, P_pad)``.

    All arrays are dense ``[S_b, ...]`` with per-stream validity carried
    by masks/sizes, so one compiled kernel serves the whole bucket:

    * ``lr_*``: the controller's low-activity runs (the downscale axis) —
      start offset, length, following-busy-run timestamp, valid mask and
      the trailing-run flag (a fired trailing low run never restores);
    * ``cum_res``: resident-sample prefix counts, edge-padded;
    * ``ds_cum``: downscale clip-saving prefix sums, 4 planes per stream
      (clock mode x accounting bucket), sharing the
      :meth:`StreamIR.downscale_cums` memo with the NumPy path;
    * ``pk_*``: the run table under the parking counterfactual (state
      padded ``-1`` so padded runs never match a real state);
    * ``cap_hi`` / ``cap_lo`` / ``cap_top``: sorted-power cap buckets (3
      states + the cube-law penalty bucket) as int32 order-key words,
      **front**-padded with the key of ``-inf`` so ``#{p > cap}`` stays
      exact, prefix ``top`` tables end-padded (``cap_top`` is read on the
      host only).
    """

    key: tuple[int, int, int, int]
    idx: np.ndarray                  # [S_b] positions in the packed stream list
    arrays: dict[str, np.ndarray]
    #: device copies by (name, mesh); the mesh is None on one device
    _jnp: dict[tuple, jax.Array] = dataclasses.field(default_factory=dict)

    def device(self, *names: str,
               dist: DistContext | None = None) -> list[jax.Array]:
        """Device copies of the named arrays, placed on first use and
        cached per mesh (repeat sweeps and search rounds must not re-upload
        the packed tensors; what only the host reads is never sent). Under
        a mesh every chip holds the whole of each array."""
        mesh = _mesh_of(dist)
        missing = [name for name in names if (name, mesh) not in self._jnp]
        if missing:
            with obs.span("backend.upload") as sp:
                placed, nbytes = _place([self.arrays[n] for n in missing],
                                        dist)
                self._jnp.update(((n, mesh), a)
                                 for n, a in zip(missing, placed))
                sp.set(bytes=nbytes)
        return [self._jnp[(name, mesh)] for name in names]


@dataclasses.dataclass
class PackedIR:
    """A kept-stream set packed for the JAX evaluators (see
    :func:`pack_ir`). Stream order is the IR's sorted-key order, so host
    folds over ``[S]`` axes mirror the NumPy fleet merge exactly."""

    streams: list                    # kept StreamIR objects, sorted-key order
    platforms: list[PlatformSpec]    # [S] resolved per stream
    buckets: list[PackedBucket]
    min_samples: int
    dt_s: float
    # per-stream scalars, [S]-aligned with ``streams``
    base_time: np.ndarray            # [S, 3] f8 per-state baseline seconds
    base_energy: np.ndarray          # [S, 3] f8 per-state baseline joules
    devs: np.ndarray                 # [S] i8 device ids (parking membership)
    tdp: np.ndarray                  # [S] f8
    pk_wakes: np.ndarray             # [S] i8 parking wake events
    pk_idle: np.ndarray              # [S] i8 parked/throttled samples
    # real (unpadded) sizes, for unpack and the property tests
    lr_n: np.ndarray                 # [S] low-run counts
    n_runs: np.ndarray               # [S]
    n_rows: np.ndarray               # [S]
    cap_n: np.ndarray                # [S, 4] cap-bucket sample counts
    bucket_of: np.ndarray            # [S] bucket index per stream
    pos_in_bucket: np.ndarray        # [S] row within the bucket
    # parking counterfactual tables (config-independent), filled lazily
    park_time: np.ndarray | None = None    # [S, 3] f8 seconds
    park_energy: np.ndarray | None = None  # [S, 3] f8 joules

    @property
    def n_streams(self) -> int:
        return len(self.streams)

    def unpack(self) -> list[dict[str, np.ndarray]]:
        """Per-stream real-sized views of the packed tensors (padding
        stripped) — the round-trip side of :func:`pack_ir`, property-
        tested bit-identical against the StreamIR memos."""
        out = []
        for s in range(self.n_streams):
            b = self.buckets[int(self.bucket_of[s])]
            r = int(self.pos_in_bucket[s])
            k = int(self.lr_n[s])
            nr = int(self.n_runs[s])
            n = int(self.n_rows[s])
            a = b.arrays
            caps = {}
            for j, name in enumerate((_DEEP, _EXEC, _ACTIVE, "penalty")):
                p_real = int(self.cap_n[s, j])
                p_pad = a["cap_hi"].shape[2]
                caps[name] = (key_words_to_f64(
                                  a["cap_hi"][r, j, p_pad - p_real:],
                                  a["cap_lo"][r, j, p_pad - p_real:]),
                              a["cap_top"][r, j, :p_real + 1])
            out.append({
                "lr_s0": a["lr_s0"][r, :k],
                "lr_len": a["lr_len"][r, :k],
                "lr_busy": a["lr_busy"][r, :k],
                "lr_trail": a["lr_trail"][r, :k],
                "cum_res": a["cum_res"][r, :n + 1],
                "ds_cum": a["ds_cum"][r, :, :n + 1],
                "pk_state": a["pk_state"][r, :nr],
                "pk_energy": a["pk_energy"][r, :nr],
                "pk_len": a["pk_len"][r, :nr],
                "cap_buckets": caps,
                "ts_first": a["ts_first"][r],
            })
        return out


def _platform_cache_key(platform_of) -> object:
    if platform_of is None or isinstance(platform_of, str):
        return platform_of
    return tuple(sorted(platform_of.items()))


def pack_ir(ir, min_samples: int, min_job_duration_s: float = 2 * 3600.0,
            hosts: Iterable[str] | None = None,
            platform_of: str | Mapping[int, str] | None = None,
            pad_floor: int = 8) -> PackedIR:
    """Pack a :class:`repro.whatif.ir.RunIR` for the JAX evaluators.

    Streams are duration-filtered exactly like
    :func:`repro.whatif.replay.replay_ir` and grouped into power-of-two
    padding buckets on ``(low runs, runs, rows, cap-bucket width)`` —
    each distinct bucket shape compiles once, so retraces stay O(log n)
    in the largest stream, not O(n_streams). All per-sample prefix
    structures come from the :class:`StreamIR` memos (``cum_resident``,
    ``downscale_cums``, ``cap_buckets``, ``parking_counterfactual``,
    ``baseline``), so they are *bitwise the same arrays* the NumPy
    oracle gathers from. ``pad_floor`` sets the minimum padded size per
    axis (tests raise it to force bucket merging).

    The result is cached on the ``ir`` object keyed by every argument
    that shapes it, so sweep + search rounds pack once.
    """
    cache = ir.__dict__.setdefault("_jax_packed", {})
    key = (int(min_samples), float(min_job_duration_s),
           None if hosts is None else tuple(sorted(set(hosts))),
           _platform_cache_key(platform_of), int(pad_floor))
    hit = cache.get(key)
    if hit is not None:
        return hit

    dt = float(ir.config.dt_s)
    kept = [s for s in ir.select(hosts)
            if s.ts_last - s.ts_first + dt >= min_job_duration_s]
    plat_cache: dict[int, PlatformSpec] = {}
    plats = [_resolve_platform(platform_of, plat_cache, s.platform_id)
             for s in kept]

    per_stream = []
    for s, plat in zip(kept, plats):
        off, low_flags = s.controller_runs()
        low_j = np.flatnonzero(low_flags)
        k = int(low_j.size)
        s0 = off[low_j]
        e0 = off[low_j + 1]
        trail = np.zeros(k, dtype=bool)
        if k and int(low_j[-1]) == low_flags.shape[0] - 1:
            trail[-1] = True
        planes = []
        for sm, mem in ((ClockLevel.MIN, ClockLevel.MAX),
                        (ClockLevel.MIN, ClockLevel.MIN)):
            delta = plat.exec_idle_w - plat.residency_floor_w(sm, mem)
            ce, ca = s.downscale_cums(float(delta), plat.deep_idle_w,
                                      min_samples)
            planes.extend((ce, ca))
        cap = s.cap_buckets(min_samples)
        cap_rows = [cap[_DEEP], cap[_EXEC], cap[_ACTIVE],
                    (cap["penalty"][0], cap["penalty"][2])]
        pk = s.parking_counterfactual(min_samples)
        base = s.baseline(min_samples)
        per_stream.append({
            "s0": s0, "e0": e0, "trail": trail,
            "busy": s.ts_first + dt * e0.astype(np.float64),
            "cum_res": s.cum_resident(),
            "planes": planes,
            "cap_rows": cap_rows,
            "pk_state": s.state.astype(np.int32),
            "pk_cf_state": pk["cf_state"].astype(np.int32),
            "pk_energy": pk["keep_sum"] + pk["idle_len"] * plat.deep_idle_w,
            "pk_len": s.length.astype(np.int64),
            "pk_wakes": pk["wakes"], "pk_idle": pk["idle_samples"],
            "base": base, "ts_first": float(s.ts_first),
            "sizes": (k, s.n_runs, s.n_rows,
                      max(r[0].shape[0] for r in cap_rows)),
        })

    n = len(kept)
    neg_inf_hi, neg_inf_lo = order_key_words(-np.inf)
    # bucket on the *scan* axis only (the low-run count): the downscale
    # kernel pays one sequential lax.scan step per padded low run, so
    # that axis sets both trace count and step count. The passive axes
    # (runs, rows, cap width) are merely gathered into — padding them to
    # the group max costs memory, not time — and folding them into the
    # key would explode 96 streams into dozens of kernel launches
    groups: dict[int, list[int]] = {}
    for i, d in enumerate(per_stream):
        groups.setdefault(_pow2(d["sizes"][0], pad_floor), []).append(i)

    buckets = []
    bucket_of = np.zeros(n, dtype=np.int64)
    pos_in_bucket = np.zeros(n, dtype=np.int64)
    for kp in sorted(groups):
        idx = np.array(groups[kp], dtype=np.int64)
        rp, npad, pp = (
            _pow2(max(per_stream[i]["sizes"][ax] for i in idx), pad_floor)
            for ax in (1, 2, 3))
        bk = (kp, rp, npad, pp)
        sb = idx.size
        arrays = {
            "lr_s0": np.zeros((sb, kp), np.int64),
            "lr_len": np.zeros((sb, kp), np.int64),
            "lr_busy": np.zeros((sb, kp), np.float64),
            "lr_valid": np.zeros((sb, kp), bool),
            "lr_trail": np.zeros((sb, kp), bool),
            "cum_res": np.zeros((sb, npad + 1), np.int64),
            "ds_cum": np.zeros((sb, 4, npad + 1), np.float64),
            "pk_state": np.full((sb, rp), -1, np.int32),
            "pk_energy": np.zeros((sb, rp), np.float64),
            "pk_len": np.zeros((sb, rp), np.int64),
            "cap_hi": np.full((sb, 4, pp), neg_inf_hi, np.int32),
            "cap_lo": np.full((sb, 4, pp), neg_inf_lo, np.int32),
            "cap_top": np.zeros((sb, 4, pp + 1), np.float64),
            "ts_first": np.zeros(sb, np.float64),
        }
        for r, i in enumerate(idx):
            d = per_stream[i]
            k, nr, nrow, _ = d["sizes"]
            arrays["lr_s0"][r, :k] = d["s0"]
            arrays["lr_len"][r, :k] = d["e0"] - d["s0"]
            arrays["lr_busy"][r, :k] = d["busy"]
            arrays["lr_valid"][r, :k] = True
            arrays["lr_trail"][r, :k] = d["trail"]
            arrays["cum_res"][r, :nrow + 1] = d["cum_res"]
            arrays["cum_res"][r, nrow + 1:] = d["cum_res"][-1]
            for j, plane in enumerate(d["planes"]):
                arrays["ds_cum"][r, j, :nrow + 1] = plane
                arrays["ds_cum"][r, j, nrow + 1:] = plane[-1]
            arrays["pk_state"][r, :nr] = d["pk_cf_state"]
            arrays["pk_energy"][r, :nr] = d["pk_energy"]
            arrays["pk_len"][r, :nr] = d["pk_len"]
            for j, (sp, top) in enumerate(d["cap_rows"]):
                p_real = sp.shape[0]
                (arrays["cap_hi"][r, j, pp - p_real:],
                 arrays["cap_lo"][r, j, pp - p_real:]) = order_key_words(sp)
                arrays["cap_top"][r, j, :p_real + 1] = top
                arrays["cap_top"][r, j, p_real + 1:] = top[-1]
            arrays["ts_first"][r] = d["ts_first"]
            bucket_of[i] = len(buckets)
            pos_in_bucket[i] = r
        buckets.append(PackedBucket(key=bk, idx=idx, arrays=arrays))

    if obs.enabled():
        obs.counter("repro_backend_pack_total",
                    help="pack_ir cache misses (full repacks)")
        obs.gauge("repro_backend_pack_buckets", float(len(buckets)),
                  help="padding buckets in the most recent pack")
        real = sum(d["sizes"][0] for d in per_stream)
        padded = sum(b.key[0] * b.idx.size for b in buckets)
        obs.gauge("repro_backend_pack_padding_waste_ratio",
                  1.0 - real / padded if padded else 0.0,
                  help="scan-axis cells lost to pow2 padding, most recent "
                       "pack")
        for b in buckets:
            obs.observe("repro_backend_pack_bucket_occupancy",
                        float(b.idx.size),
                        help="streams sharing one padding bucket")

    packed = PackedIR(
        streams=kept, platforms=plats, buckets=buckets,
        min_samples=int(min_samples), dt_s=dt,
        base_time=np.array([[d["base"].time_s[DeviceState(st)]
                             for st in _STATES] for d in per_stream]
                           ).reshape(n, 3),
        base_energy=np.array([[d["base"].energy_j[DeviceState(st)]
                               for st in _STATES] for d in per_stream]
                             ).reshape(n, 3),
        devs=np.array([s.key[2] for s in kept], dtype=np.int64),
        tdp=np.array([p.tdp_w for p in plats], dtype=np.float64),
        pk_wakes=np.array([d["pk_wakes"] for d in per_stream], np.int64),
        pk_idle=np.array([d["pk_idle"] for d in per_stream], np.int64),
        lr_n=np.array([d["sizes"][0] for d in per_stream], np.int64),
        n_runs=np.array([d["sizes"][1] for d in per_stream], np.int64),
        n_rows=np.array([d["sizes"][2] for d in per_stream], np.int64),
        cap_n=np.array([[r[0].shape[0] for r in d["cap_rows"]]
                        for d in per_stream], np.int64).reshape(n, 4),
        bucket_of=bucket_of, pos_in_bucket=pos_in_bucket,
    )
    cache[key] = packed
    return packed


# --------------------------------------------------------------------------- #
# jit / shard_map kernels
# --------------------------------------------------------------------------- #
def _downscale_kernel(lr_s0, lr_len, lr_busy, lr_valid, lr_trail, cum_res,
                      ds_cum, ts_first, dt, trig, y):
    """Whole-family Algorithm-1 replay over one bucket.

    The only truly sequential part of the replay is the cooldown chain —
    whether run k fires depends on the busy timestamp of the last fired
    run — so the ``lax.scan`` carries exactly that and nothing else. The
    fire test collapses to one float compare: with ``i_row = max(trig,
    searchsorted(ts[s0:e0], t_cd, "left"))`` and ``trig < len``, the run
    fires iff the cooldown expires before its last row, i.e. iff
    ``ts[e0-1] >= t_cd`` (timestamps are monotone). Everything priced off
    that decision — the trigger row, the prefix-table gathers, both
    clock-mode savings — is hoisted into vectorized ``[K, S, C]`` passes
    around the scan, where XLA:CPU runs an order of magnitude faster than
    inside a small-body scan step.

    The cooldown trigger index replicates the row path's
    ``searchsorted`` **exactly**: the crossing is float-predicted to
    within <<1 index, then resolved by a 4-probe window evaluating the
    same ``fl(ts_first + fl(dt*i))`` timestamps the host
    ``StreamIR.ts()`` reconstructs — bit-identical decisions, hence
    bit-identical event and throttle counts.

    The config axis is the family's **unique (trigger, cooldown) pairs**
    (decisions are clock-mode independent); savings come back for both
    clock modes and the host selects per config.
    """
    _mark_trace("downscale")
    s_dim = lr_s0.shape[0]
    k_dim = lr_s0.shape[1]
    c_dim = trig.shape[0]
    tsf = ts_first[:, None]
    y_row = y[None, :]

    with jax.named_scope("run_ends"):
        # carry-independent gathers, one vectorized [S, K] pass each
        e0 = lr_s0 + lr_len
        res_end = jnp.take_along_axis(cum_res, e0, axis=1)
        end4 = jnp.take_along_axis(
            ds_cum, jnp.broadcast_to(e0[:, None, :], (s_dim, 4, k_dim)),
            axis=2)
        # last-row timestamp per run, same float expression as StreamIR.ts()
        ts_last = tsf + dt * (e0 - 1).astype(jnp.float64)
        can_fire = (lr_valid.T[:, :, None]
                    & (lr_len.T[:, :, None] > trig[None, None, :]))

    with jax.named_scope("cooldown_scan"):
        def step(last_busy, xs):
            busy_k, ts_last_k, can_k = xs
            t_cd = last_busy + y_row
            fire = can_k & (ts_last_k[:, None] >= t_cd)
            return jnp.where(fire, busy_k[:, None], last_busy), (fire, t_cd)

        _, (fire, t_cd) = jax.lax.scan(
            step, jnp.full((s_dim, c_dim), -jnp.inf),
            (lr_busy.T, ts_last.T, can_fire), unroll=8)

    with jax.named_scope("trigger_rows"):
        # vectorized trigger-row resolution over the whole [K, S, C] block:
        # float-predicted crossing, clipped in float space first so the -inf
        # no-cooldown sentinel never reaches the int cast
        s0k = lr_s0.T[:, :, None]
        lnk = lr_len.T[:, :, None]
        tsf3 = ts_first[None, :, None]
        # the float prediction is within ~1e-6 of the exact crossing, so a
        # 4-probe window [floor(rel)-1, floor(rel)+2] provably contains the
        # searchsorted result (ties shift it by at most one index)
        rel = (t_cd - tsf3) / dt - s0k.astype(jnp.float64)
        lo = jnp.clip(jnp.floor(rel) - 1.0, 0.0,
                      lnk.astype(jnp.float64)).astype(jnp.int64)
        cnt = jnp.zeros((k_dim, s_dim, c_dim), jnp.int64)
        for w in range(4):
            j = (s0k + lo + w).astype(jnp.float64)
            ts_j = tsf3 + dt * j
            cnt = cnt + ((lo + w < lnk) & (ts_j < t_cd)).astype(jnp.int64)
        i_row = jnp.maximum(trig[None, None, :], lo + cnt)
        gpos = s0k + jnp.where(fire, i_row, 0)

    with jax.named_scope("prefix_gathers"):
        # one 2-D gather per prefix plane, each feeding exactly one consumer
        # chain — a single fused 5-plane gather tempts XLA:CPU into
        # duplicating the (expensive) gather into every savings fusion
        idx = jnp.transpose(gpos, (1, 0, 2)).reshape(s_dim, k_dim * c_dim)
        firesc = jnp.transpose(fire, (1, 0, 2))

        n_down = jnp.sum(fire.astype(jnp.int64), axis=0)
        n_rest = jnp.sum((fire & ~lr_trail.T[:, :, None]).astype(jnp.int64),
                         axis=0)
        g_res = jnp.take_along_axis(cum_res, idx, axis=1).reshape(
            s_dim, k_dim, c_dim)
        thr = jnp.sum(jnp.where(
            firesc, res_end[:, :, None] - g_res, 0), axis=1)

    def saved(plane):
        g = jnp.take_along_axis(ds_cum[:, plane], idx, axis=1).reshape(
            s_dim, k_dim, c_dim)
        return jnp.sum(jnp.where(
            firesc, end4[:, plane][:, :, None] - g, 0.0), axis=1)

    with jax.named_scope("savings"):
        # clocks (MIN, MAX) in planes 0-1, (MIN, MIN) in planes 2-3
        savings = [saved(plane) for plane in range(4)]
    return (n_down, n_rest, thr, *savings)


def _integrate_runs_kernel(state, energy, lengths, min_samples):
    """:meth:`BatchedStreamingIntegrator.update_runs` as one jit'd pass
    over ``[rows, runs]``: merge consecutive equal-state runs by
    ``segment_sum``, relabel short EXECUTION_IDLE merges ACTIVE, reduce
    per state. Times are exact integer sums (bit-identical to the
    streaming integrator); energies agree to summation order."""
    _mark_trace("integrate")
    with jax.named_scope("integrate_runs"):
        s_dim, r_dim = state.shape
        prev = jnp.concatenate(
            [jnp.full((s_dim, 1), -2, state.dtype), state[:, :-1]], axis=1)
        seg = jnp.cumsum((state != prev).astype(jnp.int64), axis=1) - 1
        gid = (seg + (jnp.arange(s_dim) * r_dim)[:, None]).reshape(-1)
        seg_len = jax.ops.segment_sum(lengths.reshape(-1), gid,
                                      num_segments=s_dim * r_dim)
        merged = seg_len[gid].reshape(s_dim, r_dim)
        final = jnp.where((state == _EXEC) & (merged < min_samples),
                          _ACTIVE, state)
        times = []
        energies = []
        for st in _STATES:
            m = final == st
            times.append(jnp.sum(jnp.where(m, lengths, 0), axis=1))
            energies.append(jnp.sum(jnp.where(m, energy, 0.0), axis=1))
        return jnp.stack(times, axis=1), jnp.stack(energies, axis=1)


def _powercap_kernel(cap_hi, cap_lo, caps_hi, caps_lo):
    """``k = #{p > cap}`` per (stream, bucket, config): the run-replay cap
    scan over the int32 key words of the sorted power buckets
    ``[S, 4, P]`` and of the caps ``[S, C]``. Returns int32 ``[S, 4, C]``;
    the float64 pricing off these counts runs on the host
    (:func:`_run_powercap_family`)."""
    _mark_trace("powercap")
    s_dim, n_b, p_dim = cap_hi.shape
    c_dim = caps_hi.shape[1]

    def per_row(words):
        return jnp.broadcast_to(words[:, None, :], (s_dim, n_b, c_dim)
                                ).reshape(s_dim * n_b, c_dim)

    with jax.named_scope("cap_scan"):
        return cap_bucket_counts(
            cap_hi.reshape(s_dim * n_b, p_dim),
            cap_lo.reshape(s_dim * n_b, p_dim),
            per_row(caps_hi), per_row(caps_lo)).reshape(s_dim, n_b, c_dim)


#: compiled-callable cache: (kernel name, mesh, axis) -> jitted fn.
#: Recreating jax.jit wrappers per call would retrace every call; this
#: keys compilation on the mesh identity so local and sharded variants
#: coexist.
_FN_CACHE: dict[tuple, object] = {}

_DS_STREAM_SPECS = (P(None, None),) * 5 + (P(None, None), P(None, None, None),
                                           P(None), P())
_KERNELS = {"downscale": _downscale_kernel, "powercap": _powercap_kernel,
            "integrate": _integrate_runs_kernel}


def _get_fn(name: str, dist: DistContext | None):
    dist_on = dist is not None and dist.enabled and name != "integrate"
    key = (name, dist.mesh if dist_on else None,
           dist.batch_axes[0] if dist_on else None)
    fn = _FN_CACHE.get(key)
    if fn is not None:
        return fn
    kernel = _KERNELS[name]
    if dist_on:
        ax = dist.batch_axes[0]
        if name == "downscale":
            in_specs = _DS_STREAM_SPECS + (P(ax),) * 2
            out_specs = (P(None, ax),) * 7
        else:
            in_specs = (P(None, None, None),) * 2 + (P(None, ax),) * 2
            out_specs = P(None, None, ax)
        kernel = shard_map(kernel, mesh=dist.mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
    fn = _FN_CACHE[key] = jax.jit(kernel)
    return fn


def _config_pad(n: int, dist: DistContext | None, family: str,
                floor: int = 8) -> int:
    """Pad the config axis to a power of two (>= ``floor``) so search
    rounds with drifting candidate counts reuse compilations, rounded up
    to the mesh axis size (shard_map needs exact divisibility — same
    rule as :mod:`repro.distributed.sharding`). The lanes added are
    counted per ``family`` (``repro_backend_pad_configs_total``)."""
    c = _pow2(n, floor)
    if dist is not None and dist.enabled:
        ax = int(dist.mesh.shape[dist.batch_axes[0]])
        c = ((c + ax - 1) // ax) * ax
    obs.counter(PAD_CONFIGS[0], float(c - n), help=PAD_CONFIGS[1],
                family=family)
    return c


def _pad_cols(a: np.ndarray, c_pad: int, fill) -> np.ndarray:
    out = np.full(a.shape[:-1] + (c_pad,), fill, dtype=a.dtype)
    out[..., :a.shape[-1]] = a
    return out


def _launch(fn, program: str, bucket: int, *args) -> list[np.ndarray]:
    """One call of a jit program, the wait for its outputs and their copy
    to the host, each under its own span (``backend.launch``,
    ``backend.wait``, ``backend.fetch``). Waiting before the copy only
    splits the device's time from the transfer: results and the order of
    work are those of a plain ``np.asarray`` of the outputs."""
    with obs.span("backend.launch", program=program, bucket=bucket):
        res = fn(*args)
    with obs.span("backend.wait", program=program):
        jax.block_until_ready(res)
    with obs.span("backend.fetch") as sp:
        out = [np.asarray(a)
               for a in (res if isinstance(res, tuple) else (res,))]
        sp.set(bytes=sum(a.nbytes for a in out))
    return out


# --------------------------------------------------------------------------- #
# Public integrator port
# --------------------------------------------------------------------------- #
def jax_integrate_runs(states: np.ndarray, energy: np.ndarray,
                       lengths: np.ndarray, min_samples: int,
                       dt_s: float = 1.0) -> list[EnergyBreakdown]:
    """Drop-in port of :func:`repro.core.energy.integrate_runs` on JAX:
    per-state times bit-identical, energies <= 1e-9 relative."""
    energy = np.asarray(energy, dtype=np.float64)
    if energy.ndim == 1:
        energy = energy[None, :]
    c, r = energy.shape
    with jax.enable_x64():
        fn = _get_fn("integrate", None)
        args, _ = _place([
            np.broadcast_to(np.asarray(states, np.int32)[None, :], (c, r)),
            energy,
            np.broadcast_to(np.asarray(lengths, np.int64)[None, :], (c, r)),
            np.int64(min_samples)])
        t, e = fn(*args)
        t = np.asarray(t)
        e = np.asarray(e)
    return [
        EnergyBreakdown(
            time_s={DeviceState(st): float(t[i, j] * dt_s)
                    for j, st in enumerate(_STATES)},
            energy_j={DeviceState(st): float(e[i, j] * dt_s)
                      for j, st in enumerate(_STATES)})
        for i in range(c)
    ]


# --------------------------------------------------------------------------- #
# Family evaluators (fill [S, C_family] blocks)
# --------------------------------------------------------------------------- #
def _price_rows(policies, platforms) -> np.ndarray:
    """[S, C] per-event prices: ``event_penalty_s`` per distinct platform."""
    rows: dict[str, np.ndarray] = {}
    out = np.empty((len(platforms), len(policies)))
    for i, plat in enumerate(platforms):
        row = rows.get(plat.name)
        if row is None:
            row = rows[plat.name] = np.array(
                [p.event_penalty_s(plat) for p in policies])
        out[i] = row
    return out


def _parked_mask(pools, devs: np.ndarray) -> np.ndarray:
    """[S, C] bool — is each stream's device outside each pool config's
    active set (``device_id % n_devices not in active_set``)?"""
    out = np.empty((devs.shape[0], len(pools)), dtype=bool)
    for c, (nd, act) in enumerate(pools):
        out[:, c] = ~np.isin(devs % nd, list(act))
    return out


def _run_downscale_family(packed: PackedIR, batch, dist):
    """Run the downscale kernel over every bucket; returns
    ``(n_down, n_rest, throttled, sav_exec, sav_act)`` as [S, C] host
    arrays (savings in W·samples, exactly the NumPy kernel's units).

    The kernel's config axis is the family's unique (trigger, cooldown)
    pairs — the decision sequence is clock-mode independent, so a dense
    x/y grid swept at both clock modes replays each pair once. The
    kernel prices both modes; this expands pairs back to configs and
    selects the mode's savings planes."""
    with obs.span("backend.upload") as sp:
        mode_lo = np.array(
            [p._min_clocks() == (ClockLevel.MIN, ClockLevel.MIN)
             for p in batch.policies], dtype=bool)
        pair_key = np.stack(
            [np.asarray(batch._trig, np.float64), np.asarray(batch._y)],
            axis=1)
        _, uniq_idx, pair_of_c = np.unique(
            pair_key, axis=0, return_index=True, return_inverse=True)
        pair_of_c = pair_of_c.reshape(-1)
        p_real = uniq_idx.shape[0]
        p_pad = _config_pad(p_real, dist, "downscale")
        (trig, y), cfg_bytes = _place(
            [_pad_cols(batch._trig[uniq_idx], p_pad, _NEVER_TRIGGERS),
             _pad_cols(batch._y[uniq_idx], p_pad, 0.0)], dist, config_axis=0)
        (dt,), dt_bytes = _place([np.float64(packed.dt_s)], dist)
        sp.set(bytes=cfg_bytes + dt_bytes)
    s = packed.n_streams
    outs = [np.zeros((s, p_real), np.int64) for _ in range(3)] + \
           [np.zeros((s, p_real)) for _ in range(4)]
    fn = _get_fn("downscale", dist)
    for b, bucket in enumerate(packed.buckets):
        res = _launch(fn, "downscale", b,
                      *bucket.device("lr_s0", "lr_len", "lr_busy",
                                     "lr_valid", "lr_trail", "cum_res",
                                     "ds_cum", "ts_first", dist=dist),
                      dt, trig, y)
        for dst, arr in zip(outs, res):
            dst[bucket.idx] = arr[:, :p_real]
    with obs.span("backend.expand", family="downscale"):
        nd, nr, th, se_hi, sa_hi, se_lo, sa_lo = outs
        sel = mode_lo[None, :]
        return [nd[:, pair_of_c], nr[:, pair_of_c], th[:, pair_of_c],
                np.where(sel, se_lo[:, pair_of_c], se_hi[:, pair_of_c]),
                np.where(sel, sa_lo[:, pair_of_c], sa_hi[:, pair_of_c])]


def _park_tables(packed: PackedIR) -> tuple[np.ndarray, np.ndarray]:
    """Config-independent parked counterfactual per stream: the
    integrator port over the pre-priced parking run tables. Cached on
    the packed IR — every parking/composite family and round shares it."""
    if packed.park_time is None:
        s = packed.n_streams
        t_out = np.zeros((s, 3))
        e_out = np.zeros((s, 3))
        fn = _get_fn("integrate", None)
        (ms,), _ = _place([np.int64(packed.min_samples)])
        for b, bucket in enumerate(packed.buckets):
            t, e = _launch(fn, "integrate", b,
                           *bucket.device("pk_state", "pk_energy", "pk_len"),
                           ms)
            t_out[bucket.idx] = t * packed.dt_s
            e_out[bucket.idx] = e * packed.dt_s
        packed.park_time = t_out
        packed.park_energy = e_out
    return packed.park_time, packed.park_energy


def _run_powercap_family(packed: PackedIR, batch, dist, dt: float):
    """Cap kernel over every bucket: ``(energy_cf [S,3,C], penalty
    [S,C], throttled [S,C])``. Caps are host-built per stream platform
    (``frac * tdp_w``, same floats as NumPy). The device counts
    ``k = #{p > cap}``, which is exact; the host gathers the prefix sums
    at ``k`` and prices them in float64 with
    :meth:`PowerCapBatch.apply_runs`'s own expressions, because a TPU
    emulates float64 and its emulated divide can return a float32
    quotient."""
    c_real = len(batch.policies)
    with obs.span("backend.upload") as sp:
        c_pad = _config_pad(c_real, dist, "powercap")
        caps = batch._fracs[None, :] * packed.tdp[:, None]
        # padded lanes get the largest key: no sample is above them
        caps_hi, caps_lo = order_key_words(_pad_cols(caps, c_pad, np.inf))
        placed = [_place([caps_hi[bucket.idx], caps_lo[bucket.idx]], dist,
                         config_axis=1) for bucket in packed.buckets]
        caps_dev = [words for words, _ in placed]
        sp.set(bytes=sum(nbytes for _, nbytes in placed))
    k = np.zeros((packed.n_streams, 4, c_real), np.int64)
    fn = _get_fn("powercap", dist)
    for b, (bucket, caps_b) in enumerate(zip(packed.buckets, caps_dev)):
        (k_b,) = _launch(fn, "powercap", b,
                         *bucket.device("cap_hi", "cap_lo", dist=dist),
                         *caps_b)
        k[bucket.idx] = k_b[:, :, :c_real]
    with obs.span("backend.cap_pricing"):
        top_at = np.zeros((packed.n_streams, 4, c_real))
        for bucket in packed.buckets:
            top_at[bucket.idx] = np.take_along_axis(
                bucket.arrays["cap_top"], k[bucket.idx], axis=2)
        e_cf = packed.base_energy[:, :, None] - (
            top_at[:, :3, :] - k[:, :3, :] * caps[:, None, :]) * dt
        pen = dt * (top_at[:, 3, :] / np.cbrt(caps) - k[:, 3, :])
        thr = k[:, 0, :] + k[:, 1, :] + k[:, 2, :]
    return e_cf, pen, thr


# --------------------------------------------------------------------------- #
# The backend's replay entry point
# --------------------------------------------------------------------------- #
def replay_ir_outcomes(
    ir,
    policies: Sequence,
    platform_of: str | Mapping[int, str] | None = None,
    min_job_duration_s: float = 2 * 3600.0,
    min_interval_s: float | None = 5.0,
    classifier: ClassifierConfig = DEFAULT_CLASSIFIER,
    dt_s: float = 1.0,
    hosts: Iterable[str] | None = None,
    dist: DistContext | None = None,
    pad_floor: int = 8,
) -> tuple[list[PolicyOutcome], int, int]:
    """Replay a policy grid against a :class:`RunIR` on the JAX backend.

    The device-side counterpart of :func:`repro.whatif.replay.replay_ir`
    + :func:`repro.whatif.sweep._outcome` fused: family kernels produce
    ``[n_streams, n_configs]`` counts/savings on device, and the fleet
    assembly on the host replays the NumPy reduction *order* (vectorized
    axis-0 left folds over sorted streams), so time/count metrics are
    bit-identical and energies/penalties <= 1e-9 relative. Every
    policy must be IR-capable (:func:`repro.whatif.ir.ir_supported`) —
    the sweep kernel routes anything else through the row path.

    ``dist`` shards the config axis over a mesh from
    :func:`config_mesh`; results hold the NumPy contract for every mesh
    shape.
    Returns ``(outcomes in grid order, n_rows, n_runs)``.
    """
    if classifier != ir.config.classifier:
        raise ValueError(
            f"IR was built for classifier {ir.config.classifier}, replay "
            f"requested {classifier}; rebuild the IR or use compact=False")
    if dt_s != ir.config.dt_s:
        raise ValueError(f"IR dt_s {ir.config.dt_s} != replay dt_s {dt_s}")
    policies = list(policies)
    min_samples = (0 if min_interval_s is None
                   else int(np.ceil(min_interval_s / dt_s)))
    selected = ir.select(hosts)
    n_rows = sum(s.n_rows for s in selected)
    n_runs = sum(s.n_runs for s in selected)
    n_cfg = len(policies)
    if n_cfg == 0:
        return [], n_rows, n_runs

    with obs.span("backend.pack", streams=len(selected)):
        packed = pack_ir(ir, min_samples,
                         min_job_duration_s=min_job_duration_s,
                         hosts=hosts, platform_of=platform_of,
                         pad_floor=pad_floor)
    s = packed.n_streams
    dt = dt_s

    if obs.enabled():
        mesh = _mesh_of(dist)
        obs.gauge("repro_backend_devices",
                  float(1 if mesh is None else mesh.size),
                  help="devices the config axis runs over (mesh size when "
                       "sharded, 1 otherwise)")
        for name, help_text in (PLACED_BYTES, PAD_CONFIGS):
            obs.REGISTRY.counter(name, help_text)

    # per-(stream, config) accumulators, initialised to the baseline
    cf_time = np.repeat(packed.base_time[:, :, None], n_cfg, axis=2)
    cf_energy = np.repeat(packed.base_energy[:, :, None], n_cfg, axis=2)
    pen = np.zeros((s, n_cfg))
    wakes = np.zeros((s, n_cfg), np.int64)
    downs = np.zeros((s, n_cfg), np.int64)
    thr = np.zeros((s, n_cfg), np.int64)

    # children of the kernels span: backend.upload, backend.launch,
    # backend.wait, backend.fetch, backend.cap_pricing and backend.expand
    with obs.span("backend.kernels", configs=n_cfg, streams=s), \
         jax.enable_x64():
        for batch, idxs in make_batches(policies):
            ci = np.asarray(idxs, dtype=np.int64)
            if isinstance(batch, NoOpBatch):
                continue
            if isinstance(batch, DownscaleBatch):
                nd, nr, th, se, sa = _run_downscale_family(
                    packed, batch, dist)
                with obs.span("backend.expand", family="downscale"):
                    cf_energy[:, 1, ci] = (packed.base_energy[:, 1:2]
                                           - se * dt)
                    cf_energy[:, 2, ci] = (packed.base_energy[:, 2:3]
                                           - sa * dt)
                    pen[:, ci] = nr * _price_rows(batch.policies,
                                                  packed.platforms)
                    wakes[:, ci] = nr
                    downs[:, ci] = nd
                    thr[:, ci] = th
            elif isinstance(batch, ParkingBatch):
                pt, pe = _park_tables(packed)
                with obs.span("backend.expand", family="parking"):
                    mask = _parked_mask(batch._pools, packed.devs)
                    m3 = mask[:, None, :]
                    cf_time[:, :, ci] = np.where(
                        m3, pt[:, :, None], packed.base_time[:, :, None])
                    cf_energy[:, :, ci] = np.where(
                        m3, pe[:, :, None], packed.base_energy[:, :, None])
                    wk = np.where(mask, packed.pk_wakes[:, None], 0)
                    wakes[:, ci] = wk
                    thr[:, ci] = np.where(mask, packed.pk_idle[:, None], 0)
                    pen[:, ci] = wk * np.array(
                        [p.resume_latency_s for p in batch.policies])[None, :]
            elif isinstance(batch, PowerCapBatch):
                e_cf, p_cap, th = _run_powercap_family(
                    packed, batch, dist, dt)
                with obs.span("backend.expand", family="powercap"):
                    cf_energy[:, :, ci] = e_cf
                    pen[:, ci] = p_cap
                    thr[:, ci] = th
            elif isinstance(batch, CompositeBatch):
                if not batch._ir_ok:
                    raise ValueError(
                        "run-level replay supports only parking+downscale "
                        "composites; route this batch through the row path")
                nd, nr, th_ds, se, sa = _run_downscale_family(
                    packed, batch._ds_batch, dist)
                pt, pe = _park_tables(packed)
                with obs.span("backend.expand", family="composite"):
                    mask = _parked_mask(batch._park_pools, packed.devs)
                    m3 = mask[:, None, :]
                    ds_e = np.repeat(packed.base_energy[:, :, None],
                                     len(idxs), axis=2)
                    ds_e[:, 1, :] -= se * dt
                    ds_e[:, 2, :] -= sa * dt
                    cf_time[:, :, ci] = np.where(
                        m3, pt[:, :, None], packed.base_time[:, :, None])
                    cf_energy[:, :, ci] = np.where(m3, pe[:, :, None], ds_e)
                    wk = np.where(mask, packed.pk_wakes[:, None], 0)
                    wakes[:, ci] = wk + nr
                    downs[:, ci] = nd
                    thr[:, ci] = np.where(mask, packed.pk_idle[:, None],
                                          th_ds)
                    price_park = np.array(
                        [p.parts[0].resume_latency_s
                         for p in batch.policies])
                    price_ds = _price_rows(
                        [p.parts[1] for p in batch.policies],
                        packed.platforms)
                    # matches price_events' per-channel left fold:
                    # fl(fl(wakes*price0) + fl(restores*price1))
                    pen[:, ci] = wk * price_park[None, :] + nr * price_ds
            else:
                raise ValueError(
                    f"jax backend supports only IR-capable policy families, "
                    f"got {type(batch).__name__}")

    # ---- fleet assembly: replicate the NumPy reduction order ---------- #
    # merge() is a per-state left fold over jobs in sorted-stream order.
    # ``np.sum`` over the outer axis of a C-order array reduces one
    # stream-row at a time — the same left fold, so times stay bitwise
    # identical to the explicit per-stream loop this replaces. Penalties
    # use the same axis-0 fold (all terms non-negative, so the naive sum
    # sits well inside the <= 1e-9 oracle tolerance fsum used to meet).
    with obs.span("backend.assembly", configs=n_cfg, streams=s):
        fleet_t = cf_time.sum(axis=0)
        fleet_e = cf_energy.sum(axis=0)
        fleet_bt = packed.base_time.sum(axis=0)
        fleet_be = packed.base_energy.sum(axis=0)

        def _total(per_state):
            # EnergyBreakdown.total_energy_j: left fold over DeviceState order
            tot = np.zeros(per_state.shape[1:])
            for j in range(3):
                tot = tot + per_state[j]
            return tot

        base_tot = float(_total(fleet_be[:, None])[0]) if s else 0.0
        cf_tot = _total(fleet_e)
        penalty_s = pen.sum(axis=0)
        wake_tot = wakes.sum(axis=0)
        down_tot = downs.sum(axis=0)
        thr_tot = thr.sum(axis=0)

        jb_tot = _total(np.swapaxes(packed.base_energy, 0, 1))    # [S]
        jc_tot = _total(np.swapaxes(cf_energy, 0, 1))             # [S, C]
        with np.errstate(invalid="ignore", divide="ignore"):
            jb_col = jb_tot[:, None]
            saved_jobs = np.where(jb_col != 0.0,
                                  (jb_col - jc_tot) / jb_col, 0.0)
        # one transpose+tolist per CDF instead of a Python float() loop
        # per (config, stream) cell — same float64 values either way
        saved_rows = np.sort(saved_jobs, axis=0).T.tolist()       # [C][S]
        pen_rows = np.sort(pen, axis=0).T.tolist()                # [C][S]

        active_t = float(fleet_bt[2]) if s else 0.0
        base_exec_den = float(fleet_be[1] + fleet_be[2]) if s else 0.0
        base_exec_frac = (float(fleet_be[1]) / base_exec_den
                          if base_exec_den else 0.0)
        cf_exec_den = fleet_e[1] + fleet_e[2]

        outcomes = []
        for c, pol in enumerate(policies):
            cf_total = float(cf_tot[c])
            saved = base_tot - cf_total
            p_s = float(penalty_s[c])
            outcomes.append(PolicyOutcome(
                name=pol.name,
                params=pol.describe(),
                n_jobs=s,
                baseline_energy_j=base_tot,
                counterfactual_energy_j=cf_total,
                energy_saved_j=saved,
                saved_fraction=saved / base_tot if base_tot else 0.0,
                penalty_s=p_s,
                penalty_fraction=p_s / active_t if active_t else 0.0,
                wake_events=int(wake_tot[c]),
                downscale_events=int(down_tot[c]),
                throttled_time_s=float(int(thr_tot[c]) * dt),
                exec_idle_energy_fraction_baseline=base_exec_frac,
                exec_idle_energy_fraction_cf=(
                    float(fleet_e[1, c]) / float(cf_exec_den[c])
                    if s and cf_exec_den[c] else 0.0),
                per_job_saved_fraction=tuple(saved_rows[c]),
                per_job_penalty_s=tuple(pen_rows[c]),
            ))
    return outcomes, n_rows, n_runs
