"""The config axis of a sweep split over a mesh of four host devices
(``dist=config_mesh(4)``), as a four-chip host runs it.

Covered: the sweep and its knee against the NumPy oracle and against the
plain reference of ``bench/reference.py`` (times, counts, Pareto flags and
the knee exact, energies and penalties within 1e-9); a repeat bit for bit;
the packed tables placed once per mesh, whole on every device, so a
second sweep places only its config-axis arrays, the same bytes on two
fleet sizes; a one-device sweep beside it on the same IR; the config
lanes ``_config_pad`` adds, counted per family; and a downscale family
padded to whole lane tiles rather than a power of two, exact on one
device and on the mesh.
"""
import pathlib
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import NamedSharding, PartitionSpec as P

import repro.obs as obs
from repro.cluster import generate_cluster
from repro.telemetry import TelemetryStore
from repro.whatif import get_ir, ir_config_for, run_sweep
from repro.whatif import backend as B
from repro.whatif.search import find_knee

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference as R  # noqa: E402
from bench.grids import common, grid200  # noqa: E402
from bench.harness import Compare  # noqa: E402
from bench.policies import build  # noqa: E402

#: the reference's semantics of the replay the sweeps below run
SEMANTICS = {"dt_s": 1.0, "min_interval_s": 5.0, "min_job_duration_s": 0.0,
             "activity_pct": 5.0, "link_gbs": 1.0}
TABLES = ("lr_s0", "lr_len", "lr_busy", "lr_valid", "lr_trail", "cum_res",
          "ds_cum", "ts_first", "cap_hi", "cap_lo")
#: three downscale pairs (each at both clock modes) and seven power caps
SMALL = ([common.noop()]
         + [common.downscale(x, y, mode) for x, y in
            ((1.0, 2.0), (3.0, 5.0), (8.0, 10.0))
            for mode in ("sm_only", "sm_and_mem")]
         + [common.powercap(f) for f in
            (0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9)])
#: the same without caps: every config-axis array is then independent of
#: the fleet's size
NO_CAPS = [s for s in SMALL if s["policy"] != "powercap"]
#: 17 triggers x 18 cooldowns, each pair at both clock modes: 306 distinct
#: downscale pairs, which pad to 384 lanes on one device (512 as a power
#: of two)
LANE_TILES = ([common.noop()]
              + [common.downscale(float(x), float(y), mode)
                 for x in range(1, 18) for y in range(2, 20)
                 for mode in ("sm_only", "sm_and_mem")])


class Fleet:
    def __init__(self, root, specs):
        self.root = root
        self.store = TelemetryStore(root)
        self.specs = specs
        self.policies = [build(s) for s in specs]
        self.ir = get_ir(self.store, ir_config_for(self.policies, dt_s=1.0))

    def sweep(self, dist, specs=None):
        policies = (self.policies if specs is None
                    else [build(s) for s in specs])
        front = run_sweep(self.store, policies, ir=self.ir, backend="jax",
                          dist=dist, min_job_duration_s=0.0)
        return front, find_knee(front.outcomes).params


def _make(tmp_path_factory, n_devices, seed, horizon_s=1500):
    root = tmp_path_factory.mktemp(f"fleet{n_devices}_")
    generate_cluster(n_devices=n_devices, horizon_s=horizon_s, seed=seed,
                     store=TelemetryStore(root, shard_format="npy_dir"),
                     shard_s=500)
    return root


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return Fleet(_make(tmp_path_factory, 8, 7), grid200.specs())


@pytest.fixture(scope="module")
def tiled(tmp_path_factory):
    """A fleet on which the 306 pairs' events, throttled times and savings
    differ (on ``small`` every downscale config has the same events and
    no throttled time)."""
    return Fleet(_make(tmp_path_factory, 6, 7, horizon_s=3600), LANE_TILES)


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices (tests/conftest.py sets them)")
    return B.config_mesh(4)


@pytest.fixture()
def counted():
    prev = obs.enabled()
    obs.enable()
    yield
    if not prev:
        obs.disable()


def _placed():
    return obs.REGISTRY.total(B.PLACED_BYTES[0])


def _padded(**labels):
    return obs.REGISTRY.total(B.PAD_CONFIGS[0], **labels)


def _reference(fleet):
    ref = R.outcomes(R.Fleet(R.read_store(fleet.root), SEMANTICS),
                     fleet.specs)
    flags = R.pareto([o["energy_saved_j"] for o in ref],
                     [o["penalty_s"] for o in ref])
    return ref, flags, R.knee(ref)


def _assert_matches(front, knee, oracle, ref=None):
    oracle_knee = find_knee(oracle.outcomes).params
    assert knee == oracle_knee
    for a, b in zip(front.outcomes, oracle.outcomes, strict=True):
        for f in ("name", "params", "n_jobs", "wake_events",
                  "downscale_events", "throttled_time_s", "pareto"):
            assert getattr(a, f) == getattr(b, f), (a.params, f)
        for f in ("baseline_energy_j", "counterfactual_energy_j",
                  "energy_saved_j", "penalty_s", "saved_fraction"):
            assert np.isclose(getattr(a, f), getattr(b, f), rtol=1e-9,
                              atol=1e-9), (a.params, f)
    if ref is None:
        return
    cmp = Compare()
    cmp.frontier(front.outcomes, knee, *ref, "mesh sweep")
    assert cmp.mismatches == 0, cmp.notes
    assert cmp.rel_err <= 1e-9, cmp.worst
    assert cmp.compared == len(front.outcomes)


def test_mesh_sweep_matches_oracle_and_reference(small, mesh, counted):
    oracle = run_sweep(small.store, small.policies, ir=small.ir,
                       backend="numpy", min_job_duration_s=0.0)
    jax_before = obs.REGISTRY.total("repro_replay_configs_total", path="jax")
    fallbacks = obs.REGISTRY.total("repro_fallbacks_total")
    front, knee = small.sweep(mesh)
    assert obs.REGISTRY.total("repro_replay_configs_total", path="jax") > \
        jax_before
    assert obs.REGISTRY.total("repro_fallbacks_total") == fallbacks
    _assert_matches(front, knee, oracle, _reference(small))
    assert obs.REGISTRY.total("repro_backend_devices") == 4
    # a repeat gives the first answer bit for bit
    again, knee_again = small.sweep(mesh)
    assert again.outcomes == front.outcomes and knee_again == knee


def test_resident_tables_are_replicated_on_the_mesh(small, mesh, counted):
    small.sweep(mesh)
    placed = _placed()
    for bucket in B.pack_ir(small.ir, 5, 0.0).buckets:
        for arr in bucket.device(*TABLES, dist=mesh):
            assert arr.sharding.is_equivalent_to(
                NamedSharding(mesh, P()), arr.ndim)
            assert arr.sharding.device_set == set(mesh.devices.flat)
            assert arr.is_fully_replicated
    # every table was already there: nothing more was placed
    assert _placed() == placed


@pytest.mark.parametrize("n_devices, seed", [(6, 3), (12, 11)])
def test_second_mesh_sweep_places_only_config_arrays(tmp_path_factory, mesh,
                                                     counted, n_devices,
                                                     seed):
    """The first mesh sweep places the fleet's tables on four devices;
    the second only its config-axis arrays: 8 lanes of trigger and
    cooldown (8 bytes each, split over the mesh) and the replicated time
    step, whatever the fleet's size."""
    fleet = Fleet(_make(tmp_path_factory, n_devices, seed), NO_CAPS)
    p0 = _placed()
    fleet.sweep(mesh)
    first = _placed() - p0
    fleet.sweep(mesh)
    second = _placed() - p0 - first
    assert second == 2 * 8 * 8 + 8 * 4
    packed = B.pack_ir(fleet.ir, 5, 0.0)
    tables = sum(bucket.arrays[n].nbytes for bucket in packed.buckets
                 for n in TABLES[:8])
    assert first == second + 4 * tables
    # with caps the config axis also carries each stream's two cap words
    fleet.sweep(mesh, SMALL)
    p1 = _placed()
    fleet.sweep(mesh, SMALL)
    assert _placed() - p1 == second + 2 * 4 * packed.n_streams * 8


def test_one_device_sweep_after_mesh_sweep(small, mesh, counted):
    oracle = run_sweep(small.store, small.policies, ir=small.ir,
                       backend="numpy", min_job_duration_s=0.0)
    small.sweep(mesh)
    front, knee = small.sweep(None)
    _assert_matches(front, knee, oracle, _reference(small))
    assert obs.REGISTRY.total("repro_backend_devices") == 1
    (arr,) = B.pack_ir(small.ir, 5, 0.0).buckets[0].device("lr_s0")
    assert len(arr.sharding.device_set) == 1


@pytest.mark.parametrize("devices, caps, pairs", [
    (None, 8 - 7, 8 - 3),
    (4, 8 - 7, 8 - 3),
    (3, 9 - 7, 9 - 3),
])
def test_pad_configs_count_the_lanes_added(small, mesh, counted, devices,
                                           caps, pairs):
    """7 caps and 3 downscale pairs pad to a power of two (at least 8),
    then up to a multiple of the mesh's devices."""
    dist = None if devices is None else B.config_mesh(devices)
    before = {f: _padded(family=f) for f in ("powercap", "downscale")}
    small.sweep(dist, SMALL)
    assert _padded(family="powercap") - before["powercap"] == caps
    assert _padded(family="downscale") - before["downscale"] == pairs


@pytest.mark.parametrize("n, devices, lanes", [
    (3, None, 8), (64, None, 64), (114, None, 128), (129, None, 256),
    (300, None, 384), (544, None, 640), (7901, None, 7936),
    (544, 4, 1024), (7901, 4, 8192), (3, 3, 9),
])
def test_config_pad_takes_pow2_or_whole_lane_tiles(mesh, counted, n, devices,
                                                   lanes):
    """The smaller of a power of two (at least 8) and whole 128-lane tiles
    on every shard, each a multiple of the mesh's devices."""
    dist = None if devices is None else B.config_mesh(devices)
    before = _padded(family="downscale")
    assert B._config_pad(n, dist, "downscale") == lanes
    assert _padded(family="downscale") - before == lanes - n


@pytest.mark.parametrize("devices, lanes", [(None, 384), (4, 512)])
def test_sweep_exact_at_lane_tile_pad(tiled, mesh, counted, devices, lanes):
    """306 pairs run as 384 lanes on one device and 512 on the mesh; both
    give the NumPy oracle's times, counts, flags and knee exactly. Three
    of the flags rest on penalties that tie: they hold only because the
    fleet's penalties are summed exactly, as the oracle sums them."""
    dist = None if devices is None else mesh
    oracle = run_sweep(tiled.store, tiled.policies, ir=tiled.ir,
                       backend="numpy", min_job_duration_s=0.0)
    before = _padded(family="downscale")
    front, knee = tiled.sweep(dist)
    assert _padded(family="downscale") - before == lanes - 306
    _assert_matches(front, knee, oracle)


def test_counters_are_declared_before_first_use(small, counted):
    small.sweep(None, [common.noop()])
    for name, _ in (B.PLACED_BYTES, B.PAD_CONFIGS):
        assert obs.REGISTRY.family(name).metrics[()].value == 0.0
