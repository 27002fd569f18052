"""The benchmark's files: every name in BENCHMARK.json resolves to its own
file, the pinned traffic equals the program's definitions, the input pins
catch a changed fleet, a run without a TPU prints no result, and a new
traffic file runs without an edit to any file that is there."""
import json
import pathlib

import pytest

from bench_copy import ROOT, edit, run_cell, small_copy

from bench.harness import load_cell, load_json, load_module
from bench.work import cap_scan_work, peaks

SPEC = load_json(ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_load_by_name(cell):
    c = load_cell(ROOT, cell, seed=1, seconds=1, trace=False)
    driver = c.module("drivers", c.traffic["kind"])
    assert all(hasattr(driver, f) for f in ("setup", "window", "check"))
    assert c.module("generators", c.config["generator"]).build
    assert {m["name"] for m in c.end_to_end()} >= {"setup_s"}
    assert len(c.end_to_end()) >= 2 and c.per_layer()
    for m in c.per_layer():
        assert m["moves"] in {e["name"] for e in c.end_to_end()}


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_metric_reader_reads_nothing_from_nothing(metric):
    reader = load_module(ROOT / "bench" / "metrics" / f"{metric}.py")
    assert reader.read({"spans": [], "counts": {}, "trace": None}) is None


def test_configurations_state_their_cuts():
    for c in SPEC["configs"]:
        conf = load_json(ROOT / c["file"])
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert set(c["reduced"]) <= set(conf["fleet"])


def test_pinned_grids_equal_the_program_grids():
    from benchmarks.whatif_bench import _grid_10k
    from repro.whatif import default_policy_grid
    from bench.grids import grid10k, grid200
    from bench.policies import build

    for pinned, program in ((grid200.specs(), default_policy_grid()),
                            (grid10k.specs(), _grid_10k())):
        assert pinned == [p.describe() for p in program]
        assert [build(s) for s in pinned[:300]] == program[:300]


def test_peaks_table():
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_cap_scan_work_hand_count():
    # 12 rows -> 16; 200 samples -> 256 lanes; 600 caps -> 640 -> two
    # 512-cap blocks = 1024
    w = cap_scan_work(12, 200, 600)
    assert w["compares"] == 16 * 256 * 1024
    assert w["bytes"] == 4 * (2 * 16 * 256 + 2 * 16 * 1024 + 16 * 1024)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return small_copy(tmp_path_factory.mktemp("bench"))


def test_refuses_without_a_tpu(copy):
    rc, res, err = run_cell(copy, "--workload", "acad192.grid200", "--seed",
                            "1", "--seconds", "1", require_tpu=True)
    assert rc != 0 and res is None
    assert "no TPU" in err


def test_pins_catch_a_changed_fleet(copy, tmp_path):
    """A fleet that the generator no longer makes as pinned (another
    simulation, another row count) stops the run before a result."""
    moved = small_copy(tmp_path / "moved")
    acad = moved / "bench" / "configs" / "acad192_3h.json"
    for change, says in (
            (lambda d: d["fleet"].update(data_seed=4), "pinned sha256"),
            (lambda d: d["pin"].update(rows=d["pin"]["rows"] + 1), "pins")):
        before = acad.read_text()
        edit(acad, change)
        rc, res, err = run_cell(moved, "--workload", "acad192.grid200",
                                "--seed", "1", "--seconds", "1")
        assert rc != 0 and res is None and says in err, err
        acad.write_text(before)


def test_new_traffic_file_runs_without_editing_a_file(copy, tmp_path):
    extra = small_copy(tmp_path / "extra")
    before = {p: p.read_bytes() for p in (extra / "bench").rglob("*")
              if p.is_file()}
    (extra / "bench" / "traffic" / "dummy.json").write_text(json.dumps(
        {"kind": "sweep", "grid": "grid200", "loop": "closed"}))
    edit(extra / "BENCHMARK.json", lambda d: d["workloads"].append(
        {"name": "acad64.dummy", "config": "acad64_3h", "traffic": "dummy",
         "chips": 1, "why": "a mix added as data"}))
    edit(extra / "BENCHMARK.json", lambda d: [
        m["workloads"].append("acad64.dummy") for m in d["end_to_end"]
        + d["per_layer"] if "acad192.grid200" in m.get("workloads", ())])
    rc, res, err = run_cell(extra, "--workload", "acad64.dummy", "--seed",
                            "2", "--seconds", "1")
    assert rc == 0, err
    assert res["correct"]
    assert set(res["metrics"]) == {"configs_per_s", "sweep_p95_s", "setup_s"}
    assert all(p.read_bytes() == b for p, b in before.items())


def test_no_result_from_the_benchmark_files_alone(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has no system to measure: the run fails and prints no result."""
    import os
    import subprocess
    import sys

    alone = small_copy(tmp_path / "alone")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            "sys.exit(run.main(['--workload', 'acad192.grid200', '--seed', "
            "'1', '--seconds', '1'], require_tpu=False))")
    assert sorted(x.name for x in alone.iterdir()) == ["BENCHMARK.json",
                                                       "bench"]
    p = subprocess.run([sys.executable, "-c", code], cwd=alone, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "repro" in p.stderr


def test_every_seed_replays_the_same_fleet_in_another_order(copy, tmp_path):
    """The seed draws the order of the devices, hosts and jobs, not the
    fleet: every seed gives the same streams, and so the same shapes on
    the device and the same outcome for every config (a device keeps its
    parking pool); one seed gives the same store twice. With
    ``draw_power`` (control readings) the seed also redraws every power
    reading under each platform's limit: the same states and shapes, and
    energies of its own."""
    import numpy as np

    from bench import reference as R
    from bench.generators import cluster
    from bench.grids import grid200
    from bench.harness import EXACT_FIELDS, semantics
    from repro.telemetry import TelemetryStore
    from repro.whatif import get_ir
    from repro.whatif.backend import pack_ir
    from repro.whatif.ir import IRConfig

    conf = load_json(copy / "bench" / "configs" / "acad64_3h.json")
    tdp = {i: p["tdp_w"] for i, p in R.load_platforms().items()}
    specs = grid200.specs()

    def streams(seed, name, draw_power=False):
        store = cluster.build(conf, seed, tmp_path / name, draw_power)
        cols = R.read_store(store.root)
        order = np.lexsort((cols["device_id"], cols["job_id"]))
        by_stream = {}
        for j, d, sm, p in zip(cols["job_id"][order],
                               cols["device_id"][order], cols["sm"][order],
                               cols["power"][order]):
            by_stream.setdefault((int(j), int(d)), []).append(
                (sm, p if not draw_power else 0.0))
        ir = get_ir(TelemetryStore(store.root), IRConfig(dt_s=1.0))
        shapes = [(b.key, b.idx.size) for b in pack_ir(ir, 5, 0.0).buckets]
        out = R.outcomes(R.Fleet(cols, semantics(conf)), specs)
        return cols, sorted(tuple(v) for v in by_stream.values()), shapes, out

    a, sa, shapes, ref = streams(5, "a")
    again = streams(5, "again")[0]
    assert all(np.array_equal(a[c], again[c], equal_nan=True) for c in a)
    orders = []
    for seed in (6, 7, 2**33 + 8):
        b, sb, shb, out = streams(seed, f"s{seed}")
        assert sb == sa and shb == shapes
        assert sorted(b["device_id"].tolist()) == \
            sorted(a["device_id"].tolist())
        assert np.array_equal(b["hostname"], b["device_id"] // 4)
        orders.append(b["power"].tolist() != a["power"].tolist())
        for x, y in zip(ref, out):
            assert all(x[f] == y[f] for f in EXACT_FIELDS)
            # a saving is a difference of two fleet totals summed in
            # another order: held relative to the total it is taken from
            assert abs(x["energy_saved_j"] - y["energy_saved_j"]) <= \
                1e-12 * x["baseline_energy_j"]
            assert x["penalty_s"] == pytest.approx(y["penalty_s"], rel=1e-12)
    assert any(orders)
    unpowered = sorted(tuple((sm, 0.0) for sm, _ in v) for v in sa)
    c, sc, shc, drawn = streams(7, "drawn", draw_power=True)
    assert sc == unpowered and shc == shapes
    limit = np.array([tdp[int(p)] for p in c["platform"]])
    assert np.all((c["power"] >= 0) & (c["power"] <= limit))
    # the schedule fixes the events; power moves the energies
    for x, y in zip(ref, drawn):
        assert all(x[f] == y[f] for f in ("n_jobs", "wake_events",
                                          "downscale_events"))
    assert [x["baseline_energy_j"] for x in ref] != \
        [y["baseline_energy_j"] for y in drawn]
