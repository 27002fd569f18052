"""Each driver runs a cell end to end at a tiny size on the CPU, and its
last line has the shape the benchmark's contract asks for."""
import pytest

from bench_copy import run_cell, small_copy

RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "checks"]


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    return small_copy(tmp_path_factory.mktemp("bench"))


def _shape(res, names):
    assert [k for k in res if k != "breakdown"] == RESULT_KEYS
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == set(names)
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(res["device"])
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell, names", [
    ("acad192.grid200", {"configs_per_s", "sweep_p95_s", "setup_s"}),
    ("acad192.search", {"search_s", "setup_s"}),
])
def test_cell_end_to_end(copy, cell, names):
    rc, res, err = run_cell(copy, "--workload", cell, "--seed",
                            "4294967311", "--seconds", "1.5")
    assert rc == 0, err
    _shape(res, names)
    assert err.strip().splitlines()[-1].startswith("check off_jax_calls")


def test_traced_run_reports_per_layer_metrics(copy):
    rc, res, err = run_cell(copy, "--workload", "acad192.grid200", "--seed",
                            "7", "--seconds", "1", "--trace", "1")
    assert rc == 0, err
    # the CPU has no device plane: the device-trace metrics say nothing
    assert set(res["metrics"]) == {"frontier_s.sweep", "pack_s.sweep",
                                   "kernels_s.sweep", "assembly_s.sweep"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_sizes_measures_each_layer_of_a_tiny_fleet(copy, tmp_path):
    import jax

    import repro.obs as obs
    from bench import sizes
    from bench.harness import load_json

    conf = load_json(copy / "bench" / "configs" / "acad64_3h.json")
    obs.enable()
    try:
        row = sizes.measure(conf, 8, 2**33 + 1, tmp_path, False,
                            jax.devices())
    finally:
        obs.disable()
        obs.reset()
    assert row["n_devices"] == 8 and row["rows"] == 8 * 1200
    assert row["streams"] > 0
    assert "backend.kernels" in row["grid10k_spans"]
    for k in ("simulate_s", "write_s", "ir_s", "grid10k_evaluate_s",
              "grid200_s", "search_s", "ref_grid10k_s", "ref_search_s"):
        assert row[k] > 0, k
