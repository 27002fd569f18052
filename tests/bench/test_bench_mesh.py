"""The four-chip cell ``acad64.grid10k.mesh4`` end to end on four CPU
devices, at a tiny cut of its configuration: the config axis of the
10^4 grid split over the mesh, the answers held to the reference, and the
program's placement and padding counters read per sweep."""
import pytest

from bench_copy import edit, run_cell, small_copy

CELL = "acad64.grid10k.mesh4"


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    import jax

    from bench.generators import cluster

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices (tests/conftest.py sets them)")
    dst = small_copy(tmp_path_factory.mktemp("bench"))

    def cut(d):
        d["fleet"].update(n_devices=16, horizon_s=1200)
        d["pin"].update(rows=16 * 1200, sha256=cluster.digest(d))

    edit(dst / "bench" / "configs" / "acad64_3h_mesh4.json", cut)
    return dst


def test_mesh_cell_is_correct(copy):
    rc, res, err = run_cell(copy, "--workload", CELL, "--seed",
                            "4294967311", "--seconds", "1")
    assert rc == 0, err
    assert res["correct"] is True, err
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"configs_per_s", "setup_s"}
    assert res["device"]["count"] == 4
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_traced_mesh_cell_reads_placement_and_padding(copy):
    rc, res, err = run_cell(copy, "--workload", CELL, "--seed", "9",
                            "--seconds", "1", "--trace", "1")
    assert rc == 0, err
    assert res["correct"] is True, err
    # the CPU has no device plane: the idle share says nothing
    assert set(res["metrics"]) == {
        "kernels_s.sweep", "device_wait_s.sweep", "fetch_s.sweep",
        "assembly_s.sweep", "placed_mb.sweep_mesh", "pad_configs.sweep_mesh"}
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # resident tables: a sweep places only its config-axis arrays, 1,024
    # lanes of trigger and cooldown (8 bytes each), the replicated time
    # step on four devices, and two cap words for 8,192 lanes per stream
    caps = round(m["placed_mb.sweep_mesh"] * 1e6) - 2 * 8 * 1024 - 8 * 4
    assert caps > 0 and caps % (2 * 4 * 8192) == 0
    # 7,901 caps padded to 8,192; 544 distinct downscale pairs to 1,024
    assert m["pad_configs.sweep_mesh"] == (8192 - 7901) + (1024 - 544)
