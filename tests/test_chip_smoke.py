"""chip_smoke.py rehearsed on the CPU at a tiny size.

Each phase runs through the same entry points and checks as on the chip
(the chip-only ``tpu_custom_call`` check aside): the jax path counted for
every IR-capable config, the NumPy contract, the search knee and the live
tick's ``warm_jax`` rung. The config-mesh phase runs on the suite's four
host devices. The script itself must refuse to run without a TPU, and
outside a checkout.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

import repro.obs as obs
from repro.whatif import default_policy_grid

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def fleet(smoke, tmp_path_factory):
    prev = obs.enabled()
    obs.enable()
    store = smoke.make_store(tmp_path_factory.mktemp("fleet"), n_devices=8,
                             horizon_s=2700, seed=smoke.SEED)
    ir, _ = smoke.phase_ir(store, default_policy_grid())
    yield store, ir
    if not prev:
        obs.disable()


def test_smoke_analyze(smoke, fleet):
    out = smoke.phase_analyze(fleet[0])
    assert 0.0 < out["energy_fraction"] < 1.0


def test_smoke_sweep_matches_numpy(smoke, fleet):
    out = smoke.phase_sweep(*fleet, default_policy_grid(), on_tpu=False)
    assert out["tolerance_used"] <= 1.0


def test_smoke_large_grid_subset_matches_numpy(smoke, fleet):
    from benchmarks.whatif_bench import _grid_10k
    grid = _grid_10k()[::8]
    out = smoke.phase_large_grid(*fleet, grid, stride=5)
    assert out["checked"] >= 200


def test_smoke_search_knee_matches_numpy(smoke, fleet):
    assert smoke.phase_search(*fleet)["n_evals"] > 0


def test_smoke_live_tick_on_warm_jax(smoke, tmp_path):
    prev = obs.enabled()
    obs.enable()
    try:
        out = smoke.phase_live(tmp_path, n_streams=200)
    finally:
        if not prev:
            obs.disable()
    assert len(out["tick_s"]) == 2


def test_smoke_config_mesh_on_four_host_devices(smoke, fleet):
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices (tests/conftest.py sets them)")
    from benchmarks.whatif_bench import _grid_10k
    # the registry is process-wide: other test files count fallbacks too
    fallbacks = obs.REGISTRY.total("repro_fallbacks_total")
    out = smoke.phase_mesh(*fleet, _grid_10k()[::16], n_chips=4)
    assert out["tolerance_used"] <= 1.0
    assert obs.REGISTRY.total("repro_fallbacks_total") == fallbacks


def _run_script(script: pathlib.Path, cwd: pathlib.Path):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_script_refuses_cpu():
    res = _run_script(ROOT / "chip_smoke.py", ROOT)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "no TPU" in res.stderr


def test_smoke_script_refuses_outside_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
