"""The trace reduction, on a trace recorded on a TPU v5e: one sweep of the
200-config grid over a 4-device, 30-minute fleet (``data/``)."""
import pathlib
from types import SimpleNamespace

import pytest

from bench_copy import ROOT  # noqa: F401  (puts the checkout on sys.path)

from bench import trace

TRACE = pathlib.Path(__file__).parent / "data" / "sweep_v5e.xplane.pb"
#: the traced sweep's window, as the recording measured it on the host
WINDOW_S = 0.018666868000003944


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(TRACE, WINDOW_S, kernels=("cap_bucket_scan",))


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(TRACE))
    (tpu,) = [p for p in pd.planes if p.name == "/device:TPU:0"]
    lines = {ln.name: list(ln.events) for ln in tpu.lines}
    return lines


def test_only_the_tensor_core_plane_is_a_device(reduced):
    # the trace also holds an empty "/device:CUSTOM:Megascale Trace" plane
    assert reduced["n_devices"] == 1


def test_busy_is_the_union_of_the_operations(reduced, raw):
    ops = raw["XLA Ops"]
    total = sum(e.duration_ns for e in ops) * 1e-9
    longest = max(e.duration_ns for e in ops) * 1e-9
    assert longest <= reduced["busy_s"] <= total
    assert 0 < reduced["busy_s"] < reduced["window_s"] == WINDOW_S


def test_programs_and_kernel(reduced, raw):
    progs = reduced["programs"]
    assert set(progs) >= {"_downscale_kernel", "_powercap_kernel"}
    assert sum(progs.values()) == pytest.approx(
        sum(e.duration_ns for e in raw["XLA Modules"]) * 1e-9)
    scans = [e for e in raw["XLA Ops"] if "cap_bucket_scan" in e.name]
    assert scans
    assert reduced["kernels"]["cap_bucket_scan"] == pytest.approx(
        sum(e.duration_ns for e in scans) * 1e-9)
    name, seconds = reduced["device_ops"][0]
    assert name == "_powercap_kernel/cap_bucket_scan.1"
    assert len(reduced["device_ops"]) == 10


def test_idle_gaps_go_to_the_innermost_host_span(reduced):
    # one span around the whole window and one around its second half
    t_sync = 1000.0
    spans = [SimpleNamespace(name="outer", t_start=t_sync, dur_s=WINDOW_S),
             SimpleNamespace(name="inner", t_start=t_sync + WINDOW_S / 2,
                             dur_s=WINDOW_S / 2)]
    r = trace.reduce(TRACE, WINDOW_S, spans=spans, sync_wall=t_sync)
    idle = dict(r["idle_gaps"])
    assert set(idle) == {"outer", "inner"}
    assert sum(idle.values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_union_and_innermost():
    assert trace._union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == \
        [[1, 4], [5, 10]]
    host = [(0, 10, "a"), (2, 4, "b"), (5, 9, "c"), (6, 7, "d")]
    assert [trace._innermost(host, t) for t in (1, 3, 4.5, 6.5, 8, 11)] == \
        ["a", "b", "a", "d", "c", "outside any span"]
