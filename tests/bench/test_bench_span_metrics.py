"""The program's spans as the benchmark reads them: each span is an event
of the profiler's host plane, where the wall-clock mapping of
``bench/trace.py`` puts it, and each reader of a span metric returns its
value from a record of a traced run and nothing without its span."""
import time
from types import SimpleNamespace

import pytest

from bench_copy import ROOT

import repro.obs as obs
from bench import trace
from bench.harness import load_module


@pytest.fixture()
def clean_obs():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def test_span_is_a_host_plane_event_on_the_trace_clock(tmp_path, clean_obs):
    from jax.profiler import ProfileData

    from bench.run import Profiler

    obs.enable()
    with Profiler(tmp_path) as prof:
        with obs.span("test.outer"):
            time.sleep(0.005)
            with obs.span("test.inner"):
                time.sleep(0.01)
    spans = obs.spans()
    pd = ProfileData.from_file(str(trace.find_xplane(tmp_path)))
    events = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, ev)
    t_sync = events[trace.SYNC].start_ns
    mapped = {name: (a, b)
              for a, b, name in trace._host_spans(spans, prof.sync_wall,
                                                  t_sync)}
    assert set(mapped) == {"test.outer", "test.inner"}
    for name, (a, b) in mapped.items():
        ev = events[name]
        assert abs(ev.start_ns - a) < 1e6, name
        assert abs(ev.duration_ns - (b - a)) < 1e6, name
    assert events["test.outer"].start_ns <= events["test.inner"].start_ns


def _span(name, dur_s):
    return SimpleNamespace(name=name, dur_s=dur_s)


#: a record of two sweeps and of two searches, and what each reader finds
SWEEPS = {"sweeps": 2}
SEARCHES = {"searches": 2}
CASES = [
    ("pareto_s.sweep", "whatif.pareto", SWEEPS, [3.0, 5.0, 1.0, 3.0], 6.0),
    ("pareto_passes.sweep", "whatif.pareto", SWEEPS, [3.0, 5.0, 1.0, 3.0],
     2.0),
    ("device_wait_s.sweep", "backend.wait", SWEEPS, [0.25, 0.75, 1.0], 1.0),
    ("fetch_s.sweep", "backend.fetch", SWEEPS, [0.5, 0.25], 0.375),
    ("launch_s.sweep", "backend.launch", SWEEPS, [0.125, 0.125, 0.5], 0.375),
    ("cap_pricing_s.sweep", "backend.cap_pricing", SWEEPS, [0.5, 1.5], 1.0),
    ("device_wait_s.search", "backend.wait", SEARCHES, [1.0, 2.0, 3.0], 3.0),
    ("fetch_s.search", "backend.fetch", SEARCHES, [0.5, 0.5], 0.5),
    ("launch_s.search", "backend.launch", SEARCHES, [0.25, 0.75], 0.5),
    ("rounds.search", "search.round", SEARCHES, [1.0] * 14, 7.0),
]


@pytest.mark.parametrize("metric, span, counts, durs, expected", CASES,
                         ids=[c[0] for c in CASES])
def test_span_metric_reader(metric, span, counts, durs, expected):
    reader = load_module(ROOT / "bench" / "metrics" / f"{metric}.py")
    others = [_span("bench.sweep", 10.0), _span("backend.kernels", 4.0),
              _span("search.find_knee", 2.0)]
    spans = others + [_span(span, d) for d in durs]
    rec = {"spans": spans, "counts": counts, "trace": None}
    assert reader.read(rec) == pytest.approx(expected)
    assert reader.read({**rec, "spans": others}) is None
    assert reader.read({**rec, "counts": {}}) is None
