"""Observability layer contract: default-off, bit-identical results,
cross-process span reassembly, stable histogram edges, parseable Prometheus
exposition, and the >= 15 distinct ``repro_*`` metrics acceptance gate.
"""
import json
import tempfile
import urllib.error
import urllib.request

import pytest

import repro.obs as obs
from repro.cluster import generate_cluster
from repro.telemetry import TelemetryStore
from repro.telemetry.pipeline import analyze_store
from repro.whatif import (default_policy_grid, frontier_to_dict, run_sweep,
                          search_frontier)
from repro.whatif.search import find_knee


@pytest.fixture(scope="module")
def store_dir():
    with tempfile.TemporaryDirectory() as d:
        store = TelemetryStore(d)
        generate_cluster(n_devices=8, horizon_s=2700, seed=3,
                         store=store, shard_s=900)
        assert len({s["host"] for s in store.manifest["shards"]}) > 1
        yield d


@pytest.fixture()
def clean_obs():
    """Isolate the global obs state; leave obs disabled and empty after."""
    prev = obs.enabled()
    obs.disable()
    obs.reset()
    yield
    obs.enable() if prev else obs.disable()
    obs.reset()


# --------------------------------------------------------------------------- #
# registry basics
# --------------------------------------------------------------------------- #
def test_disabled_helpers_record_nothing(clean_obs):
    obs.counter("repro_x_total")
    obs.gauge("repro_x", 1.0)
    obs.observe("repro_x_seconds", 0.5)
    with obs.span("nothing"):
        pass
    assert obs.REGISTRY.names() == []
    assert obs.spans() == []


def test_counter_gauge_histogram_semantics(clean_obs):
    obs.enable()
    obs.counter("repro_c_total", 2.0, path="a")
    obs.counter("repro_c_total", 3.0, path="a")
    obs.counter("repro_c_total", 1.0, path="b")
    fam = obs.REGISTRY.family("repro_c_total")
    assert {dict(k)["path"]: m.value
            for k, m in fam.metrics.items()} == {"a": 5.0, "b": 1.0}

    obs.gauge("repro_g", 2.0)
    obs.gauge("repro_g", 7.0)
    assert obs.REGISTRY.gauge("repro_g").value == 7.0

    obs.observe("repro_h_seconds", 0.01)
    obs.observe("repro_h_seconds", 1e9)        # lands in the +Inf slot
    h = obs.REGISTRY.histogram("repro_h_seconds")
    assert h.count == 2 and h.counts[-1] == 1

    with pytest.raises(ValueError):
        obs.REGISTRY.counter("repro_c_total").inc(-1.0)
    with pytest.raises(ValueError):
        obs.REGISTRY.gauge("repro_c_total")    # kind conflict
    with pytest.raises(ValueError):
        obs.REGISTRY.counter("not a name!")


def test_histogram_edges_pinned_and_mergeable(clean_obs):
    edges = obs.default_buckets()
    assert edges == tuple(10.0 ** (k / 3.0) for k in range(-18, 13))
    assert len(edges) == 31
    # bit-stable: a second computation and a fresh Histogram agree exactly,
    # which is what lets worker histograms merge bucket-wise
    assert obs.Histogram().edges == edges

    obs.enable()
    obs.observe("repro_m_seconds", 0.5)
    dump = obs.REGISTRY.dump()
    obs.REGISTRY.merge(dump)                   # self-merge doubles counts
    h = obs.REGISTRY.histogram("repro_m_seconds")
    assert h.count == 2 and h.sum == 1.0


# --------------------------------------------------------------------------- #
# spans
# --------------------------------------------------------------------------- #
def test_span_nesting_single_process(clean_obs):
    obs.enable()
    with obs.span("outer", stage="x"):
        with obs.span("inner"):
            pass
        with obs.span("inner"):
            pass
    recs = obs.spans()
    assert [r.name for r in recs] == ["inner", "inner", "outer"]
    outer = recs[-1]
    assert outer.parent_id is None and outer.attrs == {"stage": "x"}
    assert all(r.parent_id == outer.span_id for r in recs[:2])
    roots = obs.span_tree(recs)
    assert len(roots) == 1 and len(roots[0].children) == 2


def test_span_jsonl_round_trip(clean_obs, tmp_path):
    obs.enable()
    with obs.span("root"):
        with obs.span("child", k=1):
            pass
    path = obs.dump_spans_jsonl(tmp_path / "spans.jsonl")
    recs = obs.load_spans_jsonl(path)
    assert recs == obs.spans()
    roots = obs.span_tree(recs)
    assert [n.span.name for n in roots] == ["root"]
    assert [c.span.name for c in roots[0].children] == ["child"]
    # every line is a flat JSON object (consumable without this package)
    for line in path.read_text().splitlines():
        assert isinstance(json.loads(line), dict)


def test_worker_spans_reassemble_across_processes(store_dir, clean_obs):
    obs.enable()
    store = TelemetryStore(store_dir)
    analyze_store(store, workers=2, compact=False)  # exercise the row pool
    recs = obs.spans()
    by_name = {}
    for r in recs:
        by_name.setdefault(r.name, []).append(r)
    # the pool fan-out produced spans in >= 2 worker processes, plus ours
    assert len({r.pid for r in recs}) >= 2
    parts = by_name["analyze.partition"]
    assert len(parts) >= 2
    # every worker span re-parents onto the parent-process stage span
    root = by_name["analyze_store"][0]
    assert all(p.parent_id == root.span_id for p in parts)
    ids = {r.span_id for r in recs}
    assert all(r.parent_id in ids for r in recs if r.parent_id)
    # and the worker metrics merged home
    assert obs.REGISTRY.counter("repro_analyze_rows_total").value > 0


# --------------------------------------------------------------------------- #
# bit-identity: the production contract
# --------------------------------------------------------------------------- #
def test_sweep_and_search_bit_identical_obs_on_off(store_dir, clean_obs):
    store = TelemetryStore(store_dir)
    grid = default_policy_grid(dense=False)[:10]

    f_off = run_sweep(store, grid, min_job_duration_s=0.0)
    r_off = search_frontier(store, max_evals=40, min_job_duration_s=0.0)
    obs.enable()
    f_on = run_sweep(store, grid, min_job_duration_s=0.0)
    r_on = search_frontier(store, max_evals=40, min_job_duration_s=0.0)

    assert frontier_to_dict(f_on) == frontier_to_dict(f_off)
    # frontier dicts include the convergence trace — identical too
    assert frontier_to_dict(r_on.frontier) == frontier_to_dict(r_off.frontier)
    assert r_on.frontier.trace and r_off.frontier.trace


def test_jax_sweep_and_search_bit_identical_obs_on_off(store_dir, clean_obs,
                                                     tmp_path):
    """The backend's spans, and their profiler annotations under a
    profiler session, leave every answer of the jax path as it is."""
    import jax

    store = TelemetryStore(store_dir)
    grid = default_policy_grid(dense=False)

    def answers():
        front = run_sweep(store, grid, backend="jax", min_job_duration_s=0.0)
        res = search_frontier(store, max_evals=40, backend="jax",
                              min_job_duration_s=0.0)
        return (frontier_to_dict(front), find_knee(front.outcomes).params,
                frontier_to_dict(res.frontier), res.knee.params)

    off = answers()
    obs.enable()
    on = answers()
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = answers()
    finally:
        jax.profiler.stop_trace()
    assert on == off and traced == off
    assert BACKEND_CHILDREN | {"whatif.pareto", "search.find_knee"} <= \
        {s.name for s in obs.spans()}


#: the spans that split the backend's ``backend.kernels`` span
BACKEND_CHILDREN = {"backend.upload", "backend.launch", "backend.wait",
                    "backend.fetch", "backend.cap_pricing", "backend.expand"}


def test_jax_sweep_spans_name_each_step(store_dir, clean_obs):
    obs.enable()
    store = TelemetryStore(store_dir)
    front = run_sweep(store, default_policy_grid(dense=False), backend="jax",
                      min_job_duration_s=0.0)
    find_knee(front.outcomes)
    recs = obs.spans()
    by_id = {r.span_id: r for r in recs}
    by_name = {}
    for r in recs:
        by_name.setdefault(r.name, []).append(r)
    # one Pareto pass for the frontier, one for the knee
    pareto = by_name["whatif.pareto"]
    assert len(pareto) == 2
    assert all(p.attrs == {"n": len(front.outcomes)} for p in pareto)
    (knee,) = by_name["search.find_knee"]
    assert knee.attrs == {"n": len(front.outcomes)}
    assert by_id[pareto[1].parent_id] is knee
    # every step of the device programs is a child of the kernels span
    (kernels,) = by_name["backend.kernels"]
    assert BACKEND_CHILDREN <= set(by_name)
    for name in BACKEND_CHILDREN:
        assert all(r.parent_id == kernels.span_id for r in by_name[name])
    # the parking tables are priced once per packed IR, maybe by an
    # earlier test
    launches = by_name["backend.launch"]
    assert {"downscale", "powercap"} <= {r.attrs["program"] for r in launches}
    assert {r.attrs["program"] for r in launches} <= \
        {"downscale", "powercap", "integrate"}
    for r in launches:
        assert set(r.attrs) == {"program", "bucket"}
        assert isinstance(r.attrs["bucket"], int)
    waits = by_name["backend.wait"]
    assert len(waits) == len(launches) == len(by_name["backend.fetch"])
    assert all(set(r.attrs) == {"program"} for r in waits)
    for name in ("backend.fetch", "backend.upload"):
        assert all(r.attrs["bytes"] > 0 for r in by_name[name])
    assert {r.attrs["family"] for r in by_name["backend.expand"]} == \
        {"downscale", "parking", "powercap"}
    # the children cover the kernels span, less the host's loop around them
    covered = sum(r.dur_s for n in BACKEND_CHILDREN for r in by_name[n])
    assert covered <= kernels.dur_s


def test_search_trace_is_deterministic_replay_data(store_dir, clean_obs):
    store = TelemetryStore(store_dir)
    res = search_frontier(store, max_evals=40, min_job_duration_s=0.0)
    assert len(res.frontier.trace) == res.n_evals
    for i, t in enumerate(res.frontier.trace):
        assert t["i"] == i
        assert set(t) == {"i", "round", "family", "saved_fraction",
                          "penalty_s"}
    # eval order: trace rows map 1:1 onto the frontier's outcomes
    assert [t["saved_fraction"] for t in res.frontier.trace] == \
        [o.saved_fraction for o in res.frontier.outcomes]


# --------------------------------------------------------------------------- #
# acceptance gate: the instrumented pipeline emits a wide metric surface
# --------------------------------------------------------------------------- #
def test_pipeline_emits_at_least_15_repro_metrics(tmp_path, clean_obs):
    # a store of its own, so the gate counts the cold pipeline (IR build
    # included) whichever tests of this module ran before it
    store = TelemetryStore(tmp_path)
    generate_cluster(n_devices=8, horizon_s=2700, seed=3, store=store,
                     shard_s=900)
    obs.enable()
    analyze_store(store)
    run_sweep(store, default_policy_grid(dense=False)[:10],
              min_job_duration_s=0.0)
    search_frontier(store, max_evals=40, min_job_duration_s=0.0)
    names = [n for n in obs.REGISTRY.names() if n.startswith("repro_")]
    assert len(names) >= 15, names
    stages = {"analyze": "repro_analyze_", "ir": "repro_ir_",
              "replay": "repro_replay_", "search": "repro_search_"}
    for stage, prefix in stages.items():
        assert any(n.startswith(prefix) for n in names), (stage, names)

    text = obs.render_prometheus()
    assert obs.lint_exposition(text) == []
    # the exposition exposes every family recorded above
    for n in names:
        assert n in text


# --------------------------------------------------------------------------- #
# exposition + endpoint
# --------------------------------------------------------------------------- #
def test_prometheus_render_lints_clean(clean_obs):
    obs.enable()
    obs.counter("repro_t_total", 2.0, path="a b")   # label value with space
    obs.gauge("repro_t", -1.5)
    obs.observe("repro_t_seconds", 0.02)
    text = obs.render_prometheus()
    assert obs.lint_exposition(text) == []
    assert '# TYPE repro_t_seconds histogram' in text
    assert 'le="+Inf"' in text


def test_linter_rejects_malformed_expositions():
    assert obs.lint_exposition("repro_x 1\n")       # sample before TYPE
    assert obs.lint_exposition("# TYPE repro_x counter\nrepro_x one\n")
    assert obs.lint_exposition(
        "# TYPE repro_x histogram\n"
        'repro_x_bucket{le="1"} 1\n'                # no +Inf bucket
        "repro_x_count 1\n")
    assert obs.lint_exposition(
        "# TYPE repro_x histogram\n"
        'repro_x_bucket{le="+Inf"} 1\n'
        "repro_x_count 2\n")                        # +Inf != _count


def test_http_metrics_endpoint(clean_obs):
    obs.enable()
    obs.counter("repro_http_total", 3.0)
    server = obs.start_http_server(port=0)
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as resp:
            assert resp.status == 200
            body = resp.read().decode()
        assert "repro_http_total 3" in body
        assert obs.lint_exposition(body) == []
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
    finally:
        server.shutdown()


# --------------------------------------------------------------------------- #
# TRACE_COUNTS migration (jax backend)
# --------------------------------------------------------------------------- #
def test_trace_counts_is_registry_backed_mapping(clean_obs):
    import repro.whatif.backend as B
    assert dict(B.TRACE_COUNTS) == {}
    B._mark_trace("downscale")
    B._mark_trace("downscale")
    B._mark_trace("powercap")
    assert dict(B.TRACE_COUNTS) == {"downscale": 2, "powercap": 1}
    assert B.TRACE_COUNTS["downscale"] == 2
    assert B.TRACE_COUNTS.get("integrate", 0) == 0
    assert sorted(B.TRACE_COUNTS) == ["downscale", "powercap"]
    # always-on: records with obs disabled, straight into the registry
    assert not obs.enabled()
    fam = obs.REGISTRY.family("repro_backend_jit_traces_total")
    assert fam is not None and fam.kind == "counter"
