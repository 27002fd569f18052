"""Closed loop of sweeps with the config axis split over the chips of one
host: ``run_sweep(..., backend="jax", dist=config_mesh(n))`` back to
back, each to a finished frontier and its knee, ``n`` being the
configuration's ``layout.axis_size``.

The sweep driver's loop (``bench/drivers/sweep.py``, whose ``check`` and
cap-scan count this reuses) with the mesh passed to the set-up's warm
replay and to every sweep. End-to-end: ``configs_per_s`` and
``sweep_p95_s`` as there. The window also counts what the program put on
the chips (``repro_backend_placed_bytes_total``, ``placed_bytes``) and
the config lanes it padded (``repro_backend_pad_configs_total``,
``pad_configs``); a program without those counters leaves them out.
"""
from __future__ import annotations

import time

import numpy as np

from bench.drivers.sweep import _cap_work, check  # noqa: F401
from bench.harness import JaxPath, timed
from bench.policies import build

#: window counts read from the program's counters
COUNTERS = {"placed_bytes": "repro_backend_placed_bytes_total",
            "pad_configs": "repro_backend_pad_configs_total"}


class State:
    pass


def _sweep(st):
    from repro.whatif import run_sweep
    from repro.whatif.search import find_knee

    front = run_sweep(st.store, st.policies, ir=st.ir, backend="jax",
                      dist=st.dist, **st.replay)
    return front, find_knee(front.outcomes).params


def setup(cell, work) -> State:
    from repro.whatif import evaluate, get_ir, ir_config_for
    from repro.whatif.backend import config_mesh

    st = State()
    st.replay = dict(cell.config["replay"])
    st.dist = config_mesh(cell.config["layout"]["axis_size"])
    gen = cell.module("generators", cell.config["generator"])
    st.store, st.generate_s = timed(
        lambda: gen.build(cell.config, cell.seed, work / "store",
                          draw_power=cell.draw_power))
    st.root = st.store.root
    st.specs = cell.module("grids", cell.traffic["grid"]).specs()
    st.policies = [build(s) for s in st.specs]
    st.ir, st.ir_s = timed(lambda: get_ir(
        st.store, ir_config_for(st.policies, dt_s=st.replay["dt_s"])))
    st.path = JaxPath()
    with st.path:
        _, st.warm_s = timed(lambda: evaluate(
            st.policies, st.store, ir=st.ir, backend="jax", dist=st.dist,
            **st.replay))
    st.cap_work = _cap_work(st)
    return st


def _counters() -> dict:
    import repro.obs as obs

    return {k: obs.REGISTRY.total(name) for k, name in COUNTERS.items()
            if obs.REGISTRY.family(name) is not None}


def window(cell, st, seconds: float) -> dict:
    import repro.obs as obs

    lat, answers = [], []
    before = _counters()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        ts = time.perf_counter()
        with obs.span("bench.sweep"), st.path:
            answers.append(_sweep(st))
        lat.append(time.perf_counter() - ts)
    total = time.perf_counter() - t0
    st.answers = answers
    n = len(answers)
    counts = {"sweeps": n}
    counts.update((k, v - before.get(k, 0.0))
                  for k, v in _counters().items())
    return {"metrics": {"configs_per_s": n * len(st.specs) / total,
                        "sweep_p95_s": float(np.percentile(lat, 95))},
            "attempted": n, "failed": st.path.bad_calls,
            "counts": counts, "window_s": total}
