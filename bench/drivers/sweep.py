"""Closed loop of sweeps: ``run_sweep(..., backend="jax")`` back to back,
each to a finished frontier (outcomes, Pareto flags) and its knee.

End-to-end: ``configs_per_s`` (configs of every completed sweep over the
time those sweeps took) and ``sweep_p95_s`` (95th percentile of the
sweeps' latencies). Set-up generates the store, builds the run-level IR
and replays the grid once (``evaluate``, the sweep without its host-side
Pareto pass and knee), which compiles every shape the window uses.
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness import (Compare, JaxPath, check_frontier, free_device,
                           fleets, same_answers, timed)
from bench.policies import build


class State:
    pass


def _sweep(st):
    from repro.whatif import run_sweep
    from repro.whatif.search import find_knee

    front = run_sweep(st.store, st.policies, ir=st.ir, backend="jax",
                      **st.replay)
    return front, find_knee(front.outcomes).params


def setup(cell, work) -> State:
    from repro.whatif import evaluate, get_ir, ir_config_for

    st = State()
    st.replay = dict(cell.config["replay"])
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
            st.policies, st.store, ir=st.ir, backend="jax", **st.replay))
    st.cap_work = _cap_work(st)
    return st


def _cap_work(st):
    """Compares and bytes of the cap scan in one sweep, from the padded
    shapes of the packed run tables (None when the program keeps them
    elsewhere, or the grid has no cap)."""
    from bench.work import cap_scan_work

    n_caps = sum(1 for s in st.specs if s["policy"] == "powercap")
    try:
        packed = next(iter(st.ir.__dict__["_jax_packed"].values()))
        shapes = [b.arrays["cap_hi"].shape for b in packed.buckets]
    except (KeyError, AttributeError, StopIteration):
        return None
    if not n_caps:
        return None
    c_pad = max(8, 1 << (n_caps - 1).bit_length())
    work = [cap_scan_work(s_b * planes, width, c_pad)
            for s_b, planes, width in shapes]
    return {k: sum(w[k] for w in work) for k in ("compares", "bytes")}


def window(cell, st, seconds: float) -> dict:
    import repro.obs as obs

    lat, answers = [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        ts = time.perf_counter()
        with obs.span("bench.sweep"), st.path:
            answers.append(_sweep(st))
        lat.append(time.perf_counter() - ts)
    total = time.perf_counter() - t0
    st.answers = answers
    n = len(answers)
    return {"metrics": {"configs_per_s": n * len(st.specs) / total,
                        "sweep_p95_s": float(np.percentile(lat, 95))},
            "attempted": n, "failed": st.path.bad_calls,
            "counts": {"sweeps": n}, "window_s": total}


def check(cell, st, control: bool = False) -> Compare:
    free_device()
    st.store = st.ir = st.policies = None
    cmp = Compare()
    front, knee = st.answers[0]
    fleet, ctl = fleets(cell, st.root, control=control)
    check_frontier(cmp, fleet, st.specs, front.outcomes, knee, "sweep 1",
                   ctl)
    same_answers(cmp, [(f.outcomes, k) for f, k in st.answers], "sweep")
    return cmp
