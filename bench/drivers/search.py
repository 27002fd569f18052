"""Closed loop of budgeted searches: ``search_frontier(...,
backend="jax")`` back to back, each to its frontier of evaluated configs
and its knee.

End-to-end: ``search_s``, the window's time over the completed searches.
Set-up generates the store, builds the run-level IR and runs one search,
which compiles every shape its rounds use (the candidate axis is padded
to powers of two).
"""
from __future__ import annotations

import time

from bench.harness import (Compare, JaxPath, check_frontier, free_device,
                           fleets, same_answers, timed)


class State:
    pass


def _search(st):
    from repro.whatif.search import search_frontier

    res = search_frontier(st.store, ir=st.ir, backend="jax",
                          families=st.families, max_evals=st.max_evals,
                          max_rounds=st.max_rounds, **st.replay)
    return res.frontier.outcomes, res.knee.params


def setup(cell, work) -> State:
    from repro.whatif import get_ir
    from repro.whatif.ir import IRConfig
    from repro.whatif.search import default_families

    st = State()
    tr = cell.traffic
    st.replay = dict(cell.config["replay"])
    st.max_evals, st.max_rounds = tr["max_evals"], tr["max_rounds"]
    st.families = [f for f in default_families(composites=tr["composites"])
                   if tr["families"] is None or f.name in tr["families"]]
    gen = cell.module("generators", cell.config["generator"])
    st.store, st.generate_s = timed(
        lambda: gen.build(cell.config, cell.seed, work / "store",
                          draw_power=cell.draw_power))
    st.root = st.store.root
    st.ir, st.ir_s = timed(lambda: get_ir(
        st.store, IRConfig(dt_s=st.replay["dt_s"])))
    st.path = JaxPath()
    with st.path:
        _, st.warm_s = timed(lambda: _search(st))
    return st


def window(cell, st, seconds: float) -> dict:
    import repro.obs as obs

    answers = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with obs.span("bench.search"), st.path:
            answers.append(_search(st))
    total = time.perf_counter() - t0
    st.answers = answers
    n = len(answers)
    return {"metrics": {"search_s": total / n},
            "attempted": n, "failed": st.path.bad_calls,
            "counts": {"searches": n}, "window_s": total}


def check(cell, st, control: bool = False) -> Compare:
    free_device()
    st.store = st.ir = None
    cmp = Compare()
    outcomes, knee = st.answers[0]
    fleet, ctl = fleets(cell, st.root, control=control)
    check_frontier(cmp, fleet, [o.params for o in outcomes], outcomes, knee,
                   "search 1", ctl)
    same_answers(cmp, st.answers, "search")
    return cmp
