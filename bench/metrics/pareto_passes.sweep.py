"""Pareto passes per sweep: the count of ``whatif.pareto`` spans
(``whatif/sweep.py`` ``pareto_flags``) over the sweeps of the window.
Moves ``configs_per_s``."""


def read(rec):
    n = rec["counts"].get("sweeps", 0)
    passes = sum(1 for s in rec["spans"] if s.name == "whatif.pareto")
    if not n or not passes:
        return None
    return passes / n
