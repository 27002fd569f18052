"""Frontier assembly (``whatif/sweep.py``: Pareto flags, outcome
assembly) and the knee, per sweep: the sweep's wall time minus its
``whatif.evaluate`` span. Moves ``configs_per_s``."""
from bench.readers import per_call


def read(rec):
    sweep = per_call(rec, "bench.sweep", "sweeps")
    evaluate = per_call(rec, "whatif.evaluate", "sweeps")
    if sweep is None or evaluate is None:
        return None
    return sweep - evaluate
