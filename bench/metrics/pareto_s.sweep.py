"""The Pareto passes (``whatif.pareto`` spans: ``whatif/sweep.py``
``pareto_flags``, run by ``run_sweep``'s frontier and again by
``find_knee``), seconds per sweep. Moves ``configs_per_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "whatif.pareto", "sweeps")
