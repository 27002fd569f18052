"""Host fleet assembly of the outcomes (``whatif/backend.py``), per
sweep: the ``backend.assembly`` span. Moves ``configs_per_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "backend.assembly", "sweeps")
