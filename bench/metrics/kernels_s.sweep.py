"""The device programs with their transfers and the host's cap pricing
(``whatif/backend.py``), per sweep: the ``backend.kernels`` span. Moves
``configs_per_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "backend.kernels", "sweeps")
