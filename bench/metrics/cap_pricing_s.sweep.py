"""Host pricing of the power cap (``backend.cap_pricing`` span,
``whatif/backend.py``: the gather at the device's counts and the float64
pricing), seconds per sweep. Moves ``configs_per_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "backend.cap_pricing", "sweeps")
