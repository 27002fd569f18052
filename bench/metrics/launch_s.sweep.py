"""Dispatch of the device programs (``backend.launch`` spans,
``whatif/backend.py``: each call of a jit program, up to its return),
seconds per sweep. Moves ``configs_per_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "backend.launch", "sweeps")
