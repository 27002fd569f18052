"""Waiting for the device programs (``backend.wait`` spans,
``whatif/backend.py``: ``block_until_ready`` on each jit program's outputs),
seconds per sweep. Moves ``configs_per_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "backend.wait", "sweeps")
