"""Packing the run-level IR for the device (``backend.pack_ir``), per
sweep: the ``backend.pack`` span. Moves ``configs_per_s``."""
from bench.readers import per_call


def read(rec):
    return per_call(rec, "backend.pack", "sweeps")
