"""Device milliseconds of the Pallas cap scan (``cap_bucket_scan``,
``kernels/run_replay.py``) per sweep, from the profiler trace. Moves
``configs_per_s``."""
from bench.readers import kernel_ms


def read(rec):
    return kernel_ms(rec, "cap_bucket_scan", "sweeps")
