"""Give multi-device tests a few host devices WITHOUT touching the dry-run's
512-device setting (smoke tests and benches must see a small count)."""
import os

# must run before jax initializes; 4 host devices cover the 2-way mesh tests
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

try:
    from hypothesis import settings
except ModuleNotFoundError:     # tests/_hyp.py draws fixed examples itself
    pass
else:
    # the same examples on every run, and no example database: a property
    # test's verdict must not depend on the draw or on an earlier run
    settings.register_profile("repro", derandomize=True, database=None)
    settings.load_profile("repro")
