"""Process-pool task probe: runs a pool task in its worker and reports
whether that worker had imported JAX (tests/test_whatif_backend.py)."""
import sys


def probe(fn, *args):
    return fn(*args), "jax" in sys.modules
