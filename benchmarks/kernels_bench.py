"""Kernel-suite benchmark: the run-replay cap-bucket scan.

The only Pallas kernel on the telemetry hot path is the PowerCap
cap-bucket scan (:mod:`repro.kernels.run_replay`); this bench validates
the dispatcher stack on whatever backend CI has — the interpret-mode
Pallas kernel and the jnp reference against a NumPy ``searchsorted``
oracle — and records the reference path's throughput (the path the jax
replay backend actually uses off-TPU). ``--quick`` keeps the correctness
gates and shrinks shapes; there are no timing targets in either mode
(host-clock noise swamps them).

Run:  PYTHONPATH=src python -m benchmarks.run --only kernels [--quick]
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import common
from benchmarks.common import Bench


def _np_counts(sorted_p, caps):
    sp = np.asarray(sorted_p)
    cv = np.asarray(caps)
    return np.stack([
        sp.shape[1] - np.searchsorted(sp[r], cv[r], side="right")
        for r in range(sp.shape[0])]).astype(np.int32)


def bench_kernels() -> Bench:
    import jax
    import jax.numpy as jnp

    from repro.kernels import run_replay as rr

    quick = common.QUICK
    rows, n, c = (32, 512, 64) if quick else (256, 4096, 1024)

    b = Bench("kernels")
    rng = np.random.default_rng(0)
    sp = np.sort(rng.normal(0.0, 100.0, (rows, n)), axis=1)
    caps = rng.normal(0.0, 100.0, (rows, c))
    expect = _np_counts(sp, caps)
    # the kernel compares the host-built order-key words of the floats
    words = [jnp.asarray(w) for x in (sp, caps)
             for w in rr.order_key_words(x)]

    interp = np.asarray(rr.cap_bucket_scan(*words,
                                           interpret=rr.default_interpret()))
    with jax.enable_x64():
        refv = np.asarray(rr.cap_bucket_scan_reference(jnp.asarray(sp),
                                                       jnp.asarray(caps)))
        disp = np.asarray(rr.cap_bucket_counts(*words))

    b.add("cap_scan_rows_x_configs", float(rows * c))
    b.add("cap_scan_matches_oracle",
          float(np.array_equal(interp, expect)), (1.0, 0.01))
    b.add("cap_scan_reference_matches_oracle",
          float(np.array_equal(refv, expect)), (1.0, 0.01))
    b.add("cap_scan_dispatcher_matches_oracle",
          float(np.array_equal(disp, expect)), (1.0, 0.01))
    b.add("cap_scan_default_interpret", float(rr.default_interpret()))

    fn = jax.jit(rr.cap_bucket_counts)
    best = math.inf
    with jax.enable_x64():
        fn(*words).block_until_ready()
        for _ in range(1 if quick else 5):
            t0 = time.perf_counter()
            fn(*words).block_until_ready()
            best = min(best, time.perf_counter() - t0)
    b.add("cap_scan_mlookups_per_s", rows * c / best / 1e6, seconds=best,
          devices=1)
    return b
