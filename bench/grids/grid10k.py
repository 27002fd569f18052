"""The 10^4-config grid, pinned: 1 no-op, 2,048 Algorithm-1 downscale
(32 X x 32 Y x 2 clock modes), 50 consolidation pools and 7,901 power
caps. A copy of the grid ``benchmarks/whatif_bench.py`` sweeps, so that
the yardstick does not move with the program."""
import numpy as np

from bench.grids.common import downscale, noop, parking, powercap


def specs() -> list[dict]:
    grid = [noop()]
    for x in np.linspace(0.5, 16.0, 32):
        for y in np.linspace(1.0, 12.0, 32):
            for mode in ("sm_only", "sm_and_mem"):
                grid.append(downscale(round(float(x), 4),
                                      round(float(y), 4), mode))
    for n_devices in (4, 8):
        for k in range(1, n_devices):
            for resume_s in (2.0, 5.0, 10.0, 30.0, 60.0):
                grid.append(parking(n_devices, k, resume_s))
    for frac in np.linspace(0.2, 0.99, 10_000 - len(grid)):
        grid.append(powercap(round(float(frac), 6)))
    return grid
