"""The 200-config default grid, pinned: 1 no-op, 64 Algorithm-1
downscale (8 X x 4 Y x 2 clock modes), 21 consolidation pools and 114
power caps. A copy of the program's ``default_policy_grid()``."""
import numpy as np

from bench.grids.common import downscale, noop, parking, powercap


def specs() -> list[dict]:
    grid = [noop()]
    for x in (0.5, 1.0, 2.0, 3.0, 5.0, 8.0, 10.0, 15.0):
        for y in (1.0, 2.0, 5.0, 10.0):
            for mode in ("sm_only", "sm_and_mem"):
                grid.append(downscale(x, y, mode))
    for k in (1, 2, 3):
        for resume_s in (2.0, 5.0, 10.0, 30.0, 60.0):
            grid.append(parking(4, k, resume_s))
    for k in (2, 4, 6):
        for resume_s in (5.0, 30.0):
            grid.append(parking(8, k, resume_s))
    for frac in np.linspace(0.25, 0.95, 114):
        grid.append(powercap(round(float(frac), 4)))
    return grid
