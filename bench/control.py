"""Readings that the limits of ``correct`` are set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: the cell's set-up and a short window, then
the comparison of what the window produced with the plain reference (the
program's reading), and the same comparison with the reference computed
in float32 put in the program's place (the control's reading). Unlike a
timed run, the seed here also redraws every power reading of the fleet
(``draw_power``), so each seed's readings are on data of its own. Prints
one JSON line per seed. The benchmark's own runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parent.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.harness import BenchError, load_cell  # noqa: E402
from bench.run import devices_for, enable_compile_cache, log  # noqa: E402


def readings(cell, seeds, root=ROOT) -> list[dict]:
    import repro.obs as obs

    obs.enable()
    driver = cell.module("drivers", cell.traffic["kind"])
    out = []
    cell.draw_power = True
    for seed in seeds:
        cell.seed = seed
        with tempfile.TemporaryDirectory(prefix="bench_control_") as work:
            st = driver.setup(cell, pathlib.Path(work))
            driver.window(cell, st, cell.seconds)
            prog = driver.check(cell, st)
            ctl = driver.check(cell, st, control=True)
        row = {"seed": seed,
               "program": {"mismatches": prog.mismatches,
                           "rel_err": prog.rel_err},
               "control": {"mismatches": ctl.mismatches,
                           "rel_err": ctl.rel_err, "worst": ctl.worst}}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(ROOT, args.workload, args.seeds[0], args.seconds,
                         False)
        devices_for(cell.workload["chips"], True)
    except BenchError as e:
        log(f"control: {e}")
        return 2
    enable_compile_cache(ROOT)
    readings(cell, args.seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
