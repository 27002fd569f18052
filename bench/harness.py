"""What every cell shares: finding a cell's files by name, the jax-path
accounting, and the comparison with the plain reference.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own that this module finds by the name
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` (the ``file`` of the configuration);
* ``bench/traffic/<traffic>.json``, whose ``kind`` names the driver
  ``bench/drivers/<kind>.py``;
* ``bench/generators/<generator>.py``, named by the configuration;
* ``bench/grids/<grid>.py``, named by a sweep's traffic;
* ``bench/metrics/<metric>.py``, one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent

#: fields of an outcome that must agree exactly, and those held to the
#: configuration's relative tolerance
EXACT_FIELDS = ("name", "params", "n_jobs", "wake_events",
                "downscale_events", "throttled_time_s")
CLOSE_FIELDS = ("baseline_energy_j", "counterfactual_energy_j",
                "energy_saved_j", "saved_fraction", "penalty_s",
                "penalty_fraction", "exec_idle_energy_fraction_baseline",
                "exec_idle_energy_fraction_cf")
CDF_FIELDS = ("per_job_saved_fraction", "per_job_penalty_s")


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a changed input)."""


# --------------------------------------------------------------------------- #
# finding things by name
# --------------------------------------------------------------------------- #
def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path, name: str | None = None):
    """Import a file by path (its name may hold dots, as metric names do)."""
    path = pathlib.Path(path)
    if not path.is_file():
        raise BenchError(f"no file {path}")
    name = name or "bench_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    root: pathlib.Path
    spec: dict
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    #: redraw every power reading from the seed (control readings only;
    #: timed runs replay one fleet in the seed's order)
    draw_power: bool = False

    @property
    def name(self) -> str:
        return self.workload["name"]

    def file(self, *parts) -> pathlib.Path:
        return self.root.joinpath("bench", *parts)

    def module(self, kind: str, name: str):
        return load_module(self.file(kind, f"{name}.py"),
                           f"bench_{kind}_{name.replace('.', '_')}")

    def end_to_end(self) -> list[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]

    def per_layer(self) -> list[dict]:
        """The per-layer metrics this cell reports: those that list it,
        or, without a list, those whose end-to-end metric it reports."""
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def load_cell(root, name: str, seed: int, seconds: float,
              trace: bool) -> Cell:
    root = pathlib.Path(root)
    spec = load_json(root / "BENCHMARK.json")
    work = [w for w in spec["workloads"] if w["name"] == name]
    if not work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    work = work[0]
    conf = [c for c in spec["configs"] if c["name"] == work["config"]]
    if not conf:
        raise BenchError(f"no configuration {work['config']!r}")
    config = load_json(root / conf[0]["file"])
    traffic = load_json(root / "bench" / "traffic"
                        / f"{work['traffic']}.json")
    return Cell(root, spec, work, config, traffic, int(seed),
                float(seconds), bool(trace))


# --------------------------------------------------------------------------- #
# jax-path accounting
# --------------------------------------------------------------------------- #
class JaxPath:
    """Counts, around each call, whether every IR-capable config ran on
    the jax path: configs on it plus configs the IR cannot host equal the
    configs evaluated, and no fallback was counted."""

    NAMES = ("repro_replay_family_configs_total",
             "repro_replay_row_fallback_configs_total",
             "repro_fallbacks_total")

    def __init__(self):
        import repro.obs as obs
        self.reg = obs.REGISTRY
        self.bad_calls = 0

    def _read(self):
        return ([self.reg.total(n) for n in self.NAMES],
                self.reg.total("repro_replay_configs_total", path="jax"))

    def __enter__(self):
        self._before = self._read()
        return self

    def __exit__(self, *exc):
        (tot, row, fb), jax_n = self._read()
        (tot0, row0, fb0), jax0 = self._before
        on_jax = jax_n - jax0
        ok = (on_jax > 0 and on_jax + (row - row0) == tot - tot0
              and fb == fb0)
        if not ok:
            self.bad_calls += 1
        return False


# --------------------------------------------------------------------------- #
# comparison with the plain reference
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Compare:
    """Mismatches of exact quantities and the worst relative error of the
    others, accumulated over every comparison of a run."""

    mismatches: int = 0
    rel_err: float = 0.0
    compared: int = 0
    worst: str = ""
    notes: list = dataclasses.field(default_factory=list)

    def miss(self, what: str) -> None:
        self.mismatches += 1
        if len(self.notes) < 8:
            self.notes.append(what)

    def close(self, x: float, y: float, scale: float, what: str) -> None:
        if not math.isfinite(x):
            self.miss(f"{what}: {x!r} is not finite")
            return
        err = abs(x - y) / (abs(scale) + 1.0)
        if err > self.rel_err:
            self.rel_err = err
            self.worst = f"{what}: {x!r} vs reference {y!r}"

    def outcomes(self, prog, ref, what: str) -> None:
        """Program outcomes (``PolicyOutcome``) against reference dicts,
        in the same order."""
        if len(prog) != len(ref):
            self.miss(f"{what}: {len(prog)} outcomes, reference {len(ref)}")
            return
        for a, b in zip(prog, ref):
            self.compared += 1
            for f in EXACT_FIELDS:
                if getattr(a, f) != b[f]:
                    self.miss(f"{what}: {f} {getattr(a, f)!r} != "
                              f"{b[f]!r} for {b['params']}")
            for f in CLOSE_FIELDS:
                # the saving is a difference of two fleet totals: it is
                # held relative to the energy it is taken from
                scale = (b["baseline_energy_j"] if f == "energy_saved_j"
                         else b[f])
                self.close(getattr(a, f), b[f], scale,
                           f"{what}: {f} of {b['params']}")
            for f in CDF_FIELDS:
                xs, ys = getattr(a, f), b[f]
                if len(xs) != len(ys):
                    self.miss(f"{what}: {f} has {len(xs)} jobs, "
                              f"reference {len(ys)}")
                    continue
                for x, y in zip(xs, ys):
                    self.close(x, y, y, f"{what}: {f} of {b['params']}")

    def frontier(self, outcomes, knee_params, ref, ref_flags, ref_knee,
                 what: str) -> None:
        """Outcomes, Pareto flags and the knee of one frontier."""
        self.outcomes(outcomes, ref, what)
        for o, f in zip(outcomes, ref_flags):
            if o.pareto != bool(f):
                self.miss(f"{what}: Pareto flag {o.pareto} for {o.params}")
        if knee_params != ref[ref_knee]["params"]:
            self.miss(f"{what}: knee {knee_params} != reference "
                      f"{ref[ref_knee]['params']}")


def checks(compare: Compare, path: JaxPath, tolerance: float) -> dict:
    """The numbers compared, each beside its limit."""
    return {
        "mismatches": {"value": compare.mismatches, "limit": 0},
        "rel_err": {"value": compare.rel_err, "limit": tolerance},
        "off_jax_calls": {"value": path.bad_calls, "limit": 0},
    }


def passed(chk: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in chk.values())


def semantics(config: dict) -> dict:
    """What the reference needs to know of a configuration."""
    return {**config["replay"], **config["classifier"]}


def _frontier(fleet, specs):
    from bench import reference as R

    ref = R.outcomes(fleet, list(specs))
    flags = R.pareto([o["energy_saved_j"] for o in ref],
                     [o["penalty_s"] for o in ref])
    return ref, flags, R.knee(ref)


def check_frontier(cmp: Compare, fleet, specs, outcomes, knee_params,
                   what: str, control=None) -> None:
    """One frontier of the program against the reference run on the same
    store over ``specs``: every outcome, every Pareto flag, the knee.

    ``control``, a lower-precision reference fleet, takes the program's
    place: its outcomes, flags and knee are compared instead."""
    ref, flags, knee = _frontier(fleet, specs)
    if control is not None:
        c_ref, c_flags, c_knee = _frontier(control, specs)
        outcomes = [Outcome(o, f) for o, f in zip(c_ref, c_flags)]
        knee_params = c_ref[c_knee]["params"]
    cmp.frontier(outcomes, knee_params, ref, flags, knee, what)


class Outcome:
    """A reference outcome dict seen as the program's outcome object."""

    def __init__(self, d: dict, pareto: bool):
        self.__dict__.update(d)
        self.pareto = bool(pareto)


def same_answers(cmp: Compare, answers, what: str) -> None:
    """Every repeat of one call on unchanged inputs gives the first
    answer, bit for bit."""
    for i, a in enumerate(answers[1:], start=2):
        if a != answers[0]:
            cmp.miss(f"{what} {i} differs from {what} 1")


def free_device() -> None:
    """Drop every array the program left on the device."""
    import jax

    for a in jax.live_arrays():
        a.delete()


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def fleets(cell, root, control: bool = False):
    """The reference's view of a store, and with ``control`` the same in
    float32."""
    import numpy as np

    from bench import reference as R

    cols = R.read_store(root)
    sem = semantics(cell.config)
    return (R.Fleet(cols, sem),
            R.Fleet(cols, sem, dtype=np.float32) if control else None)
