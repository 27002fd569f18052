"""The paper's academic cluster (arXiv 2604.04745 §2.1), from the
program's simulator: 1 Hz per-device telemetry, the Table 4 platform mix.

The fleet is simulated once from the configuration's ``data_seed``: which
platform each device is, its unallocated gaps, its jobs and their phases,
every signal, and so the state of every sample. ``--seed`` draws the
order: a permutation of the blocks of eight devices, which renumbers the
devices, their hosts (four devices to a host) and the jobs (in the new
device order), and writes one shard per device in that order. A device
keeps its place in its block, and so its number modulo 4 and 8, which
decides where a parking pool of 4 or 8 devices parks it.

Every timed seed so replays the same streams with the same outcomes in
another order: the same work. A seed that changed the data would change
the work, since the host's Pareto pass and a search's rounds follow the
outcomes. With ``draw_power`` (the control readings of
``bench/control.py``) the seed also redraws every power reading: each
device's draw scaled by a gain of its own (part-to-part spread,
``power.gain``) plus fresh sensor noise on each sample
(``power.noise_w``), clipped to the platform's power limit. States, and so
the shapes on the device, stay; energies, savings, penalties, Pareto
flags and the knee become the seed's own. The whole store is written in
set-up.
"""
from __future__ import annotations

import hashlib

import numpy as np

from bench.harness import BenchError

#: the largest parking pool: the seed moves devices in blocks of this many
POOL = 8


def simulate(config: dict):
    """The simulator's fleet for the configuration, before the seed."""
    from repro.cluster import generate_cluster

    fleet = config["fleet"]
    return generate_cluster(n_devices=fleet["n_devices"],
                            horizon_s=fleet["horizon_s"],
                            seed=fleet["data_seed"],
                            min_job_s=fleet["min_job_s"]).frame


def _sha256(frame) -> str:
    from repro.telemetry.records import FIELDS

    h = hashlib.sha256()
    for f in FIELDS:
        h.update(f.encode())
        h.update(np.ascontiguousarray(frame[f]).tobytes())
    return h.hexdigest()


def digest(config: dict) -> str:
    """sha256 of the simulated fleet, before the seed."""
    return _sha256(simulate(config))


def build(config: dict, seed: int, root, draw_power: bool = False):
    """A store of the configured fleet as ``seed`` draws it. Fails when
    the simulator's output differs from the pinned row count or digest."""
    frame = simulate(config)
    pin = config["pin"]
    if len(frame) != pin["rows"]:
        raise BenchError(f"the generator wrote {len(frame)} rows, the "
                         f"configuration pins {pin['rows']}")
    if _sha256(frame) != pin["sha256"]:
        raise BenchError("the simulator's output differs from the pinned "
                         "sha256: the inputs moved")
    return write(config, frame, seed, root, draw_power)


def draw(config: dict, frame, seed: int, draw_power: bool = False) -> dict:
    """The columns of the store ``seed`` draws from the simulated fleet,
    in shard order (device by device)."""
    from bench.reference import load_platforms
    from repro.telemetry.records import FIELDS

    n_dev = config["fleet"]["n_devices"]
    if n_dev % POOL:
        raise BenchError(f"{n_dev} devices are not whole blocks of {POOL}")
    rng = np.random.default_rng(seed)
    dev = frame["device_id"]
    block = rng.permutation(n_dev // POOL)
    new_of = POOL * block[np.arange(n_dev) // POOL] + np.arange(n_dev) % POOL
    old_of = np.argsort(new_of)
    rows = np.concatenate([np.flatnonzero(dev == d) for d in old_of])
    cols = {f: frame[f][rows] for f in FIELDS}
    cols["device_id"] = new_of[cols["device_id"]].astype(np.int32)
    cols["hostname"] = (cols["device_id"] // 4).astype(np.int32)
    job = cols["job_id"]
    ids, first = np.unique(job[job >= 0], return_index=True)
    rank = np.empty(ids.shape[0], np.int64)
    rank[np.argsort(first)] = np.arange(ids.shape[0])
    cols["job_id"] = np.where(job >= 0, rank[np.searchsorted(ids, job)], job)
    if not draw_power:
        return cols
    power = config["power"]
    lo, hi = 1.0 - power["gain"], 1.0 + power["gain"]
    gain = rng.uniform(lo, hi, n_dev)[cols["device_id"]]
    noise = rng.normal(0.0, power["noise_w"], rows.shape[0])
    plats = load_platforms()
    tdp = np.zeros(max(plats) + 1)
    for i, p in plats.items():
        tdp[i] = p["tdp_w"]
    drawn = np.clip(cols["power"] * gain + noise, 0.0,
                    tdp[cols["platform"]])
    cols["power"] = drawn.astype(cols["power"].dtype)
    return cols


def write(config: dict, frame, seed: int, root, draw_power: bool = False):
    """Write the store ``seed`` draws, one shard per device."""
    from repro.telemetry import TelemetryFrame, TelemetryStore

    cols = draw(config, frame, seed, draw_power)
    store = TelemetryStore(root, shard_format=config["store"]["shard_format"])
    bounds = np.flatnonzero(np.diff(cols["device_id"])) + 1
    for a, b in zip(np.concatenate([[0], bounds]),
                    np.append(bounds, len(cols["device_id"]))):
        d = int(cols["device_id"][a])
        store.write_shard(TelemetryFrame({f: v[a:b] for f, v in cols.items()}),
                          host=f"h{d // 4}",
                          day=int(cols["timestamp"][a]) // 86400,
                          flush_manifest=False)
    store.save_manifest()
    return store
