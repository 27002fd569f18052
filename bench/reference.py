"""Plain reference of the what-if replay's semantics.

Given the raw telemetry rows of a store and a list of policy configs (each
the plain ``params`` dict a policy describes itself by), compute every
config's fleet outcome, the Pareto flags over the set and the knee. It
imports nothing of the system under test: it reads the store's shard files
with NumPy, keeps its own copy of the platform power table
(``bench/platforms.json``), and works row by row, stream by stream, in the
order the paper defines the quantities:

* a sample's state: deep idle when no program is resident; active when any
  compute/memory signal is at or above 5 % or any link at or above
  1 GB/s (unavailable signals left out); execution-idle otherwise. Runs
  of execution-idle shorter than ``min_interval_s`` count as active;
* a stream is one (job, host, device); samples with no job are left out,
  and so are streams spanning less than ``min_job_duration_s``;
* Algorithm 1 per stream (downscale after ``x`` seconds of low activity,
  hold ``y`` seconds after a restore), parking of the devices outside a
  pool's active set, power caps with a cube-law slowdown, and the
  parking-then-downscale composite.

``dtype`` sets the float type of every sum and product; ``np.float32``
gives the lower-precision control that the comparison must refuse.
"""
from __future__ import annotations

import json
import math
import pathlib

import numpy as np

DEEP, EXEC, ACTIVE = 0, 1, 2
COMPUTE = ("sm", "tensor", "fp16", "fp32", "fp64")
MEMORY = ("dram",)
LINKS = ("pcie_tx", "pcie_rx", "nvlink_tx", "nvlink_rx", "ici_tx", "ici_rx")
INT_COLS = ("hostname", "device_id", "platform", "job_id",
            "program_resident")
COLUMNS = ("timestamp", "power") + COMPUTE + MEMORY + LINKS + INT_COLS

PLATFORMS_FILE = pathlib.Path(__file__).resolve().parent / "platforms.json"


# --------------------------------------------------------------------------- #
# input
# --------------------------------------------------------------------------- #
def read_store(root) -> dict[str, np.ndarray]:
    """The raw columns of a store's shards, in manifest order, read with
    NumPy from the files alone."""
    root = pathlib.Path(root)
    shards = json.loads((root / "manifest.json").read_text())["shards"]
    parts: dict[str, list[np.ndarray]] = {c: [] for c in COLUMNS}
    for entry in shards:
        path = root / entry["file"]
        if path.is_dir():
            cols = {c: np.load(path / f"{c}.npy") for c in COLUMNS
                    if (path / f"{c}.npy").exists()}
        else:
            with np.load(path) as z:
                cols = {c: z[c] for c in COLUMNS if c in z}
        n = int(entry["rows"])
        for c in COLUMNS:
            col = cols.get(c)
            if col is None:
                col = (np.zeros(n, np.int64) if c in INT_COLS
                       else np.full(n, np.nan))
            if col.shape[0] != n:
                raise ValueError(f"{entry['file']}: column {c} has "
                                 f"{col.shape[0]} rows, manifest {n}")
            parts[c].append(np.asarray(col))
    return {c: (np.concatenate(v) if v else np.empty(0))
            for c, v in parts.items()}


def load_platforms(path=PLATFORMS_FILE) -> dict[int, dict]:
    table = json.loads(pathlib.Path(path).read_text())
    return {int(i): table["platforms"][name]
            for i, name in table["ids"].items()}


# --------------------------------------------------------------------------- #
# per-sample classification
# --------------------------------------------------------------------------- #
def _signal_max(cols, names, nan_as):
    out = np.zeros(cols["timestamp"].shape[0])
    for k in names:
        out = np.maximum(out, np.nan_to_num(cols[k], nan=nan_as))
    return out


def classify(cols, semantics) -> np.ndarray:
    """Raw §2.2 state of every sample."""
    thr, comm_thr = semantics["activity_pct"], semantics["link_gbs"]
    active = np.zeros(cols["timestamp"].shape[0], dtype=bool)
    for k in COMPUTE + MEMORY:
        active |= np.nan_to_num(cols[k], nan=-np.inf) >= thr
    for k in LINKS:
        active |= np.nan_to_num(cols[k], nan=-np.inf) >= comm_thr
    resident = cols["program_resident"].astype(bool)
    return np.where(resident, np.where(active, ACTIVE, EXEC), DEEP)


def low_activity(cols, activity_threshold: float, comm_gbs: float):
    """Algorithm 1's low-activity test (activity as a fraction, an
    unavailable signal read as 0)."""
    comp = _signal_max(cols, COMPUTE, 0.0)
    mem = np.nan_to_num(cols["dram"], nan=0.0)
    comm = _signal_max(cols, LINKS, 0.0)
    return ((comp / 100.0 < activity_threshold)
            & (mem / 100.0 < activity_threshold) & (comm < comm_gbs))


def final_states(raw: np.ndarray, first: np.ndarray,
                 min_samples: int) -> np.ndarray:
    """Relabel execution-idle runs shorter than ``min_samples`` active;
    a run never crosses a stream start (``first`` marks them)."""
    n = raw.shape[0]
    if n == 0:
        return raw.copy()
    brk = first.copy()
    brk[1:] |= raw[1:] != raw[:-1]
    starts = np.flatnonzero(brk)
    lengths = np.diff(np.append(starts, n))
    state = raw[starts]
    state = np.where((state == EXEC) & (lengths < min_samples), ACTIVE, state)
    return np.repeat(state, lengths)


# --------------------------------------------------------------------------- #
# the fleet
# --------------------------------------------------------------------------- #
class Fleet:
    """Streams of one store, sorted and classified once."""

    def __init__(self, cols, semantics, platforms=None, dtype=np.float64):
        self.dtype = dtype
        self.sem = semantics
        self.dt = float(semantics["dt_s"])
        self.min_samples = int(math.ceil(semantics["min_interval_s"]
                                         / self.dt))
        self.platforms = platforms or load_platforms()
        keep = cols["job_id"] >= 0
        cols = {k: v[keep] for k, v in cols.items()}
        order = np.lexsort((cols["timestamp"], cols["device_id"],
                            cols["hostname"], cols["job_id"]))
        cols = {k: v[order] for k, v in cols.items()}
        n = order.shape[0]
        key = np.stack([cols["job_id"], cols["hostname"],
                        cols["device_id"]], axis=1)
        first = np.ones(n, dtype=bool)
        if n:
            first[1:] = np.any(key[1:] != key[:-1], axis=1)
        starts = np.flatnonzero(first)
        ends = np.append(starts[1:], n)
        ts = cols["timestamp"]
        span = ts[ends - 1] - ts[starts] + self.dt if n else np.empty(0)
        kept = span >= float(semantics["min_job_duration_s"])
        rows = np.repeat(kept, ends - starts)
        cols = {k: v[rows] for k, v in cols.items()}
        first = first[rows]
        self.cols = cols
        self.n_rows = int(cols["timestamp"].shape[0])
        self.starts = np.flatnonzero(first)
        self.ends = np.append(self.starts[1:], self.n_rows)
        self.n_streams = int(self.starts.shape[0])
        self.first = first
        self.raw = classify(cols, semantics)
        self.final = final_states(self.raw, first, self.min_samples)
        self.resident = cols["program_resident"].astype(bool)
        self.power = cols["power"].astype(dtype)
        self.stream_of = np.repeat(np.arange(self.n_streams),
                                   self.ends - self.starts)
        self.devices = cols["device_id"][self.starts].astype(np.int64)
        self.plat = [self.platforms[int(p)]
                     for p in cols["platform"][self.starts]]
        self.base_time, self.base_energy = self._per_state(
            self.final, self.power)
        self._low: dict = {}
        self._parked = None

    # ------------------------------------------------------------ helpers
    def _sum(self, idx, values, n):
        """Per-group sums in ``dtype`` (bincount would sum in float64)."""
        if self.dtype == np.float64:
            return np.bincount(idx, weights=values, minlength=n)
        out = np.zeros(n, self.dtype)
        np.add.at(out, idx, values.astype(self.dtype))
        return out

    def _per_state(self, final, power):
        """[S, 3] seconds and joules per state."""
        g = self.stream_of * 3 + final
        counts = np.bincount(g, minlength=3 * self.n_streams)
        energy = self._sum(g, power, 3 * self.n_streams)
        dt = self.dtype(self.dt)
        return ((counts * self.dt).reshape(-1, 3),
                (energy * dt).reshape(-1, 3).astype(self.dtype))

    def low(self, activity_threshold, comm_gbs):
        key = (activity_threshold, comm_gbs)
        if key not in self._low:
            self._low[key] = low_activity(self.cols, activity_threshold,
                                          comm_gbs)
        return self._low[key]

    # ------------------------------------------------------------ families
    def powercap(self, specs):
        """Per stream: min(power, cap) on every sample; capped samples
        count as throttled; capped resident, non-low samples each lose
        ``dt * (cbrt(power / cap) - 1)`` seconds."""
        dt = self.dtype(self.dt)
        frac = np.array([s["cap_fraction"] for s in specs], self.dtype)
        c = frac.shape[0]
        s_n = self.n_streams
        energy = np.zeros((s_n, 3, c), self.dtype)
        pen = np.zeros((s_n, c), self.dtype)
        thr = np.zeros((s_n, c), np.int64)
        lows = [self.low(s["activity_threshold"], s["comm_threshold_gbs"])
                for s in specs]
        low_key = [(s["activity_threshold"], s["comm_threshold_gbs"])
                   for s in specs]
        for i in range(s_n):
            a, b = self.starts[i], self.ends[i]
            p = self.power[a:b]
            cap = frac * self.dtype(self.plat[i]["tdp_w"])
            fin = self.final[a:b]
            for st in (DEEP, EXEC, ACTIVE):
                # the saving is what the cap clips: the k samples above it
                # lose (sample - cap) each
                q = np.sort(p[fin == st])[::-1]
                top = np.concatenate([[0], np.cumsum(q, dtype=self.dtype)])
                k = np.searchsorted(-q, -cap, side="left")
                energy[i, st] = self.base_energy[i, st] \
                    - (top[k] - k * cap) * dt
                thr[i] += k
            for key in set(low_key):
                cols = [j for j, lk in enumerate(low_key) if lk == key]
                lw = lows[cols[0]][a:b]
                q = np.sort(p[self.resident[a:b] & ~lw])
                root = np.concatenate(
                    [[0], np.cumsum(np.cbrt(q[::-1]), dtype=self.dtype)])
                capc = cap[cols]
                k = q.shape[0] - np.searchsorted(q, capc, side="right")
                pen[i, cols] = dt * (root[k] / np.cbrt(capc) - k)
        return {"energy": energy, "time": None, "penalty": pen,
                "wakes": np.zeros((s_n, c), np.int64),
                "downs": np.zeros((s_n, c), np.int64), "throttled": thr}

    def downscale(self, specs):
        """Algorithm 1, per stream: a low run fires once its consecutive
        low-activity time (a left fold of the control interval) passes x
        and the cooldown since the last restore has run out; the device
        stays downscaled to the run's end, and the next active sample
        restores it (one restore event, the cooldown clock restarts).
        Downscaled resident samples drop by the residency-floor gap, not
        below deep-idle power."""
        dt = self.dtype(self.dt)
        c = len(specs)
        s_n = self.n_streams
        x = np.array([s["threshold_x_s"] for s in specs])
        y = np.array([s["cooldown_y_s"] for s in specs])
        eps = np.array([s["interval_eps_s"] for s in specs])
        both = np.array([s["mode"] == "sm_and_mem" for s in specs])
        trig = np.array([_trigger(e, xx) for e, xx in zip(eps, x)])
        saved = np.zeros((s_n, 3, c), self.dtype)
        thr = np.zeros((s_n, c), np.int64)
        downs = np.zeros((s_n, c), np.int64)
        rests = np.zeros((s_n, c), np.int64)
        price = np.zeros((s_n, c))
        prices: dict[str, np.ndarray] = {}
        keys = {(s["activity_threshold"], s["comm_threshold_gbs"])
                for s in specs}
        if len(keys) != 1:
            raise ValueError("one low-activity threshold per family")
        low_all = self.low(*keys.pop())
        for i in range(s_n):
            a, b = self.starts[i], self.ends[i]
            plat = self.plat[i]
            ts = self.cols["timestamp"][a:b]
            low = low_all[a:b]
            res = self.resident[a:b]
            p = self.power[a:b]
            fin = self.final[a:b]
            deep = self.dtype(plat["deep_idle_w"])
            # prefix sums of the saving per mode (0: compute clock at its
            # minimum, 1: both clocks) and accounting state
            cum = np.zeros((2, 3, b - a + 1), self.dtype)
            for m, floor in enumerate((plat["exec_idle_sm_min_w"],
                                       plat["exec_idle_all_min_w"])):
                delta = self.dtype(plat["exec_idle_w"] - floor)
                sav = np.where(res, p - np.maximum(p - delta, deep), 0)
                for st in (EXEC, ACTIVE):
                    cum[m, st, 1:] = np.cumsum(
                        np.where(fin == st, sav, 0).astype(self.dtype),
                        dtype=self.dtype)
            cum_res = np.concatenate([[0], np.cumsum(res)])
            mode = both.astype(np.int64)
            t_cd = np.zeros(c)
            down = np.zeros(c, dtype=bool)
            brk = np.flatnonzero(np.diff(low)) + 1
            for s, e in zip(np.concatenate([[0], brk]),
                            np.append(brk, b - a)):
                if not low[s]:
                    rests[i] += down
                    t_cd = np.where(down, ts[s] + y, t_cd)
                    down[:] = False
                    continue
                m = e - s
                i_t = np.searchsorted(ts[s:e], t_cd, side="left")
                at = np.maximum(trig, i_t)
                fire = at < m
                pos = s + np.where(fire, at, m)
                for st in (EXEC, ACTIVE):
                    saved[i, st] += cum[mode, st, e] - cum[mode, st, pos]
                thr[i] += cum_res[e] - cum_res[pos]
                downs[i] += fire
                down |= fire
            if plat["name"] not in prices:
                prices[plat["name"]] = np.array(
                    [_restore_price(sp, plat) for sp in specs])
            price[i] = prices[plat["name"]]
        energy = self.base_energy[:, :, None] - saved * dt
        return {"energy": energy, "time": None,
                "penalty": (rests * price).astype(self.dtype),
                "wakes": rests, "downs": downs, "throttled": thr,
                "restores": rests, "price": price}

    def parked(self):
        """Every stream under parking: execution-idle (resident, low)
        samples fall to deep-idle power and residency; each low-to-active
        step of a resident device is one wake."""
        if self._parked is None:
            low = self.low(0.05, 1.0)
            idle = self.resident & low
            raw = np.where(idle, DEEP, self.raw)
            fin = final_states(raw, self.first, self.min_samples)
            deep = np.array([self.plat[i]["deep_idle_w"]
                             for i in range(self.n_streams)])
            power = np.where(idle, deep[self.stream_of].astype(self.dtype),
                             self.power)
            t, e = self._per_state(fin, power)
            act = self.resident & ~low
            prev = np.zeros_like(idle)
            prev[1:] = idle[:-1]
            prev[self.first] = False
            wakes = np.bincount(self.stream_of, weights=act & prev,
                                minlength=self.n_streams).astype(np.int64)
            n_idle = np.bincount(self.stream_of, weights=idle,
                                 minlength=self.n_streams).astype(np.int64)
            self._parked = (t, e, wakes, n_idle)
        return self._parked

    def parked_mask(self, specs):
        n_dev = np.array([s["n_devices"] for s in specs])
        n_act = np.array([s["n_active"] for s in specs])
        return (self.devices[:, None] % n_dev[None, :]) >= n_act[None, :]

    def parking(self, specs):
        for s in specs:
            if (s["activity_threshold"], s["comm_threshold_gbs"]) != \
                    (0.05, 1.0):
                raise ValueError("parking at non-default thresholds")
        t, e, wakes, n_idle = self.parked()
        mask = self.parked_mask(specs)
        m3 = mask[:, None, :]
        resume = np.array([s["resume_latency_s"] for s in specs])
        wk = np.where(mask, wakes[:, None], 0)
        return {"energy": np.where(m3, e[:, :, None],
                                   self.base_energy[:, :, None]),
                "time": np.where(m3, t[:, :, None],
                                 self.base_time[:, :, None]),
                "penalty": (wk * resume[None, :]).astype(self.dtype),
                "wakes": wk, "downs": np.zeros_like(wk),
                "throttled": np.where(mask, n_idle[:, None], 0)}

    def park_downscale(self, specs):
        """Parking, then Algorithm 1 on what parking left: on a parked
        device every low resident sample is already parked, so the
        downscale part changes no power there but still counts its
        events; each part prices its own events."""
        park = [s["parts"][0] for s in specs]
        ds = self.downscale([s["parts"][1] for s in specs])
        pk = self.parking(park)
        mask = self.parked_mask(park)
        m3 = mask[:, None, :]
        resume = np.array([s["resume_latency_s"] for s in park])
        wk = pk["wakes"]
        pen = 0.0 + wk * resume[None, :]
        pen = pen + ds["restores"] * ds["price"]
        return {"energy": np.where(m3, pk["energy"], ds["energy"]),
                "time": pk["time"],
                "penalty": pen.astype(self.dtype),
                "wakes": wk + ds["restores"], "downs": ds["downs"],
                "throttled": np.where(mask, pk["throttled"],
                                      ds["throttled"])}

    def noop(self, specs):
        c = len(specs)
        z = np.zeros((self.n_streams, c), np.int64)
        return {"energy": np.repeat(self.base_energy[:, :, None], c, 2),
                "time": None, "penalty": np.zeros((self.n_streams, c),
                                                  self.dtype),
                "wakes": z, "downs": z, "throttled": z}


def _trigger(eps: float, x: float) -> int:
    """Index of the first sample of a low run at which the consecutive
    low-activity time (eps added once per sample) exceeds x."""
    c, i = 0.0, 0
    while True:
        c += eps
        if c > x:
            return i
        i += 1


def _perf_scale(plat, both: bool, cbf: float) -> float:
    c = plat["perf_at_min_compute"]
    m = plat["perf_at_min_memory"] if both else 1.0
    return 1.0 / (cbf / c + (1.0 - cbf) / m)


def _restore_price(spec, plat) -> float:
    """Seconds lost per downscale episode: two clock switches plus one
    control interval at the minimum clocks' throughput."""
    r = _perf_scale(plat, spec["mode"] == "sm_and_mem",
                    spec["compute_bound_fraction"])
    return 2.0 * spec["switch_latency_s"] + spec["interval_eps_s"] * (1.0 - r)


def _family(spec) -> str:
    kind = spec["policy"]
    if kind == "composite":
        parts = [p["policy"] for p in spec["parts"]]
        if parts != ["parking", "downscale"]:
            raise ValueError(f"no reference for composite {parts}")
        return "park_downscale"
    if kind not in ("noop", "downscale", "parking", "powercap"):
        raise ValueError(f"no reference for policy {kind!r}")
    return kind


def _name(spec) -> str:
    if spec["policy"] == "composite":
        return "+".join(p["policy"] for p in spec["parts"])
    return spec["policy"]


# --------------------------------------------------------------------------- #
# outcomes, Pareto flags, knee
# --------------------------------------------------------------------------- #
def outcomes(fleet: Fleet, specs: list[dict]) -> list[dict]:
    """One outcome dict per spec, in spec order (the fields of the
    program's ``PolicyOutcome``, without the Pareto flag)."""
    groups: dict[str, list[int]] = {}
    for j, s in enumerate(specs):
        groups.setdefault(_family(s), []).append(j)
    out: list[dict | None] = [None] * len(specs)
    dt = fleet.dt
    bt = fleet.base_time
    be = fleet.base_energy
    base_tot = _fold(be.sum(axis=0))
    active_t = float(bt[:, ACTIVE].sum())
    b_den = float(be[:, EXEC].sum() + be[:, ACTIVE].sum())
    base_exec = float(be[:, EXEC].sum()) / b_den if b_den else 0.0
    jb = be[:, DEEP] + be[:, EXEC] + be[:, ACTIVE]
    for fam, idx in groups.items():
        r = getattr(fleet, fam)([specs[j] for j in idx])
        e = r["energy"]
        cf_state = e.sum(axis=0)                         # [3, C]
        jc = e[:, DEEP] + e[:, EXEC] + e[:, ACTIVE]      # [S, C]
        with np.errstate(invalid="ignore", divide="ignore"):
            per_job = np.where(jb[:, None] != 0,
                               (jb[:, None] - jc) / jb[:, None], 0.0)
        for col, j in enumerate(idx):
            cf_tot = _fold(cf_state[:, col])
            saved = base_tot - cf_tot
            pen = float(r["penalty"][:, col].sum())
            den = float(cf_state[EXEC, col] + cf_state[ACTIVE, col])
            out[j] = {
                "name": _name(specs[j]),
                "params": specs[j],
                "n_jobs": fleet.n_streams,
                "baseline_energy_j": base_tot,
                "counterfactual_energy_j": cf_tot,
                "energy_saved_j": saved,
                "saved_fraction": saved / base_tot if base_tot else 0.0,
                "penalty_s": pen,
                "penalty_fraction": pen / active_t if active_t else 0.0,
                "wake_events": int(r["wakes"][:, col].sum()),
                "downscale_events": int(r["downs"][:, col].sum()),
                "throttled_time_s": float(int(r["throttled"][:, col].sum())
                                          * dt),
                "exec_idle_energy_fraction_baseline": base_exec,
                "exec_idle_energy_fraction_cf": (
                    float(cf_state[EXEC, col]) / den if den else 0.0),
                "per_job_saved_fraction": tuple(
                    float(v) for v in np.sort(per_job[:, col])),
                "per_job_penalty_s": tuple(
                    float(v) for v in np.sort(r["penalty"][:, col])),
            }
    return out


def _fold(per_state) -> float:
    """Total energy: deep idle, then execution-idle, then active."""
    return float(float(per_state[DEEP]) + float(per_state[EXEC])
                 + float(per_state[ACTIVE]))


def pareto(saved, penalty) -> np.ndarray:
    """Not dominated: no other point saves at least as much at no more
    penalty, and strictly better in one of the two."""
    s = np.asarray(saved, dtype=np.float64)
    p = np.asarray(penalty, dtype=np.float64)
    n = s.shape[0]
    flags = np.ones(n, dtype=bool)
    for a in range(0, n, 1024):
        si, pi = s[a:a + 1024, None], p[a:a + 1024, None]
        dom = (s[None, :] >= si) & (p[None, :] <= pi) \
            & ((s[None, :] > si) | (p[None, :] < pi))
        flags[a:a + 1024] = ~dom.any(axis=1)
    return flags


def knee(outs: list[dict]) -> int:
    """Index of the Pareto front's elbow: the member farthest above the
    chord between the front's ends, saving and penalty each scaled to
    the front's range; fronts of fewer than three members, or with a
    flat chord, take the member with the largest scaled saving minus
    scaled penalty. Ties keep the lower-penalty member."""
    s = [o["energy_saved_j"] for o in outs]
    p = [o["penalty_s"] for o in outs]
    flags = pareto(s, p)
    front = sorted((i for i in range(len(outs)) if flags[i]),
                   key=lambda i: (p[i], -s[i]))
    fs = [s[i] for i in front]
    fp = [p[i] for i in front]
    s_lo, s_span = min(fs), max(fs) - min(fs)
    p_lo, p_span = min(fp), max(fp) - min(fp)

    def norm(i):
        return ((s[i] - s_lo) / s_span if s_span else 0.0,
                (p[i] - p_lo) / p_span if p_span else 0.0)

    if len(front) >= 3:
        (s0, p0), (s1, p1) = norm(front[0]), norm(front[-1])
        ds, dp = s1 - s0, p1 - p0
        chord = math.hypot(ds, dp)
        if chord > 0:
            best, best_d = front[0], -math.inf
            for i in front:
                si, pi = norm(i)
                d = (dp * (si - s0) - ds * (pi - p0)) / chord
                if d > best_d + 1e-12:
                    best, best_d = i, d
            return best
    best, best_u = front[0], -math.inf
    for i in front:
        si, pi = norm(i)
        if si - pi > best_u + 1e-12:
            best, best_u = i, si - pi
    return best
