"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Read with ``jax.profiler.ProfileData``. A device is a plane named
``/device:...`` that has an ``XLA Ops`` line, which holds one event per
operation that ran on the chip (a TPU trace also holds empty planes such
as ``/device:CUSTOM:Megascale Trace``, which are not devices). From them:

* busy seconds: the union of the operations' intervals inside the window,
  averaged over the devices; the idle share is 1 minus busy over the
  window;
* device seconds per program: events of the ``XLA Modules`` line, by
  program name (the ``jit_`` name of a jitted function);
* device seconds per operation, named ``<program>/<instruction>`` (the
  HLO instruction's name, in the program whose module event encloses it);
* device seconds per kernel: operations whose name holds the kernel's
  name (a Pallas call's ``name=``);
* the longest idle gaps of the first device, each put down to the
  innermost host span (``repro.obs``) open at its middle.

The window starts at a host annotation named ``SYNC`` that the harness
emits as the profiler starts; host spans carry wall-clock start times,
which that annotation maps onto the trace's clock.
"""
from __future__ import annotations

import bisect
import collections
import pathlib

SYNC = "bench.window_start"
OPS_LINE = "XLA Ops"
#: entries kept of the longest device operations and idle gaps
N_TOP = 10
MODULES_LINE = "XLA Modules"


def find_xplane(directory) -> pathlib.Path:
    files = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def _union(intervals, lo, hi):
    """Merged intervals clipped to [lo, hi]."""
    out = []
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _program_name(event_name: str) -> str:
    name = event_name.split("(")[0]
    return name[4:] if name.startswith("jit_") else name


def _op_name(event_name: str, modules, starts, t) -> str:
    """``<program>/<instruction>`` of an operation event, whose name is the
    HLO instruction's text (``%fusion.9 = u32[...] fusion(...)``)."""
    inst = event_name.split(" = ")[0].lstrip("%")
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return f"{modules[i][2]}/{inst}"
    return inst


def reduce(path, window_s: float, spans=(), sync_wall: float | None = None,
           kernels=()) -> dict:
    """Device busy and idle time, per-program and per-kernel device
    seconds and the longest idle gaps, for the window of ``window_s``
    seconds that starts at the ``SYNC`` annotation (at the first event of
    the trace when there is none)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)
    t_sync = None
    t_first = None
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            if any(ln.name == OPS_LINE for ln in plane.lines):
                devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if t_first is None or ev.start_ns < t_first:
                    t_first = ev.start_ns
                if ev.name == SYNC and t_sync is None:
                    t_sync = ev.start_ns
    lo = t_sync if t_sync is not None else (t_first or 0.0)
    hi = lo + window_s * 1e9

    busy_ns = []
    programs: dict[str, float] = collections.defaultdict(float)
    kernel_s: dict[str, float] = collections.defaultdict(float)
    ops: dict[str, float] = collections.defaultdict(float)
    first_busy = None
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns,
             _program_name(ev.name))
            for ev in (lines[MODULES_LINE].events
                       if MODULES_LINE in lines else ()))
        for a, b, name in modules:
            if lo <= a < hi:
                programs[name] += (b - a) * 1e-9
        starts = [m[0] for m in modules]
        intervals = []
        for ev in lines[OPS_LINE].events:
            end = ev.start_ns + ev.duration_ns
            if end <= lo or ev.start_ns >= hi:
                continue
            intervals.append((ev.start_ns, end))
            name = _op_name(ev.name, modules, starts, ev.start_ns)
            ops[name] += ev.duration_ns * 1e-9
            for k in kernels:
                if k in name:
                    kernel_s[k] += ev.duration_ns * 1e-9
        merged = _union(intervals, lo, hi)
        busy_ns.append(sum(b - a for a, b in merged))
        if first_busy is None:
            first_busy = merged

    busy_s = (sum(busy_ns) / len(busy_ns) * 1e-9) if busy_ns else 0.0
    gaps = []
    if first_busy is not None:
        edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
    idle_by_span: dict[str, float] = collections.defaultdict(float)
    host = _host_spans(spans, sync_wall, t_sync)
    for a, b in gaps:
        idle_by_span[_innermost(host, (a + b) / 2)] += (b - a) * 1e-9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:N_TOP]
    idle = sorted(idle_by_span.items(), key=lambda kv: -kv[1])[:N_TOP]
    return {"window_s": window_s, "busy_s": busy_s,
            "n_devices": len(devices), "programs": dict(programs),
            "kernels": dict(kernel_s),
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in idle]}


def _host_spans(spans, sync_wall, t_sync):
    """Host spans on the trace's clock, ``(start_ns, end_ns, name)``,
    sorted by start."""
    if sync_wall is None or t_sync is None:
        return []
    out = []
    for s in spans:
        a = t_sync + (s.t_start - sync_wall) * 1e9
        out.append((a, a + s.dur_s * 1e9, s.name))
    return sorted(out)


def _innermost(host, t) -> str:
    """The span open at ``t`` that started last: with spans nested as
    they are on one thread, the innermost."""
    i = bisect.bisect_right(host, (t, float("inf"), "")) - 1
    while i >= 0:
        a, b, name = host[i]
        if a <= t < b:
            return name
        i -= 1
    return "outside any span"
