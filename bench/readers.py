"""What the per-layer metric readers share. A reader gets the record of a
traced run: ``spans`` (the program's and the harness's host spans in the
window), ``counts`` (calls the window completed) and ``trace`` (the
device-trace reduction of ``bench/trace.py``). It returns ``None`` when it
finds nothing to read."""
from __future__ import annotations


def span_total(rec, name: str) -> float:
    return sum(s.dur_s for s in rec["spans"] if s.name == name)


def per_call(rec, name: str, calls: str):
    """A span's total seconds per completed call of the window."""
    n = rec["counts"].get(calls, 0)
    if not n or not any(s.name == name for s in rec["spans"]):
        return None
    return span_total(rec, name) / n


def idle_share(rec):
    """Per cent of the traced window in which no operation ran on the
    device."""
    tr = rec["trace"]
    if not tr or not tr["n_devices"] or tr["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def kernel_ms(rec, kernel: str, calls: str):
    """Device milliseconds of a kernel per completed call."""
    tr = rec["trace"]
    n = rec["counts"].get(calls, 0)
    if not tr or not n or not tr["kernels"].get(kernel):
        return None
    return 1e3 * tr["kernels"][kernel] / n
