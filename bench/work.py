"""Work of the device kernels, computed from their padded shapes, and the
table of peaks (``bench/peaks.json``) they are held against.

``cap_bucket_scan`` (``kernels/run_replay.py``) counts, per row, the
samples above each cap by comparing int32 word pairs: rows are padded to
8 sublanes, samples to 128 lanes (front-padded), caps to 128 and then to
a whole number of 512-cap blocks. Every padded (row, sample, cap) triple
is one word-pair compare. Each 8-row block of both sample words is read
once per row block (its block index does not change along the cap axis),
each cap block once, and the int32 counts written once.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"

SUBLANES, LANES, BLOCK_C = 8, 128, 512
WORD = 4


def peaks(device_kind: str, path=PEAKS_FILE) -> dict:
    """The published peaks of a device kind; an unknown kind is an error."""
    table = json.loads(pathlib.Path(path).read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table['devices'])}") from None


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def cap_scan_work(rows: int, samples: int, caps: int) -> dict:
    """Compares and bytes of one ``cap_bucket_scan`` call on ``rows`` rows
    of ``samples`` sorted samples against ``caps`` caps per row."""
    rp = _round_up(max(rows, 1), SUBLANES)
    np_ = _round_up(max(samples, 1), LANES)
    cp = _round_up(max(caps, 1), LANES)
    cp = _round_up(cp, min(cp, BLOCK_C))
    return {"compares": rp * np_ * cp,
            "bytes": WORD * (2 * rp * np_ + 2 * rp * cp + rp * cp)}
