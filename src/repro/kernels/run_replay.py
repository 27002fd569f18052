"""Pallas TPU kernel for the run-level replay's cap-bucket scan.

The PowerCap run evaluator reduces every cap fraction to ``k = #{p >
cap}`` against a stream's *sorted* per-state power buckets
(:meth:`repro.whatif.ir.StreamIR.cap_buckets`): clipped energy, throttle
count and the cube-law penalty are then O(1) gathers into prefix sums.
This module provides that count for the JAX backend
(:mod:`repro.whatif.backend`):

* :func:`order_key_words` — the host-side encoding the kernel compares:
  each float64 becomes its order-preserving 64-bit integer key, split
  into an int32 high word and a biased int32 low word. The TPU compiler
  takes no float64 operand into a Pallas call, and rounding to float32
  would not be exact; comparing word pairs lexicographically is;
* :func:`cap_bucket_scan` — the Pallas kernel: 8-row blocks, each row
  counted against a block of caps by brute-force compares over the row
  in 128-lane chunks (no gather, which Mosaic cannot lower in VMEM);
* :func:`cap_bucket_scan_reference` — the pure-jnp oracle (vmapped
  ``searchsorted``), on float rows or on joined int64 keys;
* :func:`cap_bucket_counts` — the dispatcher the backend calls: the
  compiled Pallas kernel on TPU, the jnp reference over the joined keys
  elsewhere.

Rows may be *front-padded* with ``-inf`` (whose key is the smallest) to a
common bucket width: padding is never ``> cap``, so the counts stay those
of the real samples.
"""
from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_SUBLANES = 8
_LANES = 128
#: caps per grid step: an [8, 512] int32 accumulator is four vregs
_BLOCK_C = 512
_I32_MIN = np.iinfo(np.int32).min
_I32_MAX = np.iinfo(np.int32).max
_LOW_BIAS = 1 << 31
_MAGNITUDE = np.int64(0x7FFFFFFFFFFFFFFF)


def default_interpret() -> bool:
    """Run Pallas kernels in interpret mode? True everywhere but TPU, with
    a ``REPRO_PALLAS_INTERPRET=0/1`` env override for CI and debugging."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env.strip().lower() not in ("0", "false", "no", "")
    return jax.default_backend() != "tpu"


# --------------------------------------------------------------------------- #
# float64 <-> order-preserving int32 word pairs (host side)
# --------------------------------------------------------------------------- #
def order_key_words(x) -> tuple[np.ndarray, np.ndarray]:
    """``(hi, lo)`` int32 words whose lexicographic order is the float order.

    The IEEE bits ``b`` of a float64 map to the int64 key ``b`` (sign
    clear) or ``b ^ 0x7fff...`` (sign set), which orders like the floats;
    ``hi`` is its upper word and ``lo`` its lower word minus ``2**31``, so
    both compare as signed int32. ``-0.0`` is folded onto ``+0.0`` first,
    since the floats compare equal. NaN has no place in the order and is
    refused.
    """
    x = np.asarray(x, dtype=np.float64) + 0.0       # -0.0 + 0.0 == +0.0
    if np.isnan(x).any():
        raise ValueError("NaN has no order key")
    b = x.view(np.int64)
    k = b ^ ((b >> 63) & _MAGNITUDE)
    return ((k >> 32).astype(np.int32),
            ((k & 0xFFFFFFFF) - _LOW_BIAS).astype(np.int32))


def key_words_to_f64(hi, lo) -> np.ndarray:
    """Inverse of :func:`order_key_words` (``-0.0`` comes back ``+0.0``)."""
    k = (np.asarray(hi, np.int64) << 32) | (np.asarray(lo, np.int64)
                                            + _LOW_BIAS)
    return (k ^ ((k >> 63) & _MAGNITUDE)).view(np.float64)


def _join_key_words(hi, lo):
    """The int64 keys of word pairs, on the device (needs x64)."""
    if not jax.config.jax_enable_x64:
        raise ValueError("joining key words needs jax.enable_x64()")
    return (hi.astype(jnp.int64) << 32) | (lo.astype(jnp.int64) + _LOW_BIAS)


# --------------------------------------------------------------------------- #
# Pallas kernel
# --------------------------------------------------------------------------- #
def _cap_scan_kernel(sp_hi_ref, sp_lo_ref, cap_hi_ref, cap_lo_ref, k_ref):
    ch = cap_hi_ref[...]                          # [8, Cb]
    cl = cap_lo_ref[...]

    def chunk(j, acc):
        # int32 stride: a Python int would widen to int64 under x64
        off = pl.multiple_of(j * jnp.int32(_LANES), _LANES)
        ph = sp_hi_ref[:, pl.ds(off, _LANES)]     # [8, 128]
        plo = sp_lo_ref[:, pl.ds(off, _LANES)]
        for lane in range(_LANES):
            h = ph[:, lane:lane + 1]              # [8, 1], one per row
            lo = plo[:, lane:lane + 1]
            gt = (h > ch) | ((h == ch) & (lo > cl))
            acc = acc + gt.astype(jnp.int32)
        return acc

    n_chunks = sp_hi_ref.shape[1] // _LANES
    k_ref[...] = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_chunks), chunk,
                                   jnp.zeros(ch.shape, jnp.int32))


def _pad_to(a, rows: int, cols: int, fill: int, front: bool = False):
    r, c = a.shape
    if (r, c) == (rows, cols):
        return a
    col_pad = (cols - c, 0) if front else (0, cols - c)
    return jnp.pad(a, ((0, rows - r), col_pad), constant_values=fill)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def cap_bucket_scan(sp_hi, sp_lo, cap_hi, cap_lo, interpret: bool = False):
    """``k[r, c] = #{sorted_p[r, :] > caps[r, c]}`` via Pallas.

    Arguments are the :func:`order_key_words` of ``sorted_p`` [rows, Np]
    and ``caps`` [rows, C]; all four are int32. Returns int32 [rows, C],
    exactly ``Np - searchsorted(sorted_p[r], caps[r], side="right")``.
    Rows, samples and caps are padded to the (8, 128) tiling inside: a
    padded sample has the smallest key and a padded cap the largest, so
    neither adds to a count.
    """
    rows, n = sp_hi.shape
    c = cap_hi.shape[1]
    rp = _round_up(max(rows, 1), _SUBLANES)
    np_ = _round_up(max(n, 1), _LANES)
    cp = _round_up(max(c, 1), _LANES)
    cb = min(cp, _BLOCK_C)
    cp = _round_up(cp, cb)
    args = (_pad_to(sp_hi, rp, np_, _I32_MIN, front=True),
            _pad_to(sp_lo, rp, np_, _I32_MIN, front=True),
            _pad_to(cap_hi, rp, cp, _I32_MAX),
            _pad_to(cap_lo, rp, cp, _I32_MAX))
    # int32 block indices: a literal 0 would widen to int64 under x64
    row_spec = pl.BlockSpec((_SUBLANES, np_), lambda i, j: (i, jnp.int32(0)))
    cap_spec = pl.BlockSpec((_SUBLANES, cb), lambda i, j: (i, j))
    k = pl.pallas_call(
        _cap_scan_kernel,
        grid=(rp // _SUBLANES, cp // cb),
        in_specs=[row_spec, row_spec, cap_spec, cap_spec],
        out_specs=cap_spec,
        out_shape=jax.ShapeDtypeStruct((rp, cp), jnp.int32),
        interpret=interpret,
        name="cap_bucket_scan",
    )(*args)
    return k[:rows, :c]


def cap_bucket_scan_reference(sorted_p, caps):
    """Pure-jnp oracle: vmapped ``searchsorted(side="right")`` per row."""
    ub = jax.vmap(lambda sp, cv: jnp.searchsorted(sp, cv, side="right"))(
        sorted_p, caps)
    return (sorted_p.shape[1] - ub).astype(jnp.int32)


def cap_bucket_counts(sp_hi, sp_lo, cap_hi, cap_lo):
    """Backend dispatcher: the compiled Pallas kernel on TPU; elsewhere
    the reference over the joined int64 keys (interpret-mode Pallas is far
    slower than XLA:CPU searchsorted)."""
    if default_interpret():
        return cap_bucket_scan_reference(_join_key_words(sp_hi, sp_lo),
                                         _join_key_words(cap_hi, cap_lo))
    return cap_bucket_scan(sp_hi, sp_lo, cap_hi, cap_lo)
