"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True everywhere but TPU (where the compiled
kernels are the target); ``REPRO_PALLAS_INTERPRET=0/1`` overrides the
detection (see :func:`repro.kernels.run_replay.default_interpret`), so
CPU-only CI can force interpret mode regardless of what
``jax.default_backend()`` reports. The wrappers also adapt between the
model-code layout (B, S, H, d) and the kernels' head-major layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import decode_attention as _da
from repro.kernels import run_replay as _rr
from repro.kernels import rwkv6_scan as _wkv
from repro.kernels import ssm_scan as _ssm
from repro.kernels import rmsnorm as _rms

#: canonical interpret-mode detection, shared with the run_replay kernel
_default_interpret = _rr.default_interpret


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q: (B,S,H,d); k/v: (B,S,KV,d) — model layout. Returns (B,S,H,d)."""
    interpret = _default_interpret() if interpret is None else interpret
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = _fa.flash_attention(qh, kh, vh, causal=causal, window=window,
                              block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, cache_len, block_k: int = 512,
                     interpret: bool | None = None):
    """q: (B,1,H,d); caches: (B,S,KV,d) — model layout. Returns (B,1,H,d)."""
    interpret = _default_interpret() if interpret is None else interpret
    qh = q[:, 0]                                   # (B,H,d)
    kh = jnp.swapaxes(k_cache, 1, 2)               # (B,KV,S,d)
    vh = jnp.swapaxes(v_cache, 1, 2)
    out = _da.decode_attention(qh, kh, vh, cache_len, block_k=block_k,
                               interpret=interpret)
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, chunk: int = 32, interpret: bool | None = None):
    """r/k/v/w: (B,S,H,K) model layout; u: (H,K). Returns ((B,S,H,K), state)."""
    interpret = _default_interpret() if interpret is None else interpret
    args = [jnp.swapaxes(t, 1, 2) for t in (r, k, v, w)]
    y, state = _wkv.wkv6(*args, u, chunk=chunk, interpret=interpret)
    return jnp.swapaxes(y, 1, 2), state


@functools.partial(jax.jit, static_argnames=("chunk", "block_i", "interpret"))
def ssm_scan(u, dt, a, b, c, chunk: int = 32, block_i: int = 256,
             interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _ssm.ssm_scan(u, dt, a, b, c, chunk=chunk, block_i=block_i,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, weight, eps: float = 1e-6, block_rows: int = 256,
            interpret: bool | None = None):
    interpret = _default_interpret() if interpret is None else interpret
    return _rms.rmsnorm(x, weight, eps=eps, block_rows=block_rows,
                        interpret=interpret)
