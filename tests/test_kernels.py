"""Per-kernel shape/dtype sweeps: Pallas vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import run_replay as rr
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.rwkv6_scan import wkv6
from repro.kernels.ssm_scan import ssm_scan

KEY = jax.random.PRNGKey(0)

#: the same detection the public ops wrappers use: interpret everywhere
#: but TPU (``REPRO_PALLAS_INTERPRET`` overrides), so CPU-only CI runs
#: the whole suite green in interpret mode while TPU CI exercises the
#: compiled kernels with no test edits.
INTERPRET = rr.default_interpret()


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,h,kv,s,d", [
    (2, 4, 2, 256, 64), (1, 8, 1, 128, 128), (2, 2, 2, 512, 64),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, h, kv, s, d, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=INTERPRET)
    expect = ref.mha_reference(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


def test_flash_attention_uneven_heads():
    """GQA with q_per_kv=3 (hymba-like 25H/5KV pattern scaled down)."""
    q = jax.random.normal(KEY, (1, 6, 128, 64))
    k = jax.random.normal(KEY, (1, 2, 128, 64))
    v = jax.random.normal(KEY, (1, 2, 128, 64))
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=INTERPRET)
    expect = ref.mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# decode attention
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,h,kv,s,d,cl", [
    (2, 8, 2, 1024, 64, 700), (1, 4, 4, 512, 128, 512),
    (2, 2, 1, 512, 64, 1), (1, 16, 2, 2048, 64, 1500),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(b, h, kv, s, d, cl, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, d), dtype)
    kc = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    vc = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = decode_attention(q, kc, vc, cl, block_k=256, interpret=INTERPRET)
    expect = ref.decode_attention_reference(q, kc, vc, cl)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


# --------------------------------------------------------------------------- #
# rwkv6 wkv
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("b,h,s,kd,chunk", [
    (2, 3, 64, 16, 16), (1, 2, 128, 32, 32), (1, 1, 96, 64, 32),
    (2, 2, 64, 32, 64),  # chunk > s falls back to one chunk
])
def test_wkv6(b, h, s, kd, chunk):
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (b, h, s, kd))
    k = jax.random.normal(ks[1], (b, h, s, kd))
    v = jax.random.normal(ks[2], (b, h, s, kd))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, s, kd))) * 0.55 + 0.4
    u = jax.random.normal(ks[4], (h, kd)) * 0.1
    y, state = wkv6(r, k, v, w, u, chunk=chunk, interpret=INTERPRET)
    ye, se = ref.wkv6_reference(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(state), np.asarray(se), rtol=1e-3, atol=1e-3)


def test_wkv6_extreme_decay():
    """Decays near 0 and near 1 stay finite (log-space in-chunk form)."""
    b, h, s, kd = 1, 1, 64, 16
    ks = jax.random.split(KEY, 4)
    r = jax.random.normal(ks[0], (b, h, s, kd))
    k = jax.random.normal(ks[1], (b, h, s, kd))
    v = jax.random.normal(ks[2], (b, h, s, kd))
    w = jnp.where(jax.random.bernoulli(ks[3], 0.5, (b, h, s, kd)), 0.999, 1e-4)
    y, state = wkv6(r, k, v, w, u=jnp.zeros((h, kd)), chunk=32, interpret=INTERPRET)
    assert np.isfinite(np.asarray(y)).all()
    ye, _ = ref.wkv6_reference(r, k, v, w, jnp.zeros((h, kd)))
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=1e-3, atol=1e-3)


# --------------------------------------------------------------------------- #
# mamba selective scan
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("bsz,s,di,n,chunk,bi", [
    (2, 64, 32, 8, 16, 32), (1, 96, 64, 16, 32, 32), (2, 128, 128, 16, 32, 64),
])
def test_ssm_scan(bsz, s, di, n, chunk, bi):
    ks = jax.random.split(KEY, 5)
    u = jax.random.normal(ks[0], (bsz, s, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bsz, s, di)))
    a = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.5)
    b = jax.random.normal(ks[3], (bsz, s, n))
    c = jax.random.normal(ks[4], (bsz, s, n))
    y, h = ssm_scan(u, dt, a, b, c, chunk=chunk, block_i=bi, interpret=INTERPRET)
    ye, he = ref.ssm_scan_reference(u, dt, a, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(he), rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------- #
# rmsnorm
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("shape", [(4, 128), (3, 50, 128), (1, 7, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), shape[-1:], dtype)
    out = rmsnorm(x, w, interpret=INTERPRET)
    expect = ref.rmsnorm_reference(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **tol(dtype))


# --------------------------------------------------------------------------- #
# run-replay cap-bucket scan
# --------------------------------------------------------------------------- #
def _np_cap_counts(sorted_p, caps):
    sp = np.asarray(sorted_p)
    cv = np.asarray(caps)
    return np.stack([
        sp.shape[1] - np.searchsorted(sp[r], cv[r], side="right")
        for r in range(sp.shape[0])]).astype(np.int32)


def _pallas_cap_counts(sorted_p, caps):
    """The Pallas kernel on the host-built key words of float rows."""
    return np.asarray(rr.cap_bucket_scan(
        *rr.order_key_words(sorted_p), *rr.order_key_words(caps),
        interpret=INTERPRET))


def _dispatched_cap_counts(sorted_p, caps):
    with jax.enable_x64():
        return np.asarray(rr.cap_bucket_counts(
            *rr.order_key_words(sorted_p), *rr.order_key_words(caps)))


@pytest.mark.parametrize("rows,n,c", [(3, 17, 5), (1, 1, 7), (4, 256, 33),
                                      (2, 64, 1)])
def test_cap_bucket_scan(rows, n, c):
    rng = np.random.default_rng(rows * 1000 + n + c)
    sp = np.sort(rng.normal(0.0, 100.0, (rows, n)), axis=1)
    caps = rng.normal(0.0, 100.0, (rows, c))
    expect = _np_cap_counts(sp, caps)
    np.testing.assert_array_equal(_pallas_cap_counts(sp, caps), expect)
    with jax.enable_x64():
        np.testing.assert_array_equal(
            np.asarray(rr.cap_bucket_scan_reference(jnp.asarray(sp),
                                                    jnp.asarray(caps))),
            expect)


def test_cap_bucket_scan_ties_and_padding():
    """Exact ties follow ``side="right"`` (p > cap strictly), and -inf
    front-padding — how the replay backend widens ragged power buckets —
    never changes the counts."""
    sp = np.asarray([[1.0, 2.0, 2.0, 2.0, 3.0, 3.0]])
    caps = np.asarray([[0.5, 2.0, 3.0, 4.0, 1.0]])
    expect = np.array([[6, 2, 0, 0, 5]], np.int32)
    padded = np.concatenate([np.full((1, 5), -np.inf), sp], axis=1)
    for fn in (_pallas_cap_counts, _dispatched_cap_counts):
        np.testing.assert_array_equal(fn(sp, caps), expect)
        np.testing.assert_array_equal(fn(padded, caps), expect)


def test_cap_bucket_scan_exact_against_reference():
    """Bit-identical to the float64 reference on the cases a key encoding
    can get wrong: -inf front padding, caps equal to sample values, signed
    zeros, negative values, rows holding one real sample, and caps that
    differ from a sample in the last bit only. Values stay normal: XLA:CPU
    flushes subnormals to zero in the reference, while the key words order
    them exactly as NumPy does."""
    width, c = 9, 11
    x = 137.25
    rows = [
        [-np.inf] * 8 + [250.0],                      # one real sample
        [-np.inf] * 6 + [-0.0, 0.0, 0.0],
        [-np.inf] * 4 + [-3.5, -0.0, 0.0, 1e-300, x],
        [-1e300, -2.0, -2.0, -2.3e-308, 0.0, x,
         np.nextafter(x, np.inf), 4e5, 1e300],
        [-np.inf] * 8 + [0.0],                        # one zero sample
    ]
    cap_rows = [
        [250.0, np.nextafter(250.0, 0), np.nextafter(250.0, np.inf), 0.0,
         -0.0, -np.inf, np.inf, 1e300, -1e300, 249.0, 251.0],
        [0.0, -0.0, -1e-300, 1e-300, 1.0, -1.0, 0.0, -0.0, np.inf,
         -np.inf, 5.0],
        [x, np.nextafter(x, 0), -0.0, 0.0, -3.5, 1e-300, -np.inf, np.inf,
         -4.0, 200.0, 2.3e-308],
        [x, np.nextafter(x, np.inf), -2.0, 0.0, -0.0, 1e300, -1e300, 4e5,
         -1e-300, 3.0, -np.inf],
        [-0.0, 0.0, 1e-307, -1e-307, 1.0, -1.0, np.inf, -np.inf, 0.0,
         -0.0, 2.0],
    ]
    sp = np.asarray(rows, np.float64)
    caps = np.asarray(cap_rows, np.float64)
    assert sp.shape == (5, width) and caps.shape == (5, c)
    with jax.enable_x64():
        expect = np.asarray(rr.cap_bucket_scan_reference(jnp.asarray(sp),
                                                         jnp.asarray(caps)))
    np.testing.assert_array_equal(expect, _np_cap_counts(sp, caps))
    np.testing.assert_array_equal(_pallas_cap_counts(sp, caps), expect)
    np.testing.assert_array_equal(_dispatched_cap_counts(sp, caps), expect)


def test_order_key_words_roundtrip_and_order():
    rng = np.random.default_rng(5)
    x = np.concatenate([rng.normal(0, 1e3, 500), rng.normal(0, 1e-200, 50),
                        [-np.inf, np.inf, 0.0, -0.0, 5e-324, -5e-324,
                         1.7e308, -1.7e308]])
    hi, lo = rr.order_key_words(x)
    assert hi.dtype == lo.dtype == np.int32
    back = rr.key_words_to_f64(hi, lo)
    np.testing.assert_array_equal(back, x)     # -0.0 == 0.0 compares equal
    # the lexicographic word order is the float order, ties included
    order = np.lexsort((lo, hi))
    np.testing.assert_array_equal(x[order], np.sort(x))
    with pytest.raises(ValueError, match="NaN"):
        rr.order_key_words([1.0, np.nan])


def test_cap_bucket_counts_dispatcher_and_ops_wrapper():
    """The dispatcher the backend calls matches the NumPy oracle, and its
    off-TPU branch refuses to run without x64 (it joins int64 keys)."""
    rng = np.random.default_rng(11)
    sp = np.sort(rng.normal(size=(5, 40)), axis=1)
    caps = rng.normal(size=(5, 9))
    expect = _np_cap_counts(sp, caps)
    np.testing.assert_array_equal(_dispatched_cap_counts(sp, caps), expect)
    if rr.default_interpret():
        with pytest.raises(ValueError, match="x64"):
            rr.cap_bucket_counts(*rr.order_key_words(sp),
                                 *rr.order_key_words(caps))


def test_default_interpret_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert rr.default_interpret() is True
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert rr.default_interpret() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "false")
    assert rr.default_interpret() is False
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert rr.default_interpret() is (jax.default_backend() != "tpu")
