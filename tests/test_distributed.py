"""Distribution tests: sharding specs, MoE EP vs dense oracle, compression,
checkpoint elastic restore, cluster sim pipeline."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.compat import make_mesh, shard_map

from repro.configs import ASSIGNED_ARCHS, get_config, get_smoke_config
from repro.distributed import sharding as shd
from repro.distributed.compression import (compressed_psum, dequantize_int8,
                                           quantize_int8)
from repro.distributed.context import DistContext
from repro.models import api


def test_param_specs_cover_every_leaf():
    dist = DistContext()  # disabled: raw specs
    for arch in ASSIGNED_ARCHS:
        cfg = get_config(arch)
        abstract = api.abstract_params(cfg, ep_size=16)
        specs = shd.param_specs(abstract, dist)
        n_leaves = len(jax.tree.leaves(abstract))
        n_specs = len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P)))
        assert n_specs == n_leaves, arch


def test_quantize_roundtrip_small_error():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 0.01, (1000,)).astype(np.float32))
    q, scale, shape = quantize_int8(x)
    back = dequantize_int8(q, scale, shape)
    err = float(jnp.max(jnp.abs(back - x)))
    assert err <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-9


def test_compressed_psum_matches_exact_sum():
    """2-'pod' reduction through int8 + EF approximates the exact mean; the
    error-feedback residual equals the quantization error."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 host devices (run under dryrun XLA_FLAGS)")
    mesh = make_mesh((2,), ("pod",))
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(0, 1e-3, (2, 512)).astype(np.float32))

    def body(x, e):
        s, new_e = compressed_psum({"g": x}, "pod", {"g": e})
        return s["g"], new_e["g"]

    out, err = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("pod"), P("pod")), out_specs=P("pod"),
        check_vma=False))(g, jnp.zeros_like(g))
    exact = jnp.sum(g, axis=0)
    got = out[0]  # both pod shards hold the same sum
    assert float(jnp.max(jnp.abs(got - exact))) < 5e-5


def test_moe_ep_matches_dense_oracle():
    """Expert-parallel dispatch == dense all-experts compute (high capacity,
    2-way model mesh)."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 host devices")
    from repro.models import moe as moe_mod
    cfg = get_smoke_config("granite-moe-3b-a800m")
    mesh = make_mesh((1, 2), ("data", "model"))
    dist = DistContext(mesh=mesh, batch_axes=("data",), model_axis="model")
    key = jax.random.PRNGKey(0)
    p = moe_mod.init_moe_ffn(key, cfg, ep_size=2, n_layers=1)
    p = jax.tree.map(lambda a: a[0], p)  # single layer slice
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                          jnp.float32)

    dense_out, dense_aux = moe_mod.moe_ffn_dense(x, p, cfg)
    ep_out, ep_aux = jax.jit(
        lambda x: moe_mod.moe_ffn_ep(x, p, cfg, dist, capacity_factor=8.0))(x)
    np.testing.assert_allclose(np.asarray(ep_out, np.float32),
                               np.asarray(dense_out, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_checkpoint_elastic_reshard():
    """Save on 1 device, restore onto a 2-device mesh with shardings."""
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 host devices")
    from repro.train import checkpoint as ckpt
    from repro.train.optimizer import adamw
    cfg = get_smoke_config("qwen1.5-0.5b")
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw()
    state = opt.init(params)
    mesh = make_mesh((2, 1), ("data", "model"))
    dist = DistContext(mesh=mesh, batch_axes=("data",), model_axis="model")
    p_specs = shd.param_specs(params, dist)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 7, params, state)
        p2, s2, step = ckpt.restore(
            d, params, state,
            param_shardings=shd.named(dist, p_specs),
            opt_shardings=None)
        assert step == 7
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), rtol=1e-2,
                                       atol=1e-2)


def test_cluster_sim_pipeline_end_to_end():
    """Small cluster sample through the full analysis pipeline."""
    from repro.cluster import generate_cluster
    from repro.telemetry import analyze_fleet
    cs = generate_cluster(n_devices=6, horizon_s=2 * 3600, seed=3)
    fa = analyze_fleet(cs.frame, min_job_duration_s=1800)
    assert len(fa.jobs) >= 1
    assert 0.0 < fa.in_execution_time_fraction < 0.6
    assert fa.in_execution_energy_fraction < fa.in_execution_time_fraction
