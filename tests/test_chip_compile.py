"""The replay's device programs, compiled for a described TPU v5e.

Nothing runs: the TPU compiler that is installed here compiles each
program for a chip that is described, not attached, and refuses what the
chip's compiler would refuse (a block that breaks the (8, 128) tiling, a
float64 operand of a Pallas call, a gather Mosaic cannot lower). Covered:

* the cap-bucket Pallas kernel at the kernel bench's shape (256 rows x
  4096 sorted samples x 1024 caps);
* the ``downscale``, ``integrate`` and ``powercap`` programs of
  :func:`repro.whatif.backend._get_fn` at the packed bucket shapes of the
  what-if benches' corpus (64 devices x 3 h, seed 3), for the dense
  200-config grid and the 10^4-config grid. The power-cap program must
  hold the compiled kernel (``tpu_custom_call``) with no float64 operand;
* the sharded ``downscale`` and ``powercap`` programs of the 10^4-config
  grid on the described 2x2 host, config axis over four chips.

``jax.default_backend()`` sees the CPU here, so the tests force the
kernel's TPU branch themselves.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import run_replay as rr
from repro.whatif import backend as B

#: packed buckets of the 64-device x 3 h corpus (seed 3, min_samples 5,
#: no duration filter): (low runs K, runs R, rows N, cap width P) -> streams
CORPUS_BUCKETS = {
    (8, 8, 4096, 4096): 13,
    (16, 32, 1024, 1024): 1,
    (32, 64, 4096, 4096): 3,
    (64, 128, 8192, 4096): 5,
    (128, 256, 16384, 8192): 19,
    (256, 512, 16384, 8192): 42,
    (512, 1024, 16384, 16384): 13,
}
#: compiled here: the narrowest bucket, the one holding most streams, and
#: the widest
COMPILED_BUCKETS = [(8, 8, 4096, 4096), (256, 512, 16384, 8192),
                    (512, 1024, 16384, 16384)]
#: padded config axes per grid on one chip: (unique downscale (X, Y)
#: pairs, power caps); grid10k's 544 pairs and 7,901 caps fill whole
#: 128-lane tiles
GRID_CONFIG_PADS = {"dense200": (32, 128), "grid10k": (640, 7936)}
#: grid10k's on four chips: whole tiles on every chip need 512-lane
#: granules, so the power of two is as small
MESH_CONFIG_PADS = (1024, 8192)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The config-axis mesh of ``config_mesh(4)`` over the described
    chips (``config_mesh`` itself asks ``jax.devices()``, the CPU here)."""
    return Mesh(np.array(topo.devices[:4]), ("data",))


@pytest.fixture()
def pallas_branch(monkeypatch):
    """The dispatcher's TPU branch, and fresh jit wrappers for it."""
    monkeypatch.setattr(rr, "default_interpret", lambda: False)
    monkeypatch.setattr(B, "_FN_CACHE", {})


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pallas_calls(text: str) -> list[str]:
    """The type signature of each Pallas custom call in a lowered
    program's StableHLO text (the line's tail after the kernel body)."""
    return [line.rsplit(" : ", 1)[-1] for line in text.splitlines()
            if "tpu_custom_call" in line]


def test_cap_bucket_scan_compiles_at_kernel_bench_shape(one_chip):
    rows, n, c = 256, 4096, 1024
    i32 = [_sds(one_chip, (rows, n), jnp.int32)] * 2 + \
          [_sds(one_chip, (rows, c), jnp.int32)] * 2
    compiled = jax.jit(rr.cap_bucket_scan).lower(*i32).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _stream_args(sh, key, s_b):
    k, r, n, p = key
    f64, i64, i32 = jnp.float64, jnp.int64, jnp.int32
    return {
        "downscale": [_sds(sh, (s_b, k), i64), _sds(sh, (s_b, k), i64),
                      _sds(sh, (s_b, k), f64), _sds(sh, (s_b, k), bool),
                      _sds(sh, (s_b, k), bool), _sds(sh, (s_b, n + 1), i64),
                      _sds(sh, (s_b, 4, n + 1), f64), _sds(sh, (s_b,), f64),
                      _sds(sh, (), f64)],
        "integrate": [_sds(sh, (s_b, r), i32), _sds(sh, (s_b, r), f64),
                      _sds(sh, (s_b, r), i64), _sds(sh, (), i64)],
        "powercap": [_sds(sh, (s_b, 4, p), i32), _sds(sh, (s_b, 4, p), i32)],
    }


@pytest.mark.parametrize("key", COMPILED_BUCKETS)
@pytest.mark.parametrize("grid", sorted(GRID_CONFIG_PADS))
def test_downscale_program_compiles_at_corpus_shapes(one_chip, grid, key):
    pairs, _ = GRID_CONFIG_PADS[grid]
    with jax.enable_x64():
        args = _stream_args(one_chip, key, CORPUS_BUCKETS[key])
        compiled = B._get_fn("downscale", None).lower(
            *args["downscale"], _sds(one_chip, (pairs,), jnp.int64),
            _sds(one_chip, (pairs,), jnp.float64)).compile()
    assert compiled.as_text()


@pytest.mark.parametrize("key", COMPILED_BUCKETS)
@pytest.mark.parametrize("grid", sorted(GRID_CONFIG_PADS))
def test_powercap_program_compiles_with_pallas_kernel(one_chip, pallas_branch,
                                                      grid, key):
    s_b = CORPUS_BUCKETS[key]
    _, caps = GRID_CONFIG_PADS[grid]
    with jax.enable_x64():
        args = _stream_args(one_chip, key, s_b)
        lowered = B._get_fn("powercap", None).lower(
            *args["powercap"], _sds(one_chip, (s_b, caps), jnp.int32),
            _sds(one_chip, (s_b, caps), jnp.int32))
        compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    calls = _pallas_calls(lowered.as_text())
    assert calls and not any("f64" in c for c in calls), calls


@pytest.mark.parametrize("name", ["downscale", "powercap"])
def test_sharded_programs_compile_on_four_chip_mesh(four_chips, pallas_branch,
                                                    name):
    """The 10^4-config grid's programs with the config axis sharded over
    the 2x2 host (``dist=config_mesh(4)``), at the widest bucket."""
    key = COMPILED_BUCKETS[-1]
    pairs, caps = MESH_CONFIG_PADS
    s_b = CORPUS_BUCKETS[key]
    mesh = four_chips
    with jax.enable_x64():
        args = _stream_args(NamedSharding(mesh, P()), key, s_b)[name]
        if name == "downscale":
            cfg = NamedSharding(mesh, P("data"))
            args += [_sds(cfg, (pairs,), jnp.int64),
                     _sds(cfg, (pairs,), jnp.float64)]
        else:
            cfg = NamedSharding(mesh, P(None, "data"))
            args += [_sds(cfg, (s_b, caps), jnp.int32)] * 2
        text = B._get_fn(name, four_chips).lower(*args).compile().as_text()
    assert ("tpu_custom_call" in text) == (name == "powercap")
    # the config axis needs no communication between chips
    assert not any(op in text for op in ("all-gather", "all-reduce",
                                         "all-to-all", "collective-permute"))


@pytest.mark.parametrize("key", COMPILED_BUCKETS)
def test_integrate_program_compiles_at_corpus_shapes(one_chip, key):
    with jax.enable_x64():
        args = _stream_args(one_chip, key, CORPUS_BUCKETS[key])
        compiled = B._get_fn("integrate", None).lower(
            *args["integrate"]).compile()
    assert compiled.as_text()
