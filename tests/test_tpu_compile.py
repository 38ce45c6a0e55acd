"""Compile rehearsals for a TPU v5e that is described, not attached.

The main-path Pallas kernels and the pallas-backend gossip exchange are
compiled by the chip's own compiler (libtpu) against the ``v5e:2x2``
topology description, with ``interpret=False``.  What interpret mode
cannot show — a load from an HBM ref, a block the tiling refuses, a
kernel that will not partition under ``shard_map`` — fails here, at no
chip time.  Nothing runs: these tests say the programs compile, not that
they are correct or fast.

The topology is described inside a module fixture, never at import, so
every xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.  Shapes are the packed buckets
``make_bucket_spec`` gives for qwen3-1.7b cut to 4 layers (the one-chip
depth of ``chip_smoke.py``), one real bucket per kernel.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

N_LAYERS = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def buckets():
    """Packed bucket sizes of one node of the cut qwen3-1.7b: the tied
    embedding alone, and the largest per-layer bucket."""
    from repro.comm.packing import make_bucket_spec
    from repro.configs.base import get_config
    from repro.models import build_model
    cfg = dataclasses.replace(get_config("qwen3-1.7b"), n_layers=N_LAYERS)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    sizes = sorted(b.size for b in make_bucket_spec(shapes).buckets)
    assert sizes[-1] == cfg.vocab_size * cfg.d_model    # the tied embedding
    return {"embed": sizes[-1], "layer": sizes[-2]}


def _tiles(d, rows_multiple, dtype, sharding):
    rows = -(-d // (128 * rows_multiple)) * rows_multiple
    return jax.ShapeDtypeStruct((rows, 128), dtype, sharding=sharding)


def _kernel_case(name, buckets, one_chip):
    from repro.kernels.ef_update import ef_gossip_update
    from repro.kernels.qsgd import (qsgd_dequantize, qsgd_quantize_codes,
                                    signnorm_codes)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    if name == "ef_gossip_update":
        t = _tiles(buckets["embed"], 256, jnp.float32, one_chip)
        return (lambda *a: ef_gossip_update(*a, interpret=False),
                (t,) * 5 + (scalar,) * 3)
    t = _tiles(buckets["layer"], 8, jnp.float32, one_chip)
    if name == "qsgd_quantize_codes":
        return (lambda x, xi, inv: qsgd_quantize_codes(x, xi, inv, 16,
                                                        interpret=False),
                (t, t, scalar))
    if name == "signnorm_codes":
        return lambda x: signnorm_codes(x, interpret=False), (t,)
    codes = _tiles(buckets["layer"], 8, jnp.int8, one_chip)
    return (lambda c, s: qsgd_dequantize(c, s, interpret=False),
            (codes, scalar))


@pytest.mark.parametrize("name", ["ef_gossip_update", "qsgd_quantize_codes",
                                  "signnorm_codes", "qsgd_dequantize"])
def test_kernel_compiles_for_v5e(name, topo, one_chip, buckets):
    fn, args = _kernel_case(name, buckets, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_exchange_compiles_under_shard_map_on_4_chips(
        topo, buckets, monkeypatch):
    """The fused bucket-space CHOCO exchange (QSGD quantize kernel + EF
    kernel per bucket, ppermute on the ring) under ``shard_map`` on a
    4-chip mesh built from the described devices."""
    from repro.comm.gossip import make_gossip_exchange
    from repro.core.compression import QSGD
    from repro.kernels import ef_update, qsgd
    from repro.launch.mesh import make_mesh
    # the kernels resolve interpret mode from the attached backend (CPU
    # here); steer them to the compiled lowering for the described chip
    for mod in (ef_update, qsgd):
        monkeypatch.setattr(mod, "resolve_interpret",
                            lambda i: False if i is None else i)
    jax.clear_caches()
    mesh = make_mesh((4, 1), ("data", "model"), devices=topo.devices)
    specs = {"w": P("data", None), "b": P("data", None)}
    shard = lambda sp: NamedSharding(mesh, sp)
    state = {"w": jax.ShapeDtypeStruct((4, buckets["layer"]), jnp.float32,
                                       sharding=shard(specs["w"])),
             "b": jax.ShapeDtypeStruct((4, 2048), jnp.float32,
                                       sharding=shard(specs["b"]))}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=shard(P()))
    exchange = make_gossip_exchange(
        mode="choco", mesh=mesh, state_specs=specs, axis="data",
        compressor=QSGD(s=16), gamma=0.05, kernel_backend="pallas")
    compiled = jax.jit(exchange).lower(key, state, state, state).compile()
    text = compiled.as_text()
    jax.clear_caches()
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
