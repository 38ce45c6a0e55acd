"""Regression tests for the §Perf optimizations (EXPERIMENTS.md):
chunked attention, bf16 error-feedback state, GQA-native decode, seq-parallel
KV layout, exact_small_leaves, torus gossip, int8 qsgd wire."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.models import build_model

KEY = jax.random.PRNGKey(0)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str, timeout=420):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("arch", [
    "yi-9b",
    # fwd+bwd through the chunked scan is 15-25s each on the bigger
    # configs — slow tier; yi-9b keeps the parity check in the fast tier
    pytest.param("gemma2-9b", marks=pytest.mark.slow),
    pytest.param("qwen3-moe-30b-a3b", marks=pytest.mark.slow),
])
def test_chunked_attention_matches_naive(arch):
    """attn_impl=chunked (flash-style scan) == naive attention, fwd + bwd."""
    cfg = get_config(arch, smoke=True)
    cfg_c = dataclasses.replace(cfg, attn_impl="chunked", attn_chunk=8)
    m1, m2 = build_model(cfg), build_model(cfg_c)
    params = m1.init(KEY)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    l1, _ = jax.jit(m1.loss)(params, batch)
    l2, _ = jax.jit(m2.loss)(params, batch)
    assert abs(float(l1) - float(l2)) < 0.02, arch
    g1 = jax.grad(lambda p: m1.loss(p, batch)[0])(params)
    g2 = jax.grad(lambda p: m2.loss(p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=0.05, rtol=0.1)


def test_qsgd_int8_wire_format():
    from repro.core.compression import QSGD
    pl = QSGD(16).compress(KEY, jax.random.normal(KEY, (256,)))
    assert pl.codes.dtype == jnp.int8
    pl = QSGD(256).compress(KEY, jax.random.normal(KEY, (256,)))
    assert pl.codes.dtype == jnp.int16


def test_chunked_leaf_compression_matches_direct():
    """Row-block compression (huge-leaf path) preserves the contraction."""
    from repro.comm.gossip import _compress_leaf, BLOCK_COMPRESS_SIZE
    from repro.core.compression import TopK
    d = BLOCK_COMPRESS_SIZE + 12345        # forces the chunked path
    x = jax.random.normal(KEY, (d,))
    comp = TopK(fraction=0.01)
    pl, dfn = _compress_leaf(comp, None, x)
    q = dfn(pl)
    assert q.shape == x.shape
    err = float(jnp.sum((q - x) ** 2))
    assert err <= (1 - comp.omega(d)) * float(jnp.sum(x * x)) * 1.01
    # per-row k: the padded tail row keeps all its real coords (they beat the
    # zero padding), so the bound is k_per_row * n_rows
    nnz = int(jnp.sum(q != 0))
    k_per_row = -(-BLOCK_COMPRESS_SIZE // 100)
    assert 0 < nnz <= 2 * k_per_row


@pytest.mark.slow
@pytest.mark.distributed
def test_bf16_ef_state_trainer():
    run_sub("""
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("yi-9b", smoke=True)
        m = build_model(cfg)
        tr = DecentralizedTrainer(model=m,
            choco=ChocoConfig(state_dtype="bfloat16"), mesh=mesh, n_nodes=4,
            optimizer=sgd(), lr_fn=constant_schedule(0.05))
        state = tr.init_state(jax.random.PRNGKey(0))
        assert jax.tree.leaves(state.x_hat)[0].dtype == jnp.bfloat16
        nb = make_lm_batch_fn(cfg, 32, 4, 4)
        b = jax.tree.map(jnp.asarray, nb())
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: b))
        losses = []
        for i in range(15):
            state, mets = step(state, jax.tree.map(jnp.asarray, nb()))
            losses.append(float(mets["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]
        print("BF16 STATE OK", losses[0], "->", losses[-1])
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_torus_gossip_trainer():
    run_sub("""
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        tr = DecentralizedTrainer(model=m,
            choco=ChocoConfig(topology="torus"), mesh=mesh, n_nodes=4,
            optimizer=sgd(), lr_fn=constant_schedule(0.05))
        assert tr.torus and tr.gossip_axis == ("pod", "data")
        state = tr.init_state(jax.random.PRNGKey(0))
        nb = make_lm_batch_fn(cfg, 32, 4, 4)
        b = jax.tree.map(jnp.asarray, nb())
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: b))
        losses = []
        for i in range(10):
            state, mets = step(state, jax.tree.map(jnp.asarray, nb()))
            losses.append(float(mets["loss"]))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
        print("TORUS OK")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_exact_small_leaves_ships_dense():
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.core import TopK
        mesh = make_mesh((4, 2), ("data", "model"))
        x = {"big": jax.random.normal(jax.random.PRNGKey(0), (4, 4096)),
             "small": jax.random.normal(jax.random.PRNGKey(1), (4, 16))}
        zeros = jax.tree.map(jnp.zeros_like, x)
        ex = make_gossip_exchange(mode="choco", mesh=mesh,
                                  state_specs={"big": P("data", None),
                                               "small": P("data", None)},
                                  axis="data", compressor=TopK(fraction=0.01),
                                  gamma=0.1, exact_small_leaves=True,
                                  small_leaf_threshold=64)
        xn, xh, s = ex(jax.random.PRNGKey(0), x, zeros, jax.tree.map(jnp.zeros_like, x))
        # small leaf shipped exactly: x_hat == x after one round
        np.testing.assert_allclose(np.asarray(xh["small"]), np.asarray(x["small"]),
                                   rtol=1e-6)
        # big leaf compressed: x_hat sparse
        nnz = int(jnp.sum(xh["big"] != 0))
        assert nnz < x["big"].size * 0.05
        print("SMALL LEAVES OK")
    """)


def test_decode_gqa_native_uniform_positions():
    """Scalar-position cache write: all batch rows share the decode slot."""
    cfg = get_config("yi-9b", smoke=True)
    m = build_model(cfg)
    params = m.init(KEY)
    B, s = 3, 10
    toks = jax.random.randint(KEY, (B, s), 0, cfg.vocab_size)
    cache = m.init_cache(B, s)
    dec = jax.jit(m.decode_step)
    for t in range(s):
        lg, cache = dec(params, toks[:, t:t + 1], cache, jnp.full((B,), t, jnp.int32))
    logits_pre, _ = jax.jit(m.prefill)(params, {"tokens": toks})
    np.testing.assert_allclose(np.asarray(lg, np.float32),
                               np.asarray(logits_pre, np.float32),
                               atol=0.05, rtol=0.05)
