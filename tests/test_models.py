"""Per-architecture smoke tests (reduced configs): forward/train step on CPU,
shape + finiteness assertions, prefill/decode consistency (deliverable f)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, ARCH_IDS
from repro.models import build_model

B, S = 2, 32
KEY = jax.random.PRNGKey(0)


def _batch(cfg):
    if cfg.family == "audio":
        return {"frame_embeds": jax.random.normal(KEY, (B, S, cfg.frontend.embed_dim)),
                "targets": jnp.zeros((B, S), jnp.int32),
                "mask": jnp.ones((B, S))}
    if cfg.family == "vlm":
        return {"patch_embeds": jax.random.normal(
                    KEY, (B, cfg.frontend.n_tokens, cfg.frontend.embed_dim)),
                "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size),
                "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)}
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    return {"tokens": toks, "labels": toks}


# the two recurrent-family smokes (mamba/rwkv scans) dominate fast-tier
# walltime — slow tier; every other family stays fast
_SLOW_SMOKES = {"zamba2-1.2b", "rwkv6-3b"}


@pytest.mark.parametrize("arch", [
    pytest.param(a, marks=pytest.mark.slow) if a in _SLOW_SMOKES else a
    for a in ARCH_IDS])
def test_smoke_forward_and_train_step(arch):
    """One forward + one SGD train step on the reduced config; asserts output
    shapes and no NaNs (assignment requirement)."""
    cfg = get_config(arch, smoke=True)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    m = build_model(cfg)
    params = m.init(KEY)
    batch = _batch(cfg)

    loss, metrics = jax.jit(m.loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), arch

    grads = jax.jit(jax.grad(lambda p: m.loss(p, batch)[0]))(params)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert bool(jnp.all(jnp.isfinite(g))), (arch, path)
    new_params = jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)
    loss2, _ = jax.jit(m.loss)(new_params, batch)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS if a != "hubert-xlarge"])
def test_prefill_decode_consistency(arch):
    """Token-by-token decode reproduces the full-sequence last-token logits."""
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(KEY)
    s = 12
    toks = jax.random.randint(KEY, (B, s), 0, cfg.vocab_size)
    if cfg.family == "vlm":
        batch = {"patch_embeds": jax.random.normal(
                     KEY, (B, cfg.frontend.n_tokens, cfg.frontend.embed_dim)),
                 "tokens": toks}
        logits_pre, _ = jax.jit(m.prefill)(params, batch)
        return  # decode continuation exercised for pure-text archs below
    logits_pre, caches = jax.jit(m.prefill)(params, {"tokens": toks})
    assert logits_pre.shape == (B, 1, cfg.vocab_size)

    cache = m.init_cache(B, s)
    dec = jax.jit(m.decode_step)
    lg = None
    for t in range(s):
        lg, cache = dec(params, toks[:, t:t + 1], cache,
                        jnp.full((B,), t, jnp.int32))
    a = np.asarray(lg, np.float32)
    b = np.asarray(logits_pre, np.float32)
    scale = max(np.abs(b).max(), 1.0)
    assert np.max(np.abs(a - b)) / scale < 0.05, arch


def test_audio_prefill_runs():
    cfg = get_config("hubert-xlarge", smoke=True)
    m = build_model(cfg)
    params = m.init(KEY)
    logits, _ = jax.jit(m.prefill)(
        params, {"frame_embeds": jax.random.normal(KEY, (B, S, cfg.frontend.embed_dim))})
    assert logits.shape == (B, 1, cfg.vocab_size)


def test_param_count_formulas():
    """Analytic n_params() tracks the actual initialised count (smoke cfgs)."""
    for arch in ARCH_IDS:
        cfg = get_config(arch, smoke=True)
        m = build_model(cfg)
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(m.init(KEY)))
        predicted = cfg.n_params()
        tol = 0.6 if cfg.family in ("ssm", "hybrid") else 0.35
        assert abs(actual - predicted) / actual < tol, \
            (arch, actual, predicted)
        # exact counter must match the real init bit-for-bit
        from repro.models.transformer import count_params
        assert count_params(cfg) == actual, arch


def test_full_config_param_counts():
    """Full configs hit their nameplate sizes."""
    expect = {"yi-9b": 8.8e9, "qwen3-moe-30b-a3b": 30.5e9,
              "llama4-maverick-400b-a17b": 398e9, "gemma2-9b": 9.2e9,
              "rwkv6-3b": 2.9e9, "llava-next-mistral-7b": 7.3e9,
              "gemma-7b": 8.5e9, "qwen3-1.7b": 1.7e9}
    from repro.models.transformer import count_params
    for arch, n in expect.items():
        got = count_params(get_config(arch))
        assert abs(got - n) / n < 0.12, (arch, got, n)


def test_gemma2_local_global_pattern():
    from repro.models.transformer import block_pattern
    cfg = get_config("gemma2-9b")
    pattern, repeat, tail = block_pattern(cfg)
    assert pattern == ("dense_local", "dense_global") and repeat == 21


def test_zamba2_shared_block_pattern():
    from repro.models.transformer import block_pattern
    cfg = get_config("zamba2-1.2b")
    pattern, repeat, tail = block_pattern(cfg)
    assert pattern == ("mamba",) * 5 + ("shared",)
    assert repeat == 6 and tail == ("mamba", "mamba")
    assert 6 * repeat + len(tail) == cfg.n_layers
