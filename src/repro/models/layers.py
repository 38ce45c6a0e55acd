"""Shared neural-net layers: norms, rotary embeddings, attention (GQA/MQA,
qk-norm, logit softcap, sliding window, full cache & ring-buffer cache
decode), and gated MLPs.  Pure functions over explicit param dicts."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# initialisers / norms
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    """Fan-in-scaled normal init (LeCun-style) for dense weights."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)


def rms_norm(x, weight, eps: float):
    """RMSNorm with (1 + weight) gain, computed in f32."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(x.dtype)


def softcap(x, cap: Optional[float]):
    """Gemma-style tanh soft-capping; identity when cap is None."""
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float):
    """Inverse RoPE frequencies for a head dim under base theta."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, Dh); positions: (..., S) absolute positions."""
    Dh = x.shape[-1]
    inv = rope_frequencies(Dh, theta)                       # (Dh/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, Dh/2)
    sin, cos = jnp.sin(ang)[..., None, :], jnp.cos(ang)[..., None, :]  # (.., S, 1, Dh/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, dtype):
    """Init one attention block's params (GQA-aware, optional qk-norm)."""
    D, H, KV, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (D, H * Dh), dtype=dtype),
        "wk": dense_init(ks[1], (D, KV * Dh), dtype=dtype),
        "wv": dense_init(ks[2], (D, KV * Dh), dtype=dtype),
        "wo": dense_init(ks[3], (H * Dh, D), dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((Dh,), dtype)
        p["k_norm"] = jnp.zeros((Dh,), dtype)
    return p


def _repeat_kv(k, n_rep: int):
    """(B, S, KV, Dh) -> (B, S, KV*n_rep, Dh)"""
    if n_rep == 1:
        return k
    b, s, kv, dh = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, n_rep, dh)).reshape(b, s, kv * n_rep, dh)


def _attn_weights(q, k, cfg: ModelConfig, mask):
    """q: (B,Sq,H,Dh) k: (B,Sk,H,Dh) -> (B,H,Sq,Sk) softmax weights."""
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = softcap(logits.astype(jnp.float32), cfg.attn_logit_softcap)
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    return jax.nn.softmax(logits, axis=-1).astype(q.dtype)


def _chunked_attention(q, k, v, cfg: ModelConfig, positions, *, local: bool):
    """Flash-style attention: lax.scan over KV blocks with online softmax.
    Never materialises the (B, H, Sq, Sk) weight tensor — the pure-jnp
    analogue of kernels/flash_attention.py (which is the TPU target).
    q: (B,S,H,Dh); k,v: (B,S,H,Dh) (already GQA-repeated)."""
    B, S, H, Dh = q.shape
    blk = min(cfg.attn_chunk, S)
    assert S % blk == 0, (S, blk)
    nb = S // blk
    scale = 1.0 / math.sqrt(Dh)
    qf = q.astype(jnp.float32) * scale
    qpos = positions                                     # (B, S)

    kb = k.reshape(B, nb, blk, H, Dh)
    vb = v.reshape(B, nb, blk, H, Dh)
    pb = positions.reshape(B, nb, blk)

    def body(carry, inp):
        m, l, acc = carry                                # (B,H,S) (B,H,S) (B,H,S,Dh)
        k_t, v_t, p_t = inp                              # (B,blk,H,Dh) ..., (B,blk)
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k_t.astype(jnp.float32))
        logits = softcap(logits, cfg.attn_logit_softcap)
        mask = jnp.ones((B, 1, S, blk), bool)
        if cfg.causal:
            mask = p_t[:, None, None, :] <= qpos[:, None, :, None]
        if local and cfg.sliding_window is not None:
            mask = jnp.logical_and(
                mask, p_t[:, None, None, :] > qpos[:, None, :, None] - cfg.sliding_window)
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        pexp = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(pexp, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", pexp, v_t.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    body = jax.checkpoint(body)
    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    a0 = jnp.zeros((B, H, S, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.swapaxes(kb, 0, 1), jnp.swapaxes(vb, 0, 1), jnp.swapaxes(pb, 0, 1)),
        unroll=nb if cfg.scan_unroll else 1)   # exact HLO flops in dry-run
    out = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,H,S,Dh)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)       # (B,S,H,Dh)


NEG_INF = -1e30


def attention(p, x, cfg: ModelConfig, positions, *, local: bool = False,
              attn_mask: Optional[jax.Array] = None):
    """Full-sequence attention (train / prefill).

    positions: (B, S) absolute positions.  `local` selects the sliding-window
    mask (cfg.sliding_window).  Returns (out, (k, v)) so callers can build a
    KV cache during prefill.
    """
    B, S, D = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, Dh)
    k = (x @ p["wk"]).reshape(B, S, KV, Dh)
    v = (x @ p["wv"]).reshape(B, S, KV, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    kv_cache = (k, v)
    k, v = _repeat_kv(k, H // KV), _repeat_kv(v, H // KV)

    if cfg.attn_impl == "chunked" and attn_mask is None:
        out = _chunked_attention(q, k, v, cfg, positions, local=local)
        return out.reshape(B, S, H * Dh) @ p["wo"], kv_cache

    qpos, kpos = positions[:, None, :, None], positions[:, None, None, :]
    # mask (B, 1, Sq, Sk) -> broadcast over heads
    if cfg.causal:
        mask = kpos <= qpos
    else:
        mask = jnp.ones((B, 1, S, S), bool)
    if local and cfg.sliding_window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - cfg.sliding_window)
    if attn_mask is not None:
        mask = jnp.logical_and(mask, attn_mask)

    w = _attn_weights(q, k, cfg, mask)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, H * Dh)
    return out @ p["wo"], kv_cache


def decode_attention(p, x, cfg: ModelConfig, cache_k, cache_v, pos, *,
                     local: bool = False):
    """Single-token decode.  x: (B, 1, D); cache_k/v: (B, C, KV, Dh) where
    C = max_seq (global) or sliding_window (local ring buffer); pos: (B,)
    current absolute position.  Returns (out, new_cache_k, new_cache_v)."""
    B, _, D = x.shape
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    C = cache_k.shape[1]
    q = (x @ p["wq"]).reshape(B, 1, H, Dh)
    k = (x @ p["wk"]).reshape(B, 1, KV, Dh)
    v = (x @ p["wv"]).reshape(B, 1, KV, Dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k = apply_rope(k, pos[:, None], cfg.rope_theta)

    slot = (pos % C) if local else pos
    # Write the new k/v at `slot`.  Decode steps are batch-synchronous (all
    # requests share the position), so a single scalar-indexed
    # dynamic_update_slice is used: SPMD partitions it cleanly, whereas a
    # vmapped per-batch scatter forces GSPMD to all-gather the whole cache
    # (95 GB/step for gemma2 decode_32k — see EXPERIMENTS.md §Perf C2).
    z = jnp.zeros((), slot.dtype)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (z, slot[0], z, z))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (z, slot[0], z, z))

    # GQA-native grouped attention: contract the cache directly with the
    # grouped query tensor.  Broadcasting the cache to H heads (_repeat_kv)
    # makes GSPMD replicate the whole cache when KV < model-axis size
    # (95 GB/step all-gathers on gemma2 decode_32k — EXPERIMENTS.md §Perf C2).
    rep = H // KV
    qg = q.reshape(B, 1, KV, rep, Dh)
    scale = 1.0 / math.sqrt(Dh)
    logits = jnp.einsum("bqkrd,bckd->bkrqc", qg, cache_k) * scale
    logits = softcap(logits.astype(jnp.float32), cfg.attn_logit_softcap)
    idx = jnp.arange(C)[None, :]                      # (1, C) slot ids
    if local:
        filled = jnp.minimum(pos + 1, C)[:, None]
        mask = idx < filled                           # ring buffer: all filled slots valid
    else:
        mask = idx <= pos[:, None]
    mask = mask[:, None, None, None, :]               # (B,1,1,1,C)
    logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrqc,bckd->bqkrd", w, cache_v).reshape(B, 1, H * Dh)
    return out @ p["wo"], cache_k, cache_v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None):
    """Init MLP params for the configured type (swiglu/geglu/gelu)."""
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {"w_gate": dense_init(ks[0], (D, F), dtype=dtype),
                "w_up": dense_init(ks[1], (D, F), dtype=dtype),
                "w_down": dense_init(ks[2], (F, D), dtype=dtype)}
    return {"w_up": dense_init(ks[0], (D, F), dtype=dtype),
            "w_down": dense_init(ks[1], (F, D), dtype=dtype)}


def mlp(p, x, cfg: ModelConfig):
    """Apply the configured MLP (swiglu / geglu / plain gelu)."""
    if cfg.mlp_type == "swiglu":
        return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]
    if cfg.mlp_type == "geglu":
        return (jax.nn.gelu(x @ p["w_gate"], approximate=True) * (x @ p["w_up"])) @ p["w_down"]
    return jax.nn.gelu(x @ p["w_up"], approximate=True) @ p["w_down"]


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------

def init_embed(key, cfg: ModelConfig, dtype):
    """Init token embedding, final norm, and (untied) unembed params."""
    ks = jax.random.split(key, 2)
    p = {"tok": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype),
         "final_norm": jnp.zeros((cfg.d_model,), dtype)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dtype=dtype)
    return p


def embed_tokens(p, tokens, cfg: ModelConfig):
    """Token lookup (gemma-style sqrt(D) scaling when ``cfg.scale_embed``)."""
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.scale_embed:
        x = x * math.sqrt(cfg.d_model)
    return x


def logits_from_hidden(p, h, cfg: ModelConfig):
    """Final norm -> (tied or untied) unembed -> optional logit softcap."""
    h = rms_norm(h, p["final_norm"], cfg.norm_eps)
    w = p["tok"].T if cfg.tie_embeddings else p["unembed"]
    out = h @ w
    return softcap(out, cfg.final_logit_softcap)


def cross_entropy(logits, labels, valid=None):
    """Mean CE over valid positions.  logits (..., V), labels (...) int."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if valid is None:
        return jnp.mean(nll)
    valid = valid.astype(jnp.float32)
    return jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def chunked_lm_loss(p, h, labels, cfg: ModelConfig, valid=None):
    """Cross-entropy over the vocab computed in sequence chunks so the full
    (B, S, V) logits tensor is never materialised (beyond-paper memory opt,
    enabled via cfg.loss_chunk)."""
    if cfg.loss_chunk <= 0 or h.shape[1] % cfg.loss_chunk != 0:
        return cross_entropy(logits_from_hidden(p, h, cfg), labels, valid)
    B, S, D = h.shape
    n = S // cfg.loss_chunk
    hc = h.reshape(B, n, cfg.loss_chunk, D).transpose(1, 0, 2, 3)
    lc = labels.reshape(B, n, cfg.loss_chunk).transpose(1, 0, 2)
    vc = (valid.reshape(B, n, cfg.loss_chunk).transpose(1, 0, 2)
          if valid is not None else jnp.ones_like(lc, jnp.float32))

    def chunk_loss(args):
        hh, ll, vv = args
        logits = logits_from_hidden(p, hh, cfg)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, ll[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * vv), jnp.sum(vv)

    # rematerialised: without it the map saves every chunk's f32 log-softmax
    # for the backward pass, i.e. the full (B, S, V) logits after all
    sums, counts = jax.lax.map(jax.checkpoint(chunk_loss), (hc, lc, vc))
    return jnp.sum(sums) / jnp.maximum(jnp.sum(counts), 1.0)
