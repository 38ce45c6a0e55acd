"""The dense decoder: pre-norm RMSNorm with a (1 + w) gain, rotary
embeddings, optional QK-norm, grouped-query causal attention, SwiGLU, tied
or untied head, and its mean next-token cross-entropy.  Attention runs
over blocks of queries and the loss over blocks of tokens so that one chip
holds a full-width step.

The helpers (:func:`rms`, :func:`rope`, :func:`attention`,
:func:`cross_entropy`) are for other families that share these parts.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

#: queries per attention block and tokens per loss block
BLOCK = 512
NORMS = ("final_norm", "ln1", "ln2", "q_norm", "k_norm")


def check(model: Dict) -> None:
    """Raise for a configuration this description leaves out."""
    want = {"family": "dense", "mlp_type": "swiglu", "causal": True,
            "attn_logit_softcap": None, "final_logit_softcap": None,
            "sliding_window": None, "local_global_pattern": 0,
            "scale_embed": False, "moe": None, "ssm": None, "hybrid": None,
            "frontend": None}
    bad = {k: model.get(k) for k, v in want.items() if model.get(k) != v}
    if bad:
        raise ValueError(f"bench/families/dense.py describes the dense "
                         f"decoder only; this configuration has {bad}")


# -- parameters --------------------------------------------------------------

def param_shapes(model: Dict) -> Dict:
    """One node's parameter shapes, as the nested dict the dense decoder
    holds them in (a node's own copy; the trainer stacks nodes first)."""
    D, V, F = model["d_model"], model["vocab_size"], model["d_ff"]
    H, KV, L = model["n_heads"], model["n_kv_heads"], model["n_layers"]
    Dh = model["head_dim"]
    embed = {"final_norm": (D,), "tok": (V, D)}
    if not model["tie_embeddings"]:
        embed["unembed"] = (D, V)
    attn = {"wq": (L, D, H * Dh), "wk": (L, D, KV * Dh),
            "wv": (L, D, KV * Dh), "wo": (L, H * Dh, D)}
    if model["qk_norm"]:
        attn["q_norm"] = (L, Dh)
        attn["k_norm"] = (L, Dh)
    layer = {"attn": attn, "ln1": (L, D), "ln2": (L, D),
             "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F),
                     "w_down": (L, F, D)}}
    return {"embed": embed, "stack": {"p0": layer}, "tail": {}}


def init_leaf(path: str, shape, key):
    """Norm gains start at zero, the embedding at N(0, 0.02), every other
    weight at N(0, 1 / fan_in)."""
    name = path.rsplit("/", 1)[-1]
    if name in NORMS:
        return jnp.zeros(shape, jnp.float32)
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "tok":
        return z * 0.02
    return z / math.sqrt(shape[-2])


def flops_per_token(model: Dict, seq_len: int) -> float:
    """Forward + backward operations per token, recomputation excluded.

    Matmuls: 6 per multiply-add of every weight a token passes through
    (the embedding gather is free, the head is a matmul).  Attention: the
    causal scores and the weighted sum, 2 matmuls of d_head per head
    over the keys a query sees, on average (S + 1) / 2, times 3 for
    forward and backward: 6 * L * H * Dh * (S + 1) / 2 * 2."""
    D, V, F = model["d_model"], model["vocab_size"], model["d_ff"]
    H, KV, L = model["n_heads"], model["n_kv_heads"], model["n_layers"]
    Dh = model["head_dim"]
    per_layer = D * H * Dh * 2 + D * KV * Dh * 2 + 3 * D * F
    matmul_params = L * per_layer + V * D
    attn = 6 * L * H * Dh * (seq_len + 1)
    return 6.0 * matmul_params + attn


# -- forward -----------------------------------------------------------------

def rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def rope(x, theta):
    """x: (B, S, heads, Dh); rotate-half rotary embedding."""
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, mm):
    """q (B,S,KV,R,Dh), k/v (B,S,KV,Dh): causal attention by query blocks."""
    B, S, KV, R, Dh = q.shape
    blk = min(BLOCK, S)
    nb = S // blk
    qb = jnp.moveaxis(q.reshape(B, nb, blk, KV, R, Dh), 1, 0)
    kpos = jnp.arange(S)

    def one(args):
        qi, i = args
        sc = mm("bqkrd,bskd->bkrqs", qi, k) / math.sqrt(Dh)
        qpos = i * blk + jnp.arange(blk)
        mask = kpos[None, :] <= qpos[:, None]
        sc = jnp.where(mask, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return mm("bkrqs,bskd->bqkrd", w, v)

    out = jax.lax.map(jax.checkpoint(one), (qb, jnp.arange(nb)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, KV * R * Dh)


def cross_entropy(h, head, labels, valid, mm):
    """Mean next-token cross-entropy of final hidden states h (B, S, D)
    under the head (D, V), by blocks of tokens."""
    B, S, D = h.shape
    if valid is None:
        valid = jnp.ones((B, S), jnp.float32)
    blk = min(BLOCK, S)
    nb = S // blk
    hc = jnp.moveaxis(h.reshape(B, nb, blk, D), 1, 0)
    lc = jnp.moveaxis(labels.reshape(B, nb, blk), 1, 0)
    vc = jnp.moveaxis(valid.reshape(B, nb, blk), 1, 0)

    def nll(args):
        hh, ll, vv = args
        logits = mm("bcd,dv->bcv", hh, head)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * vv)

    total = jnp.sum(jax.lax.map(jax.checkpoint(nll), (hc, lc, vc)))
    return total / jnp.maximum(jnp.sum(valid), 1.0)


def loss(params: Dict, tokens, labels, model: Dict, mm, valid=None):
    """Mean next-token cross-entropy of one node's batch (B, S)."""
    B, S = tokens.shape
    H, KV = model["n_heads"], model["n_kv_heads"]
    Dh, eps = model["head_dim"], model["norm_eps"]
    emb = params["embed"]
    x = jnp.take(emb["tok"], tokens, axis=0)

    def layer(x, p):
        a = p["attn"]
        h = rms(x, p["ln1"], eps)
        q = mm("bsd,de->bse", h, a["wq"]).reshape(B, S, H, Dh)
        k = mm("bsd,de->bse", h, a["wk"]).reshape(B, S, KV, Dh)
        v = mm("bsd,de->bse", h, a["wv"]).reshape(B, S, KV, Dh)
        if model["qk_norm"]:
            q = rms(q, a["q_norm"], eps)
            k = rms(k, a["k_norm"], eps)
        q = rope(q, model["rope_theta"]).reshape(B, S, KV, H // KV, Dh)
        k = rope(k, model["rope_theta"])
        o = attention(q, k, v, mm)
        x = x + mm("bse,ed->bsd", o, a["wo"])
        m = p["mlp"]
        y = rms(x, p["ln2"], eps)
        g = mm("bsd,df->bsf", y, m["w_gate"])
        u = mm("bsd,df->bsf", y, m["w_up"])
        x = x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["w_down"])
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["stack"]["p0"])
    h = rms(x, emb["final_norm"], eps)
    head = emb["tok"].T if model["tie_embeddings"] else emb["unembed"]
    return cross_entropy(h, head, labels, valid, mm)
