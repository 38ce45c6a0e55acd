"""Pallas TPU kernel: flash attention (online-softmax, tiled, causal optional).

The dry-run roofline shows the memory term of every train/prefill shape is
dominated by materialised (B, H, S, S) attention weights; this kernel streams
K/V tiles through VMEM with running max/denominator so HBM traffic drops from
O(S^2) to O(S * Dh) per head — the standard flash recipe adapted to TPU tile
shapes (q block x k block multiples of 128 on the lane dim).

Layout: q, k, v are (S, Dh) per (batch, head) — the ops wrapper vmaps over
(B, H) and handles GQA head repetition.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, softcap,
                  block_q: int, block_k: int, seq_len: int, scale: float):
    qi = pl.program_id(0)
    q = q_ref[...].astype(jnp.float32) * scale           # (block_q, Dh)

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    q_pos = qi * block_q + jax.lax.iota(jnp.int32, block_q)

    def body(kj, carry):
        m, l, acc = carry
        k_tile = k_ref[pl.dslice(kj * block_k, block_k), :]
        v_tile = v_ref[pl.dslice(kj * block_k, block_k), :]
        logits = q @ k_tile.astype(jnp.float32).T        # (block_q, block_k)
        if softcap is not None:
            logits = softcap * jnp.tanh(logits / softcap)
        if causal:
            k_pos = kj * block_k + jax.lax.iota(jnp.int32, block_k)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        p = jnp.exp(logits - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + p @ v_tile.astype(jnp.float32)
        return m_new, l_new, acc_new

    n_k = seq_len // block_k
    if causal:
        # only tiles up to (and including) the diagonal contribute
        n_k_eff = jax.lax.div(qi * block_q + block_q - 1, block_k) + 1
    else:
        n_k_eff = n_k
    m, l, acc = jax.lax.fori_loop(0, n_k_eff, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "softcap", "block_q",
                                             "block_k", "interpret"))
def flash_attention_single(q, k, v, *, causal: bool = True, softcap=None,
                           block_q: int = 128, block_k: int = 128,
                           interpret=None):
    """q, k, v: (S, Dh) for one (batch, head).  Returns (S, Dh)."""
    S, Dh = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    scale = 1.0 / math.sqrt(Dh)
    kernel = functools.partial(_flash_kernel, causal=causal, softcap=softcap,
                               block_q=block_q, block_k=block_k, seq_len=S,
                               scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(S // block_q,),
        in_specs=[
            pl.BlockSpec((block_q, Dh), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K, V streamed with pl.load
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((block_q, Dh), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, Dh), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)


@functools.partial(jax.jit, static_argnames=("causal", "softcap", "interpret",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, softcap=None,
                    block_q: int = 128, block_k: int = 128,
                    interpret=None):
    """q: (B, S, H, Dh); k, v: (B, S, KV, Dh) (GQA: H % KV == 0).
    Returns (B, S, H, Dh)."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    rep = H // KV
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    fn = functools.partial(flash_attention_single, causal=causal,
                           softcap=softcap, block_q=block_q, block_k=block_k,
                           interpret=interpret)
    # vmap over batch then heads: (B, S, H, Dh) -> per (b, h) (S, Dh)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(fn))(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
