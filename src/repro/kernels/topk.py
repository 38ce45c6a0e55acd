"""Pallas TPU kernel: block-wise top-k selection mask.

Global top-k needs a full sort; the TPU-native adaptation picks the k largest
magnitudes *per VMEM block* via threshold bisection — pure vector compares and
reductions, no sort, one HBM pass.  Blockwise top-(k/nblocks) satisfies the
paper's Assumption 1 with omega = k/d exactly like global top_k (Stich et al.
2018, Lemma A.1 applied per block).

The kernel emits a {0,1} mask and the per-row thresholds; the ops wrapper
(ops.py) forms the masked dense q and the compact wire payload.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

LANES = 128
N_ITER = 24


def _block_topk_kernel(x_ref, k_ref, mask_ref, thresh_ref):
    x = x_ref[...]                       # (rows, C)
    k = k_ref[0]
    mag = jnp.abs(x)
    lo = jnp.zeros((x.shape[0],), jnp.float32)
    hi = jnp.max(mag, axis=1) + 1e-12

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((mag >= mid[:, None]).astype(jnp.int32), axis=1)
        ge = cnt >= k
        return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid)

    lo, hi = jax.lax.fori_loop(0, N_ITER, body, (lo, hi))
    mask_ref[...] = (mag >= lo[:, None]).astype(jnp.float32)
    thresh_ref[...] = lo


@functools.partial(jax.jit, static_argnames=("k", "interpret", "block_rows"))
def block_topk_mask(x, k: int, *, interpret=None, block_rows: int = 8):
    """x: (R, C) with C a multiple of 128.  Per-row top-k mask.
    Returns (mask (R,C) f32, thresholds (R,) f32)."""
    R, C = x.shape
    assert C % LANES == 0 and R % block_rows == 0, (R, C)
    grid = (R // block_rows,)
    mask, thresh = pl.pallas_call(
        _block_topk_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((block_rows, C), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows,), lambda i: (i,))],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32),
                   jax.ShapeDtypeStruct((R,), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, jnp.full((1,), k, jnp.int32))
    return mask, thresh
