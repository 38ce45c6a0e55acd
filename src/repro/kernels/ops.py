"""Jit'd public wrappers around the Pallas kernels, shape-polymorphic over
flat vectors (pad + reshape to (R, 128) tiles internally)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .qsgd import qsgd_quantize, qsgd_dequantize, LANES
from .topk import block_topk_mask
from .ef_update import ef_gossip_update


def _to_tiles(x, rows_multiple: int = 8):
    """Flat (d,) -> padded (R, 128) with R % rows_multiple == 0."""
    d = x.size
    row_unit = LANES * rows_multiple
    pad = (-d) % row_unit
    xp = jnp.pad(x.ravel(), (0, pad))
    return xp.reshape(-1, LANES), d


def _from_tiles(t, d):
    return t.ravel()[:d]


@functools.partial(jax.jit, static_argnames=("s", "interpret"))
def qsgd_compress_vector(x, xi, s: int, *, interpret=None):
    """Flat qsgd: x, xi (d,) -> (codes int8/int16 (d,), scale)."""
    xt, d = _to_tiles(x)
    xit, _ = _to_tiles(xi)
    codes, scale = qsgd_quantize(xt, xit, s, interpret=interpret)
    return _from_tiles(codes, d), scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def qsgd_decompress_vector(codes, scale, *, interpret=None):
    """Flat qsgd dequantize: codes (d,), scale scalar -> f32 (d,)."""
    ct, d = _to_tiles(codes)
    return _from_tiles(qsgd_dequantize(ct, scale, interpret=interpret), d)


@functools.partial(jax.jit, static_argnames=("k_per_block", "interpret"))
def block_topk_compress_vector(x, k_per_block: int, *, interpret=None):
    """Flat block-top-k: select ~k_per_block per 128-lane row.
    Returns the masked dense q (same shape as x)."""
    xt, d = _to_tiles(x)
    mask, _ = block_topk_mask(xt, k_per_block, interpret=interpret)
    return _from_tiles(xt * mask, d)


@functools.partial(jax.jit, static_argnames=("k_per_block", "block"))
def block_topk_select(x, k_per_block: int, *, block: int = 128):
    """Flat blockwise top-k *payload extraction* — the pure-jnp REFERENCE
    path (``lax.top_k`` + gather, no Pallas kernel behind it).  It shares
    the selection rule with the ``block_topk_mask`` kernel, but where the
    mask kernel produces the dense masked q in one tiled pass, this emits
    the compact static-shape (values, indices) wire payload, which needs a
    gather the TPU kernel does not attempt; it stays jnp under every
    ``kernels/dispatch.py`` backend.

    x: (d,) -> (values (R, k), indices (R, k) int32) with R = ceil(d/block);
    the tail block is zero-padded, so padded positions carry zero values.
    """
    assert block % LANES == 0
    d = x.size
    R = -(-d // block)
    rows = jnp.pad(x.ravel(), (0, R * block - d)).reshape(R, block)
    _, idx = jax.lax.top_k(jnp.abs(rows), k_per_block)
    vals = jnp.take_along_axis(rows, idx, axis=1)
    return vals, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ef_gossip_update_vector(x_half, x_hat, s, q_self, q_nbr,
                            w_self, w_nbr, gamma, *, interpret=None):
    """Flat fused CHOCO update; all args (d,) f32."""
    tiles = [_to_tiles(a, rows_multiple=256)[0]
             for a in (x_half, x_hat, s, q_self, q_nbr)]
    d = x_half.size
    x, xh, sn = ef_gossip_update(*tiles, w_self, w_nbr, gamma,
                                 interpret=interpret)
    return (_from_tiles(x, d), _from_tiles(xh, d), _from_tiles(sn, d))


from .flash_attention import flash_attention  # noqa: E402,F401  (public re-export)
