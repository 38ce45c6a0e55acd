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


#: bits of the k-th largest magnitude settled per pass over the rows: each
#: pass reads the rows once and counts against 2**bits - 1 candidates
THRESHOLD_BITS_PER_PASS = 3


def _int_of(dtype):
    """Signed integer type of a float dtype's width (16 or 32 bits)."""
    return {2: jnp.int16, 4: jnp.int32}[jnp.dtype(dtype).itemsize]


def _mag_bits(x):
    """|x| as its bit pattern, widened to int32: monotone in |x| (-0.0
    maps to 0)."""
    return jax.lax.bitcast_convert_type(
        jnp.abs(x), _int_of(x.dtype)).astype(jnp.int32)


def _kth_largest_bits(rows3, k: int):
    """Per row of rows3 (R, S, 128), the largest int32 t with
    count(|x| bits >= t) >= k — the bit pattern of the k-th largest
    magnitude — settled most significant bit first,
    THRESHOLD_BITS_PER_PASS bits per pass.  Each pass is one fused
    compare-and-count over the rows: no sort, no materialised mask."""
    t = jnp.zeros((rows3.shape[0],), jnp.int32)
    hi = 8 * rows3.dtype.itemsize - 1       # the sign bit of |x| is clear
    while hi > 0:
        width = min(THRESHOLD_BITS_PER_PASS, hi)
        hi -= width
        a = _mag_bits(rows3)
        # counts fall as the candidate rises, so the candidates that still
        # keep k or more are exactly the first `taken` of them
        taken = sum((jnp.sum(a >= (t + (j << hi))[:, None, None],
                             axis=(1, 2), dtype=jnp.int32) >= k
                     ).astype(jnp.int32)
                    for j in range(1, 1 << width))
        t = t + (taken << hi)
    return t


@jax.jit
def _tie_cut(rows3, t, need):
    """Per row, the flat index just past the ``need``-th element whose
    magnitude equals the threshold (0 where ``need`` is 0): lax.top_k
    keeps the lower index among equal magnitudes.  Lane-row counts and
    their prefix sum find the lane-row holding that element; a prefix
    count over its 128 lanes finds the lane."""
    R, S, _ = rows3.shape
    eq = _mag_bits(rows3) == t[:, None, None]
    per_row = jnp.sum(eq, axis=2, dtype=jnp.int32)              # (R, S)
    incl = jnp.cumsum(per_row, axis=1)
    lrow = jnp.minimum(jnp.sum(incl < need[:, None], axis=1, dtype=jnp.int32),
                       S - 1)
    before = jnp.take_along_axis(incl - per_row, lrow[:, None], axis=1)[:, 0]
    lanes = rows3[jnp.arange(R), lrow]                          # (R, 128)
    lane_incl = jnp.cumsum(_mag_bits(lanes) == t[:, None], axis=1,
                           dtype=jnp.int32)
    lane = jnp.sum(lane_incl < (need - before)[:, None], axis=1,
                   dtype=jnp.int32)
    return jnp.where(need > 0, lrow * LANES + lane + 1, 0)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_threshold_select(rows, k: int):
    """Exact per-row top-k by magnitude without a sort.

    rows: (R, N) of a 16- or 32-bit float -> (values (R, k), indices
    (R, k) int32), the index set ``lax.top_k(|rows|, k)`` keeps — equal
    magnitudes resolved to the lower index, as lax.top_k does — listed
    in ascending index order.

    1. t, the k-th largest magnitude's bit pattern, by compare-and-count
       passes (:func:`_kth_largest_bits`).
    2. Keep ``|x| > t``, and of ``|x| == t`` the first ``k - count(> t)``
       by index; the index cut (:func:`_tie_cut`) is computed only when
       some row has more equal elements than it needs.
    3. Compaction: count the kept elements of every 128-lane row; a
       scatter-max at the counts' prefix sums and running maxima over
       the k output slots give each slot's lane-row and its rank there;
       one gather of that lane-row (512 bytes) per slot, packed into
       four 32-bit words, and population counts find the slot's lane
       and so its index and value.
    """
    R, N = rows.shape
    assert 1 <= k <= N, (k, N)
    S = -(-N // LANES)
    # lane-rows of 128: a flat bucket reshapes to this view for free; the
    # zero padding never wins a tie, since k <= N
    rows3 = jnp.pad(rows, ((0, 0), (0, S * LANES - N))).reshape(R, S, LANES)
    t = _kth_largest_bits(rows3, k)
    a = _mag_bits(rows3)
    n_gt = jnp.sum(a > t[:, None, None], axis=(1, 2), dtype=jnp.int32)
    n_eq = jnp.sum(a == t[:, None, None], axis=(1, 2), dtype=jnp.int32)
    need = k - n_gt
    # the no-tie branch derives its cut from `need`, so that under
    # shard_map both branches vary over the same mesh axes
    cut = jax.lax.cond(jnp.any(n_eq > need), _tie_cut,
                       lambda r, t_, n_: n_ * 0 + S * LANES,
                       rows3, t, need)
    # keeps XLA from moving the broadcast of `cut` into the branches, which
    # would materialise it at the rows' full size
    cut = jax.lax.optimization_barrier(cut)

    def kept(lanes, lrow, t, cut):
        """Selection mask of lane-rows (..., 128) whose row numbers are
        `lrow` (...), under per-row threshold t and tie cut."""
        a = _mag_bits(lanes)
        pos = lrow[..., None] * LANES + jax.lax.broadcasted_iota(
            jnp.int32, lanes.shape, lanes.ndim - 1)
        return (a > t) | ((a == t) & (pos < cut))

    row_no = jax.lax.broadcasted_iota(jnp.int32, (R, S), 1)
    per_row = jnp.sum(kept(rows3, row_no, t[:, None, None], cut[:, None, None]),
                      axis=2, dtype=jnp.int32)                   # (R, S)
    incl = jnp.cumsum(per_row, axis=1)
    # slot s lies in lane-row L(s) = 1 + the last j with incl[j] <= s, whose
    # slots start at incl[L(s) - 1]: one scatter-max of j + 1 at incl[j],
    # then running maxima over the k slots (no gather of single elements)
    ends = jnp.zeros((R, k + 1), jnp.int32).at[
        jnp.arange(R)[:, None], incl].max(row_no + 1,
                                          mode="promise_in_bounds")[:, :k]
    slot = jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)
    lrow = jax.lax.cummax(ends, axis=1)                          # (R, k)
    rank = slot - jax.lax.cummax(jnp.where(ends > 0, slot, 0), axis=1)

    def one_row(args):
        # one row at a time keeps the gathered (k, 128) lane-rows small
        row3, lrow_r, rank_r, t_r, cut_r = args
        lanes = row3.at[lrow_r].get(mode="promise_in_bounds")     # (k, 128)
        sel = kept(lanes, lrow_r, t_r, cut_r)
        lane = jax.lax.broadcasted_iota(jnp.int32, lanes.shape, 1)
        # each slot's lane-row mask as 4 words of 32 lanes
        bit = jnp.left_shift(jnp.uint32(1), (lane % 32).astype(jnp.uint32))
        words = [jnp.sum(jnp.where(sel & (lane // 32 == w), bit, 0), axis=1,
                         dtype=jnp.uint32) for w in range(LANES // 32)]
        # the rank-th set bit: its word, then halving within the word
        seen = jnp.zeros_like(rank_r)
        word = jnp.zeros_like(words[0])
        at = jnp.zeros_like(rank_r)
        r = rank_r
        for w, bits in enumerate(words):
            n = jax.lax.population_count(bits).astype(jnp.int32)
            here = (rank_r >= seen) & (rank_r < seen + n)
            word = jnp.where(here, bits, word)
            at = jnp.where(here, 32 * w, at)
            r = jnp.where(here, rank_r - seen, r)
            seen = seen + n
        for half in (16, 8, 4, 2, 1):
            low = jnp.right_shift(word, (at % 32).astype(jnp.uint32)) & (
                (1 << half) - 1)
            n = jax.lax.population_count(low).astype(jnp.int32)
            up = r >= n
            at = at + jnp.where(up, half, 0)
            r = r - jnp.where(up, n, 0)
        int_t = _int_of(lanes.dtype)
        value_bits = jnp.sum(jnp.where(
            lane == at[:, None],
            jax.lax.bitcast_convert_type(lanes, int_t).astype(jnp.int32), 0),
            axis=1)
        return (jax.lax.bitcast_convert_type(value_bits.astype(int_t),
                                             rows.dtype),
                lrow_r * LANES + at)

    values, idx = jax.lax.map(one_row, (rows3, lrow, rank, t, cut))
    return values, idx.astype(jnp.int32)


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
