"""Bucketed flat-buffer packing for the CHOCO gossip exchange.

The wire format of the paper's Algorithm-2 messages q_i = Q(x_i - x_hat_i):
payload layout, wire-bit accounting, and the packed-vs-per-leaf launch
audit live in EXPERIMENTS.md §Perf A and §Perf D.

The per-leaf gossip path compresses and ppermutes every pytree leaf in a
Python loop — for a transformer that is dozens of top-k launches and
collective-permutes per round, exactly the launch-overhead regime Koloskova
et al. (2019/2020) say must be amortized for compressed gossip to win at
scale.  This module packs the whole parameter pytree into a small number of
dtype-homogeneous flat *buckets*:

  * the packing spec (bucket layout + per-leaf slots) is computed once from
    the pytree structure and reused every round — it depends only on static
    shape/dtype metadata, so it can be built from tracers or eval_shape;
  * leaf segments inside compressed buckets are padded to `align`-element
    boundaries (a multiple of the 128-lane TPU tile).  Blockwise compression
    commutes with block-aligned concatenation, so compressing a packed
    bucket ONCE (one Pallas/top-k launch) is bit-for-bit identical to
    compressing each leaf separately with the same blockwise operator;
  * tiny leaves (norm scales, biases) can be routed to an *exact* bucket —
    the per-leaf path's ``exact_small_leaves`` branch becomes a bucket
    routing rule — and ship uncompressed as one dense buffer;
  * each bucket emits ONE static-shape wire payload, so the whole exchange
    is a handful of collective-permutes per neighbour instead of one (or
    two) per leaf.

Layout rules: buckets are keyed by (dtype, exact?, route) and split when
they would exceed ``max_bucket_elems`` (bounds top_k width and latency).  A
single leaf larger than the cap cannot be split — it gets a dedicated
bucket, and the TopK path selects row-blockwise, so no top-k row ever
exceeds ``MAX_BUCKET_ELEMS`` lanes (int32-safe within-row indices).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression import (BlockTopK, Compressor, DensePayload,
                                    Identity, PackedQuantPayload,
                                    PackedSparsePayload, QSGD, RandK,
                                    SignNorm, SparsePayload, TopK, _resolve_k)
from repro.comm.stages import stage
from repro.kernels import dispatch as kdispatch

LANES = 128
#: default cap on bucket size — same constant the per-leaf path used for
#: row-blockwise chunking of huge leaves (int32-safe top_k, bounded latency)
MAX_BUCKET_ELEMS = 1 << 22


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one pytree leaf lives inside the packed buffers."""
    leaf: int                  # index in tree_flatten order
    bucket: int
    offset: int                # start offset inside the bucket buffer
    size: int                  # logical element count
    shape: Tuple[int, ...]
    dtype: Any


@dataclasses.dataclass(frozen=True)
class Bucket:
    index: int
    dtype: Any                 # buffer dtype (the EF-state dtype of its leaves)
    exact: bool                # ships uncompressed (DensePayload)
    size: int                  # padded buffer length
    logical: int               # sum of leaf sizes (excludes padding)


@dataclasses.dataclass(frozen=True)
class BucketSpec:
    treedef: Any
    slots: Tuple[LeafSlot, ...]
    buckets: Tuple[Bucket, ...]
    align: int                 # segment alignment inside compressed buckets

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def bucket_slots(self, b: int) -> List[LeafSlot]:
        return [s for s in self.slots if s.bucket == b]


def _round_up(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def make_bucket_spec(tree, *, align: int = LANES,
                     exact_small_leaves: bool = False,
                     small_leaf_threshold: int = 8_192,
                     max_bucket_elems: int = MAX_BUCKET_ELEMS,
                     routes: Optional[Sequence] = None) -> BucketSpec:
    """Build the packing spec from a pytree of arrays / ShapeDtypeStructs.

    Only .shape/.dtype are read, so `tree` may hold tracers or eval_shape
    results; the spec is pure static metadata, computed once and reused.

    routes: optional per-leaf hashable routing keys (tree_flatten order).
    Leaves only share a bucket when their route matches.  The gossip layer
    routes by each leaf's replication signature over non-gossip mesh axes:
    mixing a model-SHARDED leaf and a model-REPLICATED leaf in one bucket
    would make bucket-level selection (top-k order, qsgd norm) differ across
    model shards and silently de-replicate the replicated leaf.
    """
    assert align % LANES == 0, "segment alignment must be a lane multiple"
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if routes is not None:
        assert len(routes) == len(leaves), (len(routes), len(leaves))
    # open bucket per (dtype, exact, route) key: [bucket_index, cursor]
    open_buckets = {}
    slots: List[LeafSlot] = []
    buckets: List[List] = []   # [dtype, exact, cursor(=padded size), logical]
    for i, leaf in enumerate(leaves):
        size = 1
        for dim in leaf.shape:
            size *= dim
        dtype = jnp.dtype(leaf.dtype)
        exact = bool(exact_small_leaves and size <= small_leaf_threshold)
        seg = size if exact else _round_up(size, align)
        key = (dtype.name, exact, None if routes is None else routes[i])
        b = open_buckets.get(key)
        if b is None or (buckets[b][2] + seg > max_bucket_elems
                         and buckets[b][2] > 0):
            b = len(buckets)
            buckets.append([dtype, exact, 0, 0])
            open_buckets[key] = b
        slots.append(LeafSlot(leaf=i, bucket=b, offset=buckets[b][2],
                              size=size, shape=tuple(leaf.shape), dtype=dtype))
        buckets[b][2] += seg
        buckets[b][3] += size
    return BucketSpec(
        treedef=treedef,
        slots=tuple(slots),
        buckets=tuple(Bucket(index=i, dtype=d, exact=e, size=c, logical=l)
                      for i, (d, e, c, l) in enumerate(buckets)),
        align=align)


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------

def pack_leaves(spec: BucketSpec, flat_leaves: Sequence[jax.Array]
                ) -> List[jax.Array]:
    """Flat per-leaf vectors -> one padded flat buffer per bucket.

    One concatenate per bucket; segment padding is zero (blockwise top-k
    never prefers a zero over a real coordinate, qsgd codes zeros to zero).
    """
    parts: List[List[jax.Array]] = [[] for _ in spec.buckets]
    cursors = [0] * len(spec.buckets)
    for slot in spec.slots:
        seg = flat_leaves[slot.leaf].ravel().astype(spec.buckets[slot.bucket].dtype)
        pad = (slot.offset - cursors[slot.bucket])
        if pad:
            parts[slot.bucket].append(
                jnp.zeros((pad,), spec.buckets[slot.bucket].dtype))
        parts[slot.bucket].append(seg)
        cursors[slot.bucket] = slot.offset + slot.size
    bufs = []
    for b, bucket in enumerate(spec.buckets):
        tail = bucket.size - cursors[b]
        if tail:
            parts[b].append(jnp.zeros((tail,), bucket.dtype))
        bufs.append(jnp.concatenate(parts[b]) if len(parts[b]) > 1
                    else parts[b][0])
    return bufs


def unpack_leaves(spec: BucketSpec, bufs: Sequence[jax.Array]
                  ) -> List[jax.Array]:
    """Bucket buffers -> flat per-leaf vectors (in slot dtype, slot order)."""
    out: List[Optional[jax.Array]] = [None] * len(spec.slots)
    for slot in spec.slots:
        seg = jax.lax.dynamic_slice_in_dim(bufs[slot.bucket], slot.offset,
                                           slot.size)
        out[slot.leaf] = seg.astype(slot.dtype)
    return out


def pack_pytree(spec: BucketSpec, tree) -> List[jax.Array]:
    """Pack a whole pytree (matching the spec's treedef) into the bucket
    buffers — ``pack_leaves`` plus the structure check."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    assert treedef == spec.treedef, "pytree structure does not match the spec"
    return pack_leaves(spec, leaves)


def unpack_pytree(spec: BucketSpec, bufs: Sequence[jax.Array]):
    """Inverse of :func:`pack_pytree`: bucket buffers back to a pytree with
    the spec's structure and per-leaf shapes/dtypes."""
    flats = unpack_leaves(spec, bufs)
    leaves = [f.reshape(s.shape) for f, s in zip(flats, sorted(
        spec.slots, key=lambda sl: sl.leaf))]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)


# ---------------------------------------------------------------------------
# per-bucket compression
# ---------------------------------------------------------------------------

def _slot_budget(compressor, slots, bucket: Bucket) -> int:
    """Sparse coordinate budget: resolved PER SLOT and summed, so the packed
    exchange keeps exactly the per-leaf path's budget (an absolute k means
    k per leaf, not k per bucket; fractions sum to the same total)."""
    if slots:
        k = sum(_resolve_k(s.size, compressor.k, compressor.fraction)
                for s in slots)
    else:
        k = _resolve_k(bucket.logical, compressor.k, compressor.fraction)
    return min(k, bucket.logical)


def _logical_positions(slots, bucket: Bucket) -> jax.Array:
    """Padded-buffer indices of the bucket's logical coordinates."""
    if not slots:
        return jnp.arange(bucket.logical)
    return jnp.concatenate([s.offset + jnp.arange(s.size) for s in slots])


def compress_bucket(compressor: Compressor, key, buf: jax.Array,
                    bucket: Bucket,
                    slots: Optional[Sequence[LeafSlot]] = None,
                    *, backend: str = "jnp"):
    """Compress one packed bucket buffer into a single wire payload.

    slots: the bucket's LeafSlots — lets sparse operators resolve their
    coordinate budget per leaf (matching the per-leaf path) and sample over
    logical positions only (never the alignment padding).

    backend: the resolved kernel backend ("jnp"/"pallas",
    kernels/dispatch.py) for the elementwise quantize math.  Only QSGD
    and SignNorm have a fused kernel; both backends are bit-exact, so
    the wire payload is identical either way.

    Dispatches to the block-kernel paths (one launch per bucket):
      * BlockTopK  -> batched blockwise top-k  (kernels/ops.block_topk_select)
      * TopK       -> exact top-k by threshold select
                      (kernels/ops.topk_threshold_select: the index set of
                      lax.top_k, listed by ascending index), k resolved per
                      slot; a bucket over MAX_BUCKET_ELEMS is cut into rows
                      of that width, each keeping an equal share of k
      * RandK      -> per-slot budget, sampled over logical positions only
      * QSGD       -> the int8/int16 quantize codes of kernels/qsgd.py
                      (fused pallas launch or the ref-exact jnp inline)
                      + a scale using the *logical* dim's tau
      * SignNorm   -> int8 sign codes + logical-mean scale
      * Identity / exact buckets -> the dense buffer itself
    Anything else falls back to the compressor's own flat compress() over
    the padded buffer.
    """
    if bucket.exact or isinstance(compressor, Identity):
        return DensePayload(buf)
    if isinstance(compressor, BlockTopK):
        return compressor.compress(key, buf)
    if isinstance(compressor, RandK):
        k = _slot_budget(compressor, slots, bucket)
        # sample over logical coordinates only — uniform sampling of the
        # padded buffer would ship guaranteed-zero padding positions
        logical = _logical_positions(slots, bucket)
        idx = logical[jax.random.permutation(key, bucket.logical)[:k]]
        vals = buf[idx]
        if compressor.rescale:
            vals = vals * (bucket.logical / k)
        return SparsePayload(vals, idx.astype(jnp.int32), buf.size)
    if isinstance(compressor, TopK):
        from repro.kernels.ops import topk_threshold_select
        k = _slot_budget(compressor, slots, bucket)
        if buf.size > MAX_BUCKET_ELEMS:
            # oversized single-leaf bucket (spec cannot split a leaf):
            # row-blockwise selection over rows of MAX_BUCKET_ELEMS —
            # bounded row width, int32-safe within-row indices
            n_rows = -(-buf.size // MAX_BUCKET_ELEMS)
            kb = max(1, -(-k // n_rows))
            rows = jnp.pad(buf, (0, n_rows * MAX_BUCKET_ELEMS - buf.size))
            vals, idx = topk_threshold_select(
                rows.reshape(n_rows, MAX_BUCKET_ELEMS), kb)
            return PackedSparsePayload(vals, idx, buf.size, MAX_BUCKET_ELEMS)
        vals, idx = topk_threshold_select(buf[None], k)
        return SparsePayload(vals[0], idx[0], buf.size)
    if isinstance(compressor, QSGD):
        # elementwise codes via kernels/dispatch.py (fused pallas launch
        # or the bit-exact jnp inline); the norm reduction stays here, on
        # the unpadded buffer, so both backends share it exactly.  Padding
        # quantizes to zero codes (|0|*s/norm + xi < 1 floors to 0).
        s = compressor.s
        x32 = buf.astype(jnp.float32)
        xi = jax.random.uniform(key, buf.shape)
        norm = jnp.sqrt(jnp.sum(jnp.square(x32)))
        inv_norm = jnp.where(norm == 0, 0.0, 1.0 / norm)
        # levels naturally bound by s (|x|/norm <= 1); int16 above s=127
        # exactly like QSGD.compress — int8 would silently halve large coords
        codes = kdispatch.qsgd_codes(x32, xi, inv_norm, s, backend=backend)
        # scale with the logical dimension's tau: zero padding contributes
        # nothing to the norm but would inflate tau if counted in d
        tau = compressor._tau(bucket.logical) if compressor.rescale else 1.0
        scale = norm / (s * tau)
        bits = int(math.ceil(math.log2(2 * s + 1))) + 1
        return PackedQuantPayload(codes, scale.astype(jnp.float32), bits,
                                  dim=bucket.size, logical=bucket.logical)
    if isinstance(compressor, SignNorm):
        x32 = buf.astype(jnp.float32)
        scale = jnp.sum(jnp.abs(x32)) / bucket.logical
        return PackedQuantPayload(kdispatch.sign_codes(x32, backend=backend),
                                  scale.astype(jnp.float32), 1,
                                  dim=bucket.size, logical=bucket.logical)
    return compressor.compress(key, buf)


def bucket_dense(payload, bucket: Bucket) -> jax.Array:
    """Dense q for one bucket, padded back to the full buffer length."""
    q = payload.dense()
    if q.size < bucket.size:
        q = jnp.pad(q, (0, bucket.size - q.size))
    return q[: bucket.size].astype(bucket.dtype)


def compress_bufs(compressor: Compressor, key, spec: BucketSpec,
                  bufs: Sequence[jax.Array], *, backend: str = "jnp"):
    """Compress already-packed bucket buffers.  Returns (payloads, q_bufs):
    one wire payload per bucket plus its dense q padded back to the full
    buffer length — the bucket-space twin of :func:`compress_packed`, used
    directly by the fused EF path (which keeps state in bucket space).

    Key salting is per bucket (``fold_in(key, bucket.index)``) for
    stochastic compressors on compressed buckets — identical to
    :func:`compress_packed`, so both paths draw the same wire bits.
    """
    payloads = []
    for bucket, buf in zip(spec.buckets, bufs):
        bkey = (jax.random.fold_in(key, bucket.index)
                if (compressor.stochastic and key is not None
                    and not bucket.exact) else None)
        payloads.append(compress_bucket(compressor, bkey, buf, bucket,
                                        spec.bucket_slots(bucket.index),
                                        backend=backend))
    q_bufs = [bucket_dense(p, b) for p, b in zip(payloads, spec.buckets)]
    return payloads, q_bufs


def compress_packed(compressor: Compressor, key, spec: BucketSpec,
                    flat_leaves: Sequence[jax.Array], *,
                    backend: str = "jnp"):
    """pack -> compress (once per bucket).  Returns (payloads, q_leaves):
    one payload per bucket plus the dense per-leaf q (for the local EF
    update), so local and remote integration use the SAME quantized values.
    """
    with stage("pack"):
        bufs = pack_leaves(spec, flat_leaves)
    with stage("compress"):
        payloads, q_bufs = compress_bufs(compressor, key, spec, bufs,
                                         backend=backend)
    with stage("unpack"):
        q_leaves = unpack_leaves(spec, q_bufs)
    return payloads, q_leaves


def payloads_dense_leaves(spec: BucketSpec, payloads) -> List[jax.Array]:
    """Received payloads -> flat per-leaf dense q (one unpack per exchange)."""
    return unpack_leaves(
        spec, [bucket_dense(p, b) for p, b in zip(payloads, spec.buckets)])


def bucket_omegas(spec: BucketSpec, compressor: Compressor) -> List[float]:
    """Per-bucket Assumption-1 omega, in bucket order.  Each bucket is
    compressed independently, so each is its own CHOCO-Gossip instance with
    its own contraction — this is what the per-bucket Theorem-2 stepsize
    (core.choco_gossip.GammaSpec) is evaluated against.  Exact buckets ship
    uncompressed (omega = 1); sparse coordinate budgets resolve per slot,
    exactly as compress_bucket does."""
    omegas = []
    for b in spec.buckets:
        if b.exact or isinstance(compressor, Identity):
            omegas.append(1.0)
        elif isinstance(compressor, (TopK, RandK)):
            k = _slot_budget(compressor, spec.bucket_slots(b.index), b)
            omegas.append(k / b.logical)
        else:
            omegas.append(compressor.omega(b.logical))
    return omegas


def bucket_omega_worst(spec: BucketSpec, compressor: Compressor) -> float:
    """Worst-case (smallest) Assumption-1 omega over the spec's compressed
    buckets.  A single global consensus stepsize is governed by the
    slowest-contracting bucket, so this is the omega it must be computed
    from (not a fixed representative dimension).  Exact buckets ship
    uncompressed (omega = 1) and never bind — unless every bucket is exact,
    in which case omega is exactly 1."""
    omegas = [w for b, w in zip(spec.buckets, bucket_omegas(spec, compressor))
              if not (b.exact or isinstance(compressor, Identity))]
    return min(omegas) if omegas else 1.0


def bucket_wire_bits(spec: BucketSpec, compressor: Compressor) -> List[int]:
    """Analytic bits-on-the-wire per bucket, in bucket order — the
    per-bucket twin of :func:`bucket_omegas`, consumed by the telemetry
    run header (``obs/metrics.py::bucket_telemetry``)."""
    bits = []
    for b in spec.buckets:
        if b.exact:
            bits.append(b.logical * jnp.dtype(b.dtype).itemsize * 8)
        elif isinstance(compressor, (TopK, RandK)):
            # mirrors compress_bucket: coordinate budget resolved per slot
            bits.append(sum(compressor.wire_bits(s.size)
                            for s in spec.bucket_slots(b.index)))
        elif isinstance(compressor, (BlockTopK, QSGD, SignNorm)):
            bits.append(compressor.wire_bits(b.logical))
        else:
            bits.append(compressor.wire_bits(b.size))
    return [int(x) for x in bits]


def bucket_selection(spec: BucketSpec, compressor: Compressor
                     ) -> List[Optional[dict]]:
    """How each bucket's top-k picks its coordinates, in bucket order —
    ``{"selection", "rows", "k"}`` (k kept per row) or None where nothing
    is selected by magnitude.  Mirrors :func:`compress_bucket`: TopK runs
    the sort-free threshold select, BlockTopK a ``lax.top_k`` (a sort)
    per ``block``-wide row."""
    out: List[Optional[dict]] = []
    for b in spec.buckets:
        if b.exact:
            out.append(None)
        elif isinstance(compressor, TopK):
            k = _slot_budget(compressor, spec.bucket_slots(b.index), b)
            rows = (-(-b.size // MAX_BUCKET_ELEMS)
                    if b.size > MAX_BUCKET_ELEMS else 1)
            out.append({"selection": "threshold", "rows": rows,
                        "k": max(1, -(-k // rows))})
        elif isinstance(compressor, BlockTopK):
            out.append({"selection": "sort",
                        "rows": -(-b.size // compressor.block),
                        "k": compressor._kb()})
        else:
            out.append(None)
    return out


def packed_wire_bits(spec: BucketSpec, compressor: Compressor) -> int:
    """Analytic bits-on-the-wire of one packed exchange (all buckets)."""
    return sum(bucket_wire_bits(spec, compressor))
