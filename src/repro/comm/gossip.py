"""Distributed CHOCO gossip over a device mesh, driven by compiled schedules.

Implements the source paper's Algorithm 2 lines 4-9 / Algorithm 5 (choco),
Algorithm 3 (plain), and the engine dispatch for the stochastic-process and
push-sum variants.  Wire audits: EXPERIMENTS.md §Perf D (bucketed payloads)
and §Perf E (schedule replay); the stochastic engines are audited in
§Perf F and the bounded-staleness engine in §Perf G.

The gossip graph lives on one or more mesh axes (``axes``): every slice of
the mesh along those axes is one "node" of the paper's communication graph.
The exchange is implemented inside ``shard_map`` with ``jax.lax.ppermute``
of the *compressed payload only* — the collective bytes in the compiled HLO
are the paper's transmitted bits.  Every tensor-parallel / FSDP shard
compresses and gossips its own slice (coordinate-wise operators commute with
sharding).

Which neighbours exchange, in how many rounds, with what weights, is no
longer hardcoded: a :class:`~repro.comm.schedule.GossipSchedule` (compiled
once, pure Python, from any ``core.topology.Topology``) lists the
permutation rounds of W − I, and this engine replays them — one
``lax.ppermute`` per round, every round reusing the same packed payloads.
Ring and torus are now just two compiled schedules; hypercube, star, chain,
fully-connected, and arbitrary W (via greedy edge coloring) run through the
identical code path.  A *sequence* of schedules gives time-varying mixing,
cycled across the ``gossip_steps`` consensus rounds of each SGD step
(multiple gossip rounds per step: Hashemi et al., NeurIPS 2020).

Two engines for the choco exchange:
  * ``packed`` (default) — the bucketed flat-buffer engine (comm/packing.py):
    the whole pytree is packed into a few dtype-homogeneous buckets, each
    compressed ONCE and shipped as ONE static-shape payload per neighbour —
    a handful of collective-permutes per round regardless of leaf count;
  * ``per-leaf`` (legacy) — compress + ppermute every leaf separately; kept
    as the reference/bench baseline (see benchmarks/bench_collectives.py).

Four exchange modes:
  * ``choco``     — Algorithm 2 lines 4-9 (compressed, error-feedback)
  * ``plain``     — Algorithm 3 line 4-5 (exact neighbour averaging)
  * ``allreduce`` — centralized mini-batch SGD baseline (pmean over the axes)
  * ``pushsum``   — directed column-stochastic mixing with the (x, w) weight
                    pair and de-biased x/w (comm/pushsum.py)

Stochastic topologies: choco and plain also accept a ``TopologyProcess``
(comm/stochastic.py) — a per-step distribution over mixing matrices
(randomized matchings sampled one round at a time, or i.i.d. Bernoulli link
failures).  Every node draws the identical sample from the shared exchange
key (fold_in, zero communication).  The compressed process engine
(:func:`make_process_choco_fn`) keeps per-round reference replicas instead
of the static engine's running aggregate s — see its docstring for why s is
unsound under time-varying W.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compression import Compressor
from repro.comm.schedule import GossipSchedule
from repro.comm.stages import stage

def _leaf_keys(key, n: int, salt: int):
    return jax.random.split(jax.random.fold_in(key, salt), n)


# Leaves larger than this are compressed row-blockwise: reshape to (R, BLOCK)
# and vmap the operator per row.  Identical omega guarantee (Assumption 1 per
# block), avoids int32 overflow in lax.top_k for multi-billion-element expert
# stacks, and matches the Pallas block-topk kernel's TPU-native semantics.
BLOCK_COMPRESS_SIZE = 1 << 22


def _compress_leaf(compressor: Compressor, key, flat):
    """Returns (payload, dense_fn) where dense_fn(payload) -> flat dense q."""
    d = flat.size
    if d <= BLOCK_COMPRESS_SIZE:
        pl_ = compressor.compress(key, flat)
        return pl_, lambda p: p.dense()
    C = BLOCK_COMPRESS_SIZE
    R = -(-d // C)
    padded = jnp.pad(flat, (0, R * C - d))
    rows = padded.reshape(R, C)
    if compressor.stochastic:
        keys = jax.random.split(key, R)
        pl_ = jax.vmap(compressor.compress)(keys, rows)
    else:
        pl_ = jax.vmap(lambda r: compressor.compress(None, r))(rows)

    def dense_fn(p):
        return jax.vmap(lambda q: q.dense())(p).reshape(R * C)[:d]

    return pl_, dense_fn


def _pack_align(compressor: Optional[Compressor], pack_align: Optional[int]):
    """Segment alignment for the packed engine: the compressor's block width
    for blockwise operators (so bucket compression commutes with packing),
    the 128-lane unit otherwise."""
    block = getattr(compressor, "block", None)
    if pack_align is None:
        return block or 128
    if block and pack_align % block != 0:
        raise ValueError(
            f"pack_align={pack_align} must be a multiple of the compressor's "
            f"block width {block}: blockwise selection must never straddle "
            f"leaf segments, or packed != per-leaf compression")
    return pack_align


def _leaf_routes(state_specs, gossip_axes) -> Optional[list]:
    """Per-leaf bucket-routing keys from the exchange's PartitionSpecs: the
    set of NON-gossip mesh axes each leaf is sharded over.  Leaves sharded
    differently (e.g. model-sharded weights vs model-replicated norm scales)
    must not share a bucket — bucket-level selection and scales would differ
    across those shards and de-replicate the replicated leaves."""
    if state_specs is None:
        return None
    gset = set(gossip_axes if isinstance(gossip_axes, (tuple, list))
               else (gossip_axes,))
    specs = jax.tree_util.tree_leaves(
        state_specs, is_leaf=lambda x: isinstance(x, P))
    routes = []
    for sp in specs:
        axes = set()
        if isinstance(sp, P):
            for entry in sp:
                if entry is None:
                    continue
                for a in (entry if isinstance(entry, tuple) else (entry,)):
                    axes.add(a)
        routes.append(tuple(sorted(axes - gset)))
    return routes


def _flatten_states(x_half, x_hat, s):
    leaves_h, treedef = jax.tree_util.tree_flatten(x_half)
    leaves_hat = treedef.flatten_up_to(x_hat)
    leaves_s = treedef.flatten_up_to(s)
    return leaves_h, leaves_hat, leaves_s, treedef


def _packed_self_half(compressor, key, leaves_h, leaves_hat, spec,
                      backend: str = "jnp"):
    """Send half of a packed choco round: deltas -> payloads, per-leaf
    dense q, and the updated public copies x_hat.  Factored so the serial
    and pipelined engines share one compress stage — the receive half
    (:func:`_neighbor_sum`) is a separate call, which keeps the collective's
    start/done free of any data dependency the caller does not create.
    ``backend`` is the resolved kernel backend for the quantize math
    (kernels/dispatch.py); both backends produce identical wire bytes."""
    from repro.comm.packing import compress_packed
    with stage("compress"):
        deltas = [(lh.astype(lhat.dtype) - lhat).ravel()
                  for lh, lhat in zip(leaves_h, leaves_hat)]
    payloads, q_leaves = compress_packed(compressor, key, spec, deltas,
                                         backend=backend)
    with stage("ef_update"):
        new_hat = [lhat + q.reshape(lh.shape).astype(lhat.dtype)
                   for lh, lhat, q in zip(leaves_h, leaves_hat, q_leaves)]
    return payloads, q_leaves, new_hat


def _per_leaf_self_half(compressor, identity, exact_small_leaves: bool,
                        small_leaf_threshold: int, tkey, leaves_h,
                        leaves_hat):
    """Send half of a legacy per-leaf choco round: compress every leaf's EF
    delta separately (tiny leaves optionally exact), advance x_hat.
    Returns (payloads, dense_fns, q_dense, new_hat) — the per-leaf twin of
    :func:`_packed_self_half`, shared by the serial and pipelined engines."""
    keys = _leaf_keys(tkey, len(leaves_h), 0)
    payloads, dense_fns, new_hat, q_dense = [], [], [], []
    for i, (lh, lhat) in enumerate(zip(leaves_h, leaves_hat)):
        with stage("compress"):
            # compress in the EF-state dtype: bf16 states -> bf16 wire
            delta = (lh.astype(lhat.dtype) - lhat).ravel()
            comp_i = (identity if exact_small_leaves
                      and delta.size <= small_leaf_threshold else compressor)
            pl, dfn = _compress_leaf(
                comp_i, keys[i] if comp_i.stochastic else None, delta)
            payloads.append(pl)
            dense_fns.append(dfn)
            qd = dfn(pl)
            q_dense.append(qd)
        with stage("ef_update"):
            new_hat.append(lhat + qd.reshape(lh.shape).astype(lhat.dtype))
    return payloads, dense_fns, q_dense, new_hat


def _choco_leaf_updates(leaves_h, leaves_s, q_leaves, nbr_leaves, new_hat,
                        w_self, w_nbr, gamma):
    """Algorithm 5 lines 8-10, per leaf (elementwise; XLA fuses these).
    ``gamma`` is a scalar or a per-leaf sequence (per-bucket Theorem-2
    stepsizes resolved by :func:`_resolve_leaf_gammas`)."""
    gammas = _broadcast_gammas(gamma, len(leaves_h))
    new_s, new_x = [], []
    with stage("ef_update"):
        for lh, ls, qd, nb, nh, g in zip(leaves_h, leaves_s, q_leaves,
                                         nbr_leaves, new_hat, gammas):
            sn = ls + (w_self * qd + w_nbr * nb).reshape(
                lh.shape).astype(ls.dtype)
            new_s.append(sn)
            new_x.append(lh + g * (sn - nh).astype(lh.dtype))
    return new_s, new_x


def _broadcast_gammas(gamma, n_leaves: int):
    """Scalar gamma -> n_leaves copies; a per-leaf list passes through."""
    if isinstance(gamma, (list, tuple)):
        assert len(gamma) == n_leaves, (len(gamma), n_leaves)
        return list(gamma)
    return [gamma] * n_leaves


def _resolve_bucket_gammas(gamma, spec, compressor: Compressor):
    """Per-BUCKET consensus stepsizes, in bucket order.  A plain float
    broadcasts; a :class:`~repro.core.choco_gossip.GammaSpec` derives
    Theorem 2 from each bucket's own omega (each bucket is an independent
    coordinate-wise CHOCO instance), so exact buckets (omega = 1) stop
    being dragged down to the worst top-k bucket's contraction and vice
    versa.  Consumed directly by the fused bucket-space EF path."""
    from repro.core.choco_gossip import GammaSpec
    if not isinstance(gamma, GammaSpec):
        return [gamma] * spec.n_buckets
    from repro.comm.packing import bucket_omegas
    omegas = bucket_omegas(spec, compressor)
    return [gamma.value(w) for w in omegas]


def _resolve_leaf_gammas(gamma, spec, compressor: Compressor):
    """Per-leaf consensus stepsizes for the packed engine: each leaf
    inherits its bucket's gamma (:func:`_resolve_bucket_gammas`), in
    tree_flatten order.  A plain float passes through unchanged."""
    from repro.core.choco_gossip import GammaSpec
    if not isinstance(gamma, GammaSpec):
        return gamma
    by_bucket = _resolve_bucket_gammas(gamma, spec, compressor)
    return [by_bucket[slot.bucket]
            for slot in sorted(spec.slots, key=lambda sl: sl.leaf)]


def _fused_update_ok(spec, leaves_h, leaves_s) -> bool:
    """Whether the fused bucket-space EF path applies: every bucket buffer,
    every packed slot, and every (x, s) state leaf must already be float32.
    Then pack/unpack are pure copies (no dtype rounding), bucket-space
    subtraction commutes with packing, and the fused path computes the
    exact per-leaf update algebra on the bucket buffers.  Mixed-precision
    EF states (bf16 x_hat) keep the leaf path, with the pallas backend
    still fusing the quantize."""
    f32 = jnp.dtype(jnp.float32)
    return (all(jnp.dtype(b.dtype) == f32 for b in spec.buckets)
            and all(jnp.dtype(sl.dtype) == f32 for sl in spec.slots)
            and all(jnp.dtype(l.dtype) == f32 for l in leaves_h)
            and all(jnp.dtype(l.dtype) == f32 for l in leaves_s))


# ---------------------------------------------------------------------------
# schedule plumbing
# ---------------------------------------------------------------------------

def _weight_groups(schedule: GossipSchedule):
    """Consecutive rounds sharing one receive weight merge into a group:
    their dense payloads accumulate unweighted and the weight applies once.
    (A uniform ring's +1/-1 shifts are one group — reproducing the
    pre-schedule engine's ``w_nbr * (left + right)`` arithmetic exactly.)"""
    groups = []
    for rnd in schedule.rounds:
        wkey = rnd.weight if rnd.weight is not None else rnd.weights
        if groups and groups[-1][0] == wkey:
            groups[-1][1].append(rnd.perm)
        else:
            groups.append([wkey, [rnd.perm]])
    return [(w, tuple(perms)) for w, perms in groups]


def _flat_node_index(axes: Tuple[str, ...], sizes: Tuple[int, ...]):
    """Row-major flat node id over the gossip axes — matches ppermute's
    flattening of a tuple axis name."""
    idx = jax.lax.axis_index(axes[0])
    for a, sz in zip(axes[1:], sizes[1:]):
        idx = idx * sz + jax.lax.axis_index(a)
    return idx


def _weight_value(w, flat_idx_fn):
    """Uniform weights stay python floats (weak-typed: they convert to the
    payload dtype, preserving the legacy engines' arithmetic bit for bit);
    per-node weights gather one scalar by the local node id (flat_idx_fn is
    only invoked on that branch)."""
    if isinstance(w, float):
        return w
    return jnp.asarray(w, jnp.float32)[flat_idx_fn()]


def _accumulate_rounds(payloads, perms, axis_arg, dense_fn):
    """sum_r dense(ppermute_r(payloads)) — no zero-init, so a single-round
    group is exactly the received payload's dense form."""
    acc = None
    for perm in perms:
        got = jax.lax.ppermute(payloads, axis_arg, list(perm))
        dl = dense_fn(got)
        acc = dl if acc is None else [a + d for a, d in zip(acc, dl)]
    return acc


def _neighbor_sum(payloads, groups, axis_arg, dense_fn, flat_idx_fn):
    """Weighted neighbour aggregate  sum_j w_ij q_j  (j != i) as flat
    buffers.  Returns (buffers, w_nbr): a single weight group defers its
    scalar to the caller (applied leaf-wise, matching the legacy engines);
    multiple groups weight each group's accumulator and pre-sum, so the
    caller applies w_nbr = 1.0."""
    if len(groups) == 1:
        w, perms = groups[0]
        acc = _accumulate_rounds(payloads, perms, axis_arg, dense_fn)
        return acc, _weight_value(w, flat_idx_fn)
    total = None
    for w, perms in groups:
        acc = _accumulate_rounds(payloads, perms, axis_arg, dense_fn)
        wv = _weight_value(w, flat_idx_fn)
        contrib = [wv * a for a in acc]
        total = contrib if total is None else [t + c
                                               for t, c in zip(total, contrib)]
    return total, 1.0


def _mix(payloads, q_like, groups, axis_arg, dense_fn, flat_idx_fn):
    """Stage ``mix``: the neighbour aggregate of :func:`_neighbor_sum`, or
    with no neighbours (n == 1) zeros shaped like the own dense q
    ``q_like`` and weight 0."""
    with stage("mix"):
        if not groups:
            return [q * 0.0 for q in q_like], 0.0
        return _neighbor_sum(payloads, groups, axis_arg, dense_fn,
                             flat_idx_fn)


class _LazyFlatIndex:
    """Computes the flat node id at most once per traced exchange (only
    schedules with per-node weights need it)."""

    def __init__(self, axes, sizes):
        self.axes, self.sizes, self.value = axes, sizes, None

    def __call__(self):
        if self.value is None:
            self.value = _flat_node_index(self.axes, self.sizes)
        return self.value


# ---------------------------------------------------------------------------
# stochastic topology processes (comm/stochastic.py)
# ---------------------------------------------------------------------------

def _process_neighbor_sum(process, payloads, axis_arg, dense_fn, flat_idx_fn,
                          sample_key, t):
    """Sampled-round neighbour aggregate for the PLAIN engine under a
    TopologyProcess (the payload is the fresh iterate x itself, so sampled
    mixing is exact: x' = W_t x).

    Returns (nbr_bufs, w_nbr, w_self) — the sampled-step analogue of
    ``_neighbor_sum`` + ``_self_weight``.  ``sample_key`` is the exchange key
    BEFORE the per-axis fold-ins, so every node draws the identical sample
    (fold_in(key, SAMPLE_SALT + t) — see comm/stochastic.py) without
    communication.

    * matching — ``lax.switch`` over one-ppermute branches: only the sampled
      round's permute executes, so a k-round schedule costs ONE collective
      launch per gossip round instead of k.  Receive/self weights are the
      process's 1/p_r-scaled vectors, gathered at the local node id (f32 in
      every branch, so all switch branches have identical avals).
    * linkfail — every compiled round still ships (the payload is sent; the
      lossy link drops it in flight), but each destination scales its
      received contribution by the round's Bernoulli edge keep-mask and
      folds the dropped weight back into its self weight.

    The compressed CHOCO engine does NOT use this helper: integrating
    sampled q's into the running aggregate s is unsound (s = sum_tau W_tau
    q_tau is a non-decaying random walk around the static-W target — see
    make_process_choco_fn for the replica-based algorithm that replaces it).
    """
    i = flat_idx_fn()
    if process.kind == "matching":
        rounds = process.schedule.rounds

        def branch(r):
            recv = jnp.asarray(process.branch_recv[r], jnp.float32)
            selfw = jnp.asarray(process.branch_self[r], jnp.float32)
            perm = list(rounds[r].perm)

            def run(pl):
                got = jax.lax.ppermute(pl, axis_arg, perm)
                bufs = dense_fn(got)
                wv = recv[i]
                return [wv * b for b in bufs], selfw[i]
            return run

        idx = process.round_index(sample_key, t)
        nbr_bufs, w_self = jax.lax.switch(
            idx, [branch(r) for r in range(len(rounds))], payloads)
        return nbr_bufs, 1.0, w_self

    if process.kind == "linkfail":
        mask = process.edge_mask(sample_key, t)
        rmasks = process.round_masks(mask)
        total, recv_w = None, jnp.float32(0.0)
        for rnd, rm, recv in zip(process.schedule.rounds, rmasks,
                                 process.round_recv):
            got = jax.lax.ppermute(payloads, axis_arg, list(rnd.perm))
            bufs = dense_fn(got)
            wv = (jnp.asarray(recv, jnp.float32) * rm)[i]
            contrib = [wv * b for b in bufs]
            total = contrib if total is None else [a + c for a, c
                                                   in zip(total, contrib)]
            recv_w = recv_w + wv
        return total, 1.0, 1.0 - recv_w

    raise ValueError(f"unknown topology process kind {process.kind!r}")


def _self_weight(schedule: GossipSchedule, flat_idx_fn):
    if schedule.self_weight is not None:
        return schedule.self_weight
    return jnp.asarray(schedule.self_weights, jnp.float32)[flat_idx_fn()]


# ---------------------------------------------------------------------------
# choco engines
# ---------------------------------------------------------------------------

def make_choco_schedule_fn(*, axes: Tuple[str, ...], sizes: Tuple[int, ...],
                           schedules: Tuple[GossipSchedule, ...],
                           compressor: Compressor, gamma: float,
                           gossip_steps: int = 1,
                           exact_small_leaves: bool = False,
                           small_leaf_threshold: int = 8_192,
                           packed: bool = True,
                           pack_align: Optional[int] = None,
                           leaf_routes: Optional[list] = None,
                           kernel_backend: str = "jnp") -> Callable:
    """Returns local_fn(key, x_half, x_hat, s) -> (x, x_hat, s) for shard_map.

    Implements, per local shard and ``gossip_steps`` times per call
    (schedule t = schedules[t % len(schedules)] — time-varying mixing):

        q      = Q(x - x_hat)
        x_hat += q
        s     += sum_j w_ij q_j          (schedule rounds, ppermute'd)
        x      = x + gamma (s - x_hat)

    packed=True (default): bucketed flat-buffer engine — the pytree is packed
    into a few dtype-homogeneous buckets (spec from comm/packing.py), each
    compressed once and shipped as one static-shape payload per neighbour.
    The spec (and flatten) is built ONCE per exchange, so k gossip steps
    amortize k compressions into one pack.
    packed=False: legacy per-leaf compression + one ppermute per leaf per
    round; kept as the reference engine.

    exact_small_leaves: leaves below the threshold (norm scales, biases) ship
    uncompressed — for a top-1% sparsifier the (value, index) pair costs 8
    bytes/coordinate, so compressing a 4 KB norm vector saves nothing while
    adding top-k latency; beyond-paper toggle, off for paper-faithful runs.
    In the packed engine this is a bucket-routing rule: small leaves go to a
    dense "exact" bucket instead of taking a per-leaf branch.

    kernel_backend: the RESOLVED backend ("jnp"/"pallas") from
    kernels/dispatch.py — resolution (auto probing, toolchain gating)
    happens in :func:`make_gossip_exchange`; this builder only consumes
    the decision.  With "pallas" and all-f32 EF state the packed engine
    switches to the fused bucket-space path: state lives in bucket buffers
    across all gossip_steps, each round issues ONE fused quantize launch
    and ONE fused EF-update launch per bucket (kernels/qsgd.py +
    kernels/ef_update.py) instead of 8 full-size jnp streams per leaf,
    and leaves are unpacked once at the end.  Parity contract with the
    jnp path: identical wire payloads (same codes, same scales — the
    norm reductions and float associations match exactly) and identical
    x_hat; the x/s iterates agree up to FMA-contraction rounding (the
    backends compile structurally different graphs, so LLVM/XLA may
    contract different mul+add pairs — ulp-level, asserted in
    tests/test_fused.py).
    """
    from repro.core.choco_gossip import GammaSpec
    from repro.core.compression import Identity
    identity = Identity()
    if isinstance(gamma, GammaSpec) and not packed:
        raise ValueError(
            "per-bucket gamma (GammaSpec) requires the packed engine: the "
            "legacy per-leaf exchange has no bucket spec to derive omegas "
            "from — pass a float gamma, or packed=True")
    n = 1
    for sz in sizes:
        n *= sz
    for sch in schedules:
        assert sch.n == n, f"schedule n={sch.n} != mesh gossip extent {n}"
    assert gossip_steps >= 1
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    align = _pack_align(compressor, pack_align)
    compiled = [(sch, _weight_groups(sch)) for sch in schedules]

    def packed_local_fn(key, x_half, x_hat, s):
        from repro.comm.packing import (bucket_dense, compress_bufs,
                                        make_bucket_spec, pack_leaves,
                                        unpack_leaves)
        from repro.kernels import dispatch as kdispatch
        # distinct randomness per gossip node and per model/fsdp shard
        for a in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        leaves_h, leaves_hat, leaves_s, treedef = _flatten_states(
            x_half, x_hat, s)
        spec = make_bucket_spec(leaves_hat, align=align,
                                exact_small_leaves=exact_small_leaves,
                                small_leaf_threshold=small_leaf_threshold,
                                routes=leaf_routes)
        flat_idx = _LazyFlatIndex(axes, sizes)
        unflatten = treedef.unflatten
        dense_fn = lambda got: [bucket_dense(g, b) for g, b
                                in zip(got, spec.buckets)]

        if (kernel_backend == "pallas"
                and _fused_update_ok(spec, leaves_h, leaves_s)):
            # fused bucket-space path: pack the three state trees ONCE,
            # run every gossip round on the bucket buffers (one fused
            # quantize launch + one fused EF-update launch per bucket),
            # unpack once at the end.  Padding is exactly preserved: it
            # starts 0 in every buffer, deltas/q/neighbour sums are 0
            # there, and the EF update maps (0,0,0,0,0) -> (0,0,0).
            bucket_gammas = _resolve_bucket_gammas(gamma, spec, compressor)
            shapes = [lh.shape for lh in leaves_h]
            with stage("pack"):
                h_bufs = pack_leaves(spec, leaves_h)
                hat_bufs = pack_leaves(spec, leaves_hat)
                s_bufs = pack_leaves(spec, leaves_s)
            for t in range(gossip_steps):
                sched, groups = compiled[t % len(compiled)]
                tkey = key if t == 0 else jax.random.fold_in(key, t)
                with stage("compress"):
                    d_bufs = [hb - hatb for hb, hatb
                              in zip(h_bufs, hat_bufs)]
                    payloads, q_bufs = compress_bufs(
                        compressor, tkey, spec, d_bufs, backend="pallas")
                nbr_bufs, w_nbr = _mix(payloads, q_bufs, groups, axis_arg,
                                       dense_fn, flat_idx)
                with stage("mix"):
                    w_self = _self_weight(sched, flat_idx)
                with stage("ef_update"):
                    for b in range(spec.n_buckets):
                        h_bufs[b], hat_bufs[b], s_bufs[b] = \
                            kdispatch.ef_bucket_update(
                                h_bufs[b], hat_bufs[b], s_bufs[b],
                                q_bufs[b], nbr_bufs[b], w_self, w_nbr,
                                bucket_gammas[b], backend="pallas")
            with stage("unpack"):
                leaves_h = [f.reshape(sh) for f, sh
                            in zip(unpack_leaves(spec, h_bufs), shapes)]
                leaves_hat = [f.reshape(sh) for f, sh
                              in zip(unpack_leaves(spec, hat_bufs), shapes)]
                leaves_s = [f.reshape(sh) for f, sh
                            in zip(unpack_leaves(spec, s_bufs), shapes)]
            return (unflatten(leaves_h), unflatten(leaves_hat),
                    unflatten(leaves_s))

        gammas = _resolve_leaf_gammas(gamma, spec, compressor)
        for t in range(gossip_steps):
            sched, groups = compiled[t % len(compiled)]
            tkey = key if t == 0 else jax.random.fold_in(key, t)
            payloads, q_leaves, new_hat = _packed_self_half(
                compressor, tkey, leaves_h, leaves_hat, spec,
                backend=kernel_backend)
            nbr_leaves, w_nbr = _mix(payloads, q_leaves, groups, axis_arg,
                                     dense_fn, flat_idx)
            if groups:                     # bucket space -> leaves
                with stage("unpack"):
                    nbr_leaves = unpack_leaves(spec, nbr_leaves)
            with stage("mix"):
                w_self = _self_weight(sched, flat_idx)
            leaves_s, leaves_h = _choco_leaf_updates(
                leaves_h, leaves_s, q_leaves, nbr_leaves, new_hat,
                w_self, w_nbr, gammas)
            leaves_hat = new_hat
        return unflatten(leaves_h), unflatten(leaves_hat), unflatten(leaves_s)

    if packed:
        return packed_local_fn

    def local_fn(key, x_half, x_hat, s):
        # distinct randomness per gossip node and per model/fsdp shard
        for a in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        leaves_h, leaves_hat, leaves_s, treedef = _flatten_states(
            x_half, x_hat, s)
        flat_idx = _LazyFlatIndex(axes, sizes)
        for t in range(gossip_steps):
            sched, groups = compiled[t % len(compiled)]
            tkey = key if t == 0 else jax.random.fold_in(key, t)
            payloads, dense_fns, q_dense, new_hat = _per_leaf_self_half(
                compressor, identity, exact_small_leaves,
                small_leaf_threshold, tkey, leaves_h, leaves_hat)
            dense_fn = lambda got: [dfn(g) for dfn, g
                                    in zip(dense_fns, got)]
            nbr_sum, w_nbr = _mix(payloads, q_dense, groups, axis_arg,
                                  dense_fn, flat_idx)
            with stage("mix"):
                w_self = _self_weight(sched, flat_idx)
            leaves_s, leaves_h = _choco_leaf_updates(
                leaves_h, leaves_s, q_dense, nbr_sum, new_hat,
                w_self, w_nbr, gamma)
            leaves_hat = new_hat
        unflatten = treedef.unflatten
        return unflatten(leaves_h), unflatten(leaves_hat), unflatten(leaves_s)

    return local_fn


# ---------------------------------------------------------------------------
# stochastic-process choco engine (per-round references — Algorithm 2 style)
# ---------------------------------------------------------------------------

def _send_vec(perm, n) -> Tuple[float, ...]:
    vec = [0.0] * n
    for src, _ in perm:
        vec[src] = 1.0
    return tuple(vec)


def _make_compress_stage(compressor: Compressor, *, packed: bool, align: int,
                         leaf_routes: Optional[list]) -> Callable:
    """Shared compression front half of the replica-based engines: returns
    ``stage(tkey, deltas, shapes_like) -> (payloads, q_leaves, dense_fn)``
    where ``payloads`` are the wire arrays handed to ``lax.ppermute``,
    ``q_leaves`` the dense local q per leaf, and ``dense_fn`` densifies a
    received payload back to per-leaf flat buffers.  ``packed`` selects the
    bucketed flat-buffer path (one payload per bucket) vs the legacy
    per-leaf path; both are consumed by ``make_process_choco_fn`` and the
    bounded-staleness engine (comm/async_gossip.py)."""
    def packed_stage(tkey, deltas, shapes_like):
        from repro.comm.packing import (bucket_dense, compress_packed,
                                        make_bucket_spec, unpack_leaves)
        spec = make_bucket_spec(shapes_like, align=align, routes=leaf_routes)
        payloads, q_leaves = compress_packed(compressor, tkey, spec, deltas)
        dense_fn = lambda got: unpack_leaves(
            spec, [bucket_dense(g, b) for g, b in zip(got, spec.buckets)])
        return payloads, q_leaves, dense_fn

    def per_leaf_stage(tkey, deltas, shapes_like):
        keys = _leaf_keys(tkey, len(deltas), 0)
        payloads, dfns, q_leaves = [], [], []
        with stage("compress"):
            for i, d in enumerate(deltas):
                pl, dfn = _compress_leaf(
                    compressor, keys[i] if compressor.stochastic else None,
                    d)
                payloads.append(pl)
                dfns.append(dfn)
                q_leaves.append(dfn(pl))
        return payloads, q_leaves, (
            lambda got: [dfn(g) for dfn, g in zip(dfns, got)])

    return packed_stage if packed else per_leaf_stage


def _ef_send_half(compress_stage, tkey, leaves_x, hat):
    """Error-feedback send half shared by the replica engines: compress the
    EF deltas against the public copies ``hat``, advance them, and return
    the wire payloads plus the densify callback.  Factored so the send side
    is one dependency-free block in the traced graph — the receive half is
    whatever the engine later does with ``payloads``, which keeps the
    collective's start/done pair separable in the compiled HLO (the
    property the pipelined engine and benchmarks/bench_overlap.py rely on).
    """
    with stage("compress"):
        deltas = [(a.astype(h.dtype) - h).ravel()
                  for a, h in zip(leaves_x, hat)]
    payloads, q_leaves, dense_fn = compress_stage(tkey, deltas, hat)
    with stage("ef_update"):
        q_trees = [q.reshape(h.shape).astype(h.dtype)
                   for h, q in zip(hat, q_leaves)]
        new_hat = [h + q for h, q in zip(hat, q_trees)]
    return payloads, q_trees, new_hat, dense_fn


def make_process_choco_fn(*, axes: Tuple[str, ...], sizes: Tuple[int, ...],
                          process, compressor: Compressor, gamma: float,
                          gossip_steps: int = 1, packed: bool = True,
                          pack_align: Optional[int] = None,
                          leaf_routes: Optional[list] = None) -> Callable:
    """Compressed gossip under a sampled/masked TopologyProcess.

    The static engine's running aggregate s_i = sum_tau (W q_tau)_i is only
    meaningful when W is FIXED: under per-step sampled W_t it becomes a
    non-decaying random walk around the target sum_j w_ij x_hat_j and the
    iterates drift away (unbiased but integrating variance).  The sound
    algorithm is the source paper's Algorithm 2 itself — every consumer of a
    public copy must hear every update of it — realized here with
    *per-round references*, the minimal replica set for round-sampled
    communication:

      * own references H_r (one per schedule round the node sends in):
        q_i^(r) = Q(x_i - H_r), H_r += q_i^(r), updated ONLY when round r
        actually ships;
      * source replicas S_r (one per round): S_r += received q — exact
        copies of the round-r source's H_r, because that source updates its
        H_r in exactly the rounds this node hears it (static round
        structure + shared sampling seed = replica consistency with zero
        metadata on the wire);
      * update  x_i += gamma * sum_r live_r * v_r[i] * (S_r - H_r)  — the
        Algorithm-1 row form with every term locally fresh.

    matching: one round live per gossip round (``lax.switch`` — a single
    permute launch and a single compression per step, against the sampled
    round's reference).  linkfail: all rounds ship one shared q (single
    compression, x_hat is the one own-reference) and the Bernoulli edge
    mask gates each round's receive weight.

    Memory: matching holds 2R state trees (R own refs + R replicas), and
    linkfail R + 1 — the O(degree) public-copy cost of the paper's
    Algorithm 2, which the static engine's Algorithm-5 s-trick avoids only
    because its W never changes.  The trainer allocates x_hat / s as lists
    of trees accordingly.
    """
    n = 1
    for sz in sizes:
        n *= sz
    assert process.n == n, f"process n={process.n} != mesh extent {n}"
    assert gossip_steps >= 1
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    align = _pack_align(compressor, pack_align)
    rounds = process.schedule.rounds
    R = len(rounds)
    send_vecs = [_send_vec(rnd.perm, n) for rnd in rounds]

    compress_stage = _make_compress_stage(compressor, packed=packed,
                                          align=align,
                                          leaf_routes=leaf_routes)

    def matching_local_fn(key, x_half, hat_list, s_list):
        sample_key = key
        for a in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        leaves_x, treedef = jax.tree_util.tree_flatten(x_half)
        H = [treedef.flatten_up_to(h) for h in hat_list]   # R own refs
        S = [treedef.flatten_up_to(sv) for sv in s_list]   # R replicas
        flat_idx = _LazyFlatIndex(axes, sizes)
        i = flat_idx()
        for t in range(gossip_steps):
            tkey = key if t == 0 else jax.random.fold_in(key, t)

            def branch(r):
                recv = jnp.asarray(process.branch_recv[r], jnp.float32)
                send = jnp.asarray(send_vecs[r], jnp.float32)
                perm = list(rounds[r].perm)

                def run(ops):
                    lx, Hs, Ss = ops
                    ref = Hs[r]
                    with stage("compress"):
                        deltas = [(a.astype(h.dtype) - h).ravel()
                                  for a, h in zip(lx, ref)]
                    payloads, q_leaves, dense_fn = compress_stage(
                        jax.random.fold_in(tkey, r), deltas, ref)
                    with stage("ef_update"):
                        m_send = send[i]
                        new_ref = [h + (m_send * q.reshape(h.shape)).astype(
                                       h.dtype)
                                   for h, q in zip(ref, q_leaves)]
                    with stage("mix"):
                        got = jax.lax.ppermute(payloads, axis_arg, perm)
                        recv_dense = dense_fn(got)
                    with stage("ef_update"):
                        # non-receivers get ppermute zeros: replica unchanged
                        new_rep = [sv + rd.reshape(sv.shape).astype(sv.dtype)
                                   for sv, rd in zip(Ss[r], recv_dense)]
                        v = recv[i]
                        # cast the whole f32-weighted update back: v is a
                        # strong f32 scalar and would silently upcast bf16
                        # params
                        new_x = [a + (gamma * v * (sr - hr)).astype(a.dtype)
                                 for a, sr, hr in zip(lx, new_rep, new_ref)]
                    Hs2 = [new_ref if rr == r else Hs[rr] for rr in range(R)]
                    Ss2 = [new_rep if rr == r else Ss[rr] for rr in range(R)]
                    return new_x, Hs2, Ss2
                return run

            idx = process.round_index(sample_key, t)
            leaves_x, H, S = jax.lax.switch(
                idx, [branch(r) for r in range(R)], (leaves_x, H, S))
        u = treedef.unflatten
        return u(leaves_x), [u(h) for h in H], [u(sv) for sv in S]

    def linkfail_local_fn(key, x_half, x_hat, s_list):
        sample_key = key
        for a in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        leaves_x, treedef = jax.tree_util.tree_flatten(x_half)
        leaves_hat = treedef.flatten_up_to(x_hat)
        S = [treedef.flatten_up_to(sv) for sv in s_list]
        flat_idx = _LazyFlatIndex(axes, sizes)
        i = flat_idx()
        for t in range(gossip_steps):
            tkey = key if t == 0 else jax.random.fold_in(key, t)
            with stage("compress"):
                deltas = [(a.astype(h.dtype) - h).ravel()
                          for a, h in zip(leaves_x, leaves_hat)]
            payloads, q_leaves, dense_fn = compress_stage(tkey, deltas,
                                                          leaves_hat)
            with stage("ef_update"):
                leaves_hat = [h + q.reshape(h.shape).astype(h.dtype)
                              for h, q in zip(leaves_hat, q_leaves)]
            with stage("mix"):
                mask = process.edge_mask(sample_key, t)
                rmasks = process.round_masks(mask)
            acc = [jnp.zeros((), a.dtype) for a in leaves_x]
            new_S = []
            for r, rnd in enumerate(rounds):
                with stage("mix"):
                    got = jax.lax.ppermute(payloads, axis_arg,
                                           list(rnd.perm))
                    recv_dense = dense_fn(got)
                with stage("ef_update"):
                    # the replica ALWAYS integrates (the payload was sent;
                    # the lossy link gates only the mixing weight below) —
                    # it must keep tracking the source's x_hat exactly
                    S_r = [sv + rd.reshape(sv.shape).astype(sv.dtype)
                           for sv, rd in zip(S[r], recv_dense)]
                    new_S.append(S_r)
                    wv = (jnp.asarray(process.round_recv[r], jnp.float32)
                          * rmasks[r])[i]
                    acc = [a + wv * (sr - h)
                           for a, sr, h in zip(acc, S_r, leaves_hat)]
            S = new_S
            with stage("ef_update"):
                # acc is f32 (strong per-node mask weights): cast the whole
                # update back so bf16 params stay bf16
                leaves_x = [a + (gamma * ac).astype(a.dtype)
                            for a, ac in zip(leaves_x, acc)]
        u = treedef.unflatten
        return u(leaves_x), u(leaves_hat), [u(sv) for sv in S]

    if process.kind == "matching":
        return matching_local_fn
    if process.kind == "linkfail":
        return linkfail_local_fn
    raise ValueError(f"unknown topology process kind {process.kind!r}")


# ---------------------------------------------------------------------------
# exact baselines
# ---------------------------------------------------------------------------

def make_plain_schedule_fn(*, axes: Tuple[str, ...], sizes: Tuple[int, ...],
                           schedules: Tuple[GossipSchedule, ...],
                           gossip_steps: int = 1,
                           process=None) -> Callable:
    """Exact neighbour averaging (Algorithm 3): x = sum_j w_ij x_j, on any
    compiled schedule (the uncompressed iterates themselves are the wire
    payload).  process != None averages with the sampled mixing matrix of a
    comm/stochastic.py TopologyProcess instead of the static W."""
    compiled = [(sch, _weight_groups(sch)) for sch in schedules]
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)

    def local_fn(key, x_half, x_hat, s):
        sample_key = key
        x = x_half
        flat_idx = _LazyFlatIndex(axes, sizes)
        for t in range(gossip_steps):
            sched, groups = compiled[t % len(compiled)]
            if process is None and not groups:
                continue
            leaves, treedef = jax.tree_util.tree_flatten(x)
            with stage("mix"):
                if process is not None:
                    nbr, w_nbr, w_self = _process_neighbor_sum(
                        process, leaves, axis_arg, lambda got: got, flat_idx,
                        sample_key, t)
                else:
                    nbr, w_nbr = _neighbor_sum(leaves, groups, axis_arg,
                                               lambda got: got, flat_idx)
                    w_self = _self_weight(sched, flat_idx)
                # cast back: per-node weights are f32 scalars and would
                # upcast bf16 params (uniform python-float weights make
                # this a no-op)
                x = treedef.unflatten(
                    [(w_self * a + w_nbr * b).astype(a.dtype)
                     for a, b in zip(leaves, nbr)])
        return x, x_hat, s

    return local_fn


def make_allreduce_fn(*, axes) -> Callable:
    """Centralized baseline: exact average over the gossip axes (all-reduce)."""
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)

    def local_fn(key, x_half, x_hat, s):
        del key
        with stage("mix"):
            new_x = jax.tree.map(lambda a: jax.lax.pmean(a, axis_arg),
                                 x_half)
        return new_x, x_hat, s
    return local_fn


# ---------------------------------------------------------------------------
# exchange builder
# ---------------------------------------------------------------------------

def _default_schedules(axes, sizes) -> Tuple[GossipSchedule, ...]:
    """Back-compat default: a uniform ring on one gossip axis, the 2-d torus
    over a (pod, data) axis pair — the two pre-schedule engine graphs."""
    from repro.comm.schedule import compile_schedule
    from repro.core.topology import ring, torus2d
    if len(axes) == 1:
        return (compile_schedule(ring(sizes[0])),)
    assert len(axes) == 2, "gossip over more than 2 mesh axes needs explicit schedules"
    return (compile_schedule(torus2d(*sizes), grid=tuple(sizes)),)


def make_gossip_exchange(*, mode: str, mesh, state_specs, axis,
                         compressor: Optional[Compressor] = None,
                         gamma: float = 1.0, exact_small_leaves: bool = False,
                         small_leaf_threshold: int = 8_192,
                         packed: bool = True,
                         pack_align: Optional[int] = None,
                         schedules: Optional[Sequence[GossipSchedule]] = None,
                         gossip_steps: int = 1,
                         process=None,
                         pipelined: bool = False,
                         weight_specs=None,
                         kernel_backend: str = "auto") -> Callable:
    """Build the jit-able exchange: (key, x_half, x_hat, s) -> (x, x_hat, s).

    axis: one mesh axis name, or a tuple of axis names whose row-major
    flattening carries the schedule's node ids (the trainer maps the torus
    onto the (pod, data) ICI grid this way).
    state_specs: pytree of PartitionSpec matching the param pytree (with the
    leading node dim mapped to the gossip axes).
    schedules: compiled GossipSchedule sequence (time-varying mixing cycles
    through it across gossip_steps); None = a ring on a single axis / the
    2-d torus on an axis pair, matching the pre-schedule engines.
    packed selects the bucketed flat-buffer engine (default) vs the legacy
    per-leaf exchange.
    process: comm/stochastic.py TopologyProcess — replaces the static round
    replay with per-step sampled rounds (choco / plain modes only); its
    schedule IS the schedule, so ``schedules`` must be omitted or length 1.
    mode="pushsum" builds the directed column-stochastic engine
    (comm/pushsum.py): the returned callable has the 5-ary push-sum
    signature (key, x, x_hat, s, w) -> (x, x_hat, s, w) and needs
    ``weight_specs`` (PartitionSpec of the per-node weight scalar).
    pipelined=True (choco, static schedule only) builds the overlap engine
    (comm/pipelined.py): identical signature and state trees, but the
    x-update reads the PREVIOUS round's (s, x_hat) pair, so the collective
    has no consumer in the current update and can run concurrently with
    whatever compute the caller traces around the exchange.
    kernel_backend: "auto" (default) probes the toolchain and picks the
    Pallas kernels when they can run compiled on this jax/backend
    (kernels/dispatch.py); "pallas"/"jnp" force.  Only the packed static
    choco engines (serial + pipelined) are pallas-eligible — forcing
    "pallas" elsewhere raises.  Backends ship identical wire bytes and
    identical x_hat; x/s agree to FMA-contraction rounding (see
    make_choco_schedule_fn).
    """
    from repro.kernels import dispatch as kdispatch
    engine_eligible = (mode == "choco" and packed and process is None)
    resolved_backend = kdispatch.resolve_backend(
        kernel_backend, engine_eligible=engine_eligible)
    axes = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
    sizes = tuple(mesh.shape[a] for a in axes)
    n = 1
    for sz in sizes:
        n *= sz
    if process is not None:
        if mode not in ("choco", "plain"):
            raise ValueError(
                f"topology processes run on the choco/plain engines only; "
                f"mode={mode!r} (the push-sum engine handles directed graphs "
                f"itself, allreduce has no gossip graph)")
        if getattr(process, "kind", None) == "staleness" and mode != "choco":
            raise ValueError(
                "bounded staleness runs on the compressed choco engine "
                "only: the stale snapshots are reconstructed from rings of "
                "compressed increments, and the plain engine ships fresh "
                "iterates with no increment stream to ring-buffer")
        if schedules is not None and len(tuple(schedules)) > 1:
            raise ValueError(
                "a topology process already IS the per-step mixing "
                "distribution; combining it with a time-varying schedule "
                "sequence is ambiguous — pass one or the other")
        if process.n != n:
            raise ValueError(f"process n={process.n} != mesh gossip "
                             f"extent {n}")
        schedules = (process.schedule,)

    if mode == "pushsum":
        from repro.comm.pushsum import make_pushsum_schedule_fn
        if not packed:
            raise ValueError("the push-sum engine is packed-only (the weight "
                             "scalar rides in-band with the bucket payloads); "
                             "per-leaf push-sum is not implemented")
        if schedules is None or len(tuple(schedules)) != 1:
            raise ValueError("push-sum needs exactly one compiled directed "
                             "schedule (compile_directed_schedule)")
        if weight_specs is None:
            raise ValueError("push-sum needs weight_specs: the PartitionSpec "
                             "of the per-node (n, 1) weight column")
        local_fn = make_pushsum_schedule_fn(
            axes=axes, sizes=sizes, schedule=tuple(schedules)[0],
            compressor=compressor, gamma=gamma, gossip_steps=gossip_steps,
            pack_align=pack_align,
            leaf_routes=_leaf_routes(state_specs, axes))
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), state_specs, state_specs, state_specs,
                      weight_specs),
            out_specs=(state_specs, state_specs, state_specs, weight_specs),
        )

    if pipelined:
        if mode != "choco":
            raise ValueError(
                f"pipelined gossip runs on the compressed choco engine only "
                f"(mode={mode!r}): overlapping the exchange requires the "
                f"EF-compressed increment stream whose integration can be "
                f"deferred one round — plain/allreduce ship fresh iterates "
                f"the update must consume immediately")
        if process is not None:
            raise ValueError(
                "pipelined gossip composes a deterministic one-round delay "
                "with a STATIC schedule; stacking it on a stochastic "
                "topology process (whose gamma already folds its own "
                "delay/sampling model) is unsupported — pick one")
        if schedules is not None and len(tuple(schedules)) > 1:
            raise ValueError(
                "pipelined gossip supports a single static schedule: the "
                "tau=1 gamma is derived from one delay-averaged mixing "
                "matrix, which a time-varying sequence does not have")

    schedules = (tuple(schedules) if schedules
                 else _default_schedules(axes, sizes))
    if len(schedules) > 1 and gossip_steps % len(schedules) != 0:
        # the t-loop restarts at 0 every exchange, so a sequence longer than
        # gossip_steps would silently never run its tail schedules (while
        # gamma is still computed conservatively over the whole sequence)
        raise ValueError(
            f"time-varying mixing with {len(schedules)} schedules needs "
            f"gossip_steps to be a multiple of the sequence length so every "
            f"schedule runs each SGD step; got gossip_steps={gossip_steps}")

    if mode == "choco" and process is not None \
            and getattr(process, "kind", None) == "staleness":
        # bounded-staleness engine (comm/async_gossip.py): x_hat is the
        # [public copy + depth-tau own ring] list, s the [R replicas +
        # R*tau receive rings] list — see make_async_choco_fn
        from repro.comm.async_gossip import make_async_choco_fn
        local_fn = make_async_choco_fn(
            axes=axes, sizes=sizes, process=process, compressor=compressor,
            gamma=gamma, gossip_steps=gossip_steps, packed=packed,
            pack_align=pack_align,
            leaf_routes=_leaf_routes(state_specs, axes))
        R = len(process.schedule.rounds)
        tau = process.max_staleness
        hat_specs = [state_specs] * (1 + tau)
        s_specs = [state_specs] * (R * (1 + tau))
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), state_specs, hat_specs, s_specs),
            out_specs=(state_specs, hat_specs, s_specs),
        )

    if mode == "choco" and process is not None:
        # replica-based engine: x_hat / s are LISTS of state trees (per-round
        # references — see make_process_choco_fn); their specs replicate the
        # single-tree specs element-wise
        local_fn = make_process_choco_fn(
            axes=axes, sizes=sizes, process=process, compressor=compressor,
            gamma=gamma, gossip_steps=gossip_steps, packed=packed,
            pack_align=pack_align,
            leaf_routes=_leaf_routes(state_specs, axes))
        R = len(process.schedule.rounds)
        hat_specs = (state_specs if process.kind == "linkfail"
                     else [state_specs] * R)
        s_specs = [state_specs] * R
        return jax.shard_map(
            local_fn, mesh=mesh,
            in_specs=(P(), state_specs, hat_specs, s_specs),
            out_specs=(state_specs, hat_specs, s_specs),
        )

    if mode == "choco" and pipelined:
        from repro.comm.pipelined import make_pipelined_choco_fn
        local_fn = make_pipelined_choco_fn(
            axes=axes, sizes=sizes, schedule=schedules[0],
            compressor=compressor, gamma=gamma, gossip_steps=gossip_steps,
            exact_small_leaves=exact_small_leaves,
            small_leaf_threshold=small_leaf_threshold,
            packed=packed, pack_align=pack_align,
            leaf_routes=_leaf_routes(state_specs, axes),
            kernel_backend=resolved_backend)
    elif mode == "choco":
        local_fn = make_choco_schedule_fn(
            axes=axes, sizes=sizes, schedules=schedules,
            compressor=compressor, gamma=gamma, gossip_steps=gossip_steps,
            exact_small_leaves=exact_small_leaves,
            small_leaf_threshold=small_leaf_threshold,
            packed=packed, pack_align=pack_align,
            leaf_routes=_leaf_routes(state_specs, axes),
            kernel_backend=resolved_backend)
    elif mode == "plain":
        local_fn = make_plain_schedule_fn(axes=axes, sizes=sizes,
                                          schedules=schedules,
                                          gossip_steps=gossip_steps,
                                          process=process)
    elif mode == "allreduce":
        local_fn = make_allreduce_fn(axes=axes)
    else:
        raise ValueError(mode)

    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), state_specs, state_specs, state_specs),
        out_specs=(state_specs, state_specs, state_specs),
        # a pallas_call's out_shape carries no varying-mesh-axes (vma)
        # type, which check_vma=True rejects; the check only guards
        # replication typing and never changes numerics
        check_vma=(resolved_backend != "pallas"),
    )
