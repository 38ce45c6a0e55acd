"""Directed push-sum gossip with compressed payloads (column-stochastic A).

CHOCO-style error feedback (paper Algorithm 2's q/x_hat machinery) on a
directed graph; consensus-rate and in-band weight audits are logged in
EXPERIMENTS.md §Perf F.

The symmetric CHOCO engines average with a row-stochastic, symmetric W; on a
directed graph the natural mixing matrix A is only *column*-stochastic
(every node splits its unit mass over its out-neighbours: 1^T A = 1^T), so
plain neighbour averaging converges to a Perron-weighted point, not the
average.  Push-sum (Kempe et al. 2003; SGP, Assran et al. 2019; compressed:
Toghani & Uribe 2022) fixes the bias by running the SAME recursion on a
scalar weight w (init 1) and de-biasing with the ratio x / w.

Per node i, per gossip round (gamma-lazy, CHOCO-style error feedback):

    q_i      = Q(x_i - x_hat_i)              compressed delta (packed bucket)
    x_hat_i += q_i
    s_i     += a_ii q_i + sum_j a_ij q_j     in-band over the schedule rounds
    x_i     += gamma (s_i - x_hat_i)
    w_i     += gamma (a_ii w_i + sum_j a_ij w_j - w_i)   EXACT (one scalar)

Because 1^T A = 1^T, both 1^T x and 1^T w are conserved exactly, and with
the identity compressor the x-recursion collapses to the classical lazy
push-sum x' = ((1-gamma) I + gamma A) x.  The de-biased estimate z = x / w
converges to the true average even though neither x nor w does.

Wire format: the per-neighbour payload of each round is the packed CHOCO
bucket payload tuple PLUS the node's weight scalar appended in-band — both
ride the same ``lax.ppermute`` call, so the weight costs 4 bytes per
neighbour per round, never an extra collective round.

The schedule is a :func:`~repro.comm.schedule.compile_directed_schedule`
decomposition of A into partial-permutation rounds (bipartite edge
coloring); symmetric schedules also work (a symmetric doubly-stochastic W is
column-stochastic), which is how the engine is cross-checked against CHOCO.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.compression import Compressor
from repro.comm.schedule import GossipSchedule, round_recv_vec
from repro.comm.stages import stage
from repro.comm.gossip import (_LazyFlatIndex, _flatten_states, _pack_align,
                               _packed_self_half, _self_weight)


def make_pushsum_schedule_fn(*, axes: Tuple[str, ...], sizes: Tuple[int, ...],
                             schedule: GossipSchedule,
                             compressor: Compressor, gamma: float,
                             gossip_steps: int = 1,
                             pack_align: Optional[int] = None,
                             leaf_routes: Optional[list] = None) -> Callable:
    """Returns local_fn(key, x, x_hat, s, w) -> (x, x_hat, s, w) for
    shard_map — the push-sum twin of the packed CHOCO engine.

    ``w`` is the per-node weight column: global shape (n, 1), local (1, 1)
    inside shard_map.  Rounds are NOT weight-grouped: a directed round's
    receive weight belongs to the *destination* (a_dst,src), and partial
    permutation rounds rarely share one, so each round applies its own
    per-node weight vector.
    """
    n = 1
    for sz in sizes:
        n *= sz
    assert schedule.n == n, f"schedule n={schedule.n} != mesh extent {n}"
    assert gossip_steps >= 1
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    align = _pack_align(compressor, pack_align)
    # per-round per-destination receive weights as f32 rows (R, n)
    recv_rows = [tuple(round_recv_vec(rnd, n)) for rnd in schedule.rounds]

    def local_fn(key, x, x_hat, s, w):
        from repro.comm.packing import (bucket_dense, make_bucket_spec,
                                        unpack_leaves)
        for a in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        leaves_x, leaves_hat, leaves_s, treedef = _flatten_states(x, x_hat, s)
        spec = make_bucket_spec(leaves_hat, align=align,
                                routes=leaf_routes)
        flat_idx = _LazyFlatIndex(axes, sizes)
        for t in range(gossip_steps):
            tkey = key if t == 0 else jax.random.fold_in(key, t)
            payloads, q_leaves, new_hat = _packed_self_half(
                compressor, tkey, leaves_x, leaves_hat, spec)
            with stage("mix"):
                a_self = _self_weight(schedule, flat_idx)
                # in-band wire unit: (bucket payloads, weight scalar) — one
                # ppermute pytree per round, the scalar rides along
                wire = (payloads, w)
                nbr_bufs = None
                nbr_w = a_self * w
                for rnd, recv in zip(schedule.rounds, recv_rows):
                    got_pl, got_w = jax.lax.ppermute(wire, axis_arg,
                                                     list(rnd.perm))
                    a_recv = jnp.asarray(recv, jnp.float32)[flat_idx()]
                    bufs = [a_recv * bucket_dense(g, b)
                            for g, b in zip(got_pl, spec.buckets)]
                    nbr_bufs = bufs if nbr_bufs is None else [
                        acc + b for acc, b in zip(nbr_bufs, bufs)]
                    nbr_w = nbr_w + a_recv * got_w
                if nbr_bufs is None:            # n == 1: A = [[1]]
                    nbr_leaves = [q * 0.0 for q in q_leaves]
            if nbr_bufs is not None:
                with stage("unpack"):
                    nbr_leaves = unpack_leaves(spec, nbr_bufs)
            new_s, new_x = [], []
            with stage("ef_update"):
                for lx, ls, qd, nb, nh in zip(leaves_x, leaves_s, q_leaves,
                                              nbr_leaves, new_hat):
                    # s += a_ii q_i + sum_j a_ij q_j  (Algorithm-5 shape,
                    # A cols)
                    sn = ls + (a_self * qd + nb).reshape(
                        lx.shape).astype(ls.dtype)
                    new_s.append(sn)
                    new_x.append(lx + gamma * (sn - nh).astype(lx.dtype))
                leaves_s, leaves_x, leaves_hat = new_s, new_x, new_hat
                w = w + gamma * (nbr_w - w).astype(w.dtype)
        unflatten = treedef.unflatten
        return (unflatten(leaves_x), unflatten(leaves_hat),
                unflatten(leaves_s), w)

    return local_fn


def debias(x, w):
    """Push-sum de-biased estimate z = x / w, broadcast over each leaf's
    trailing dims (w is the (n, 1) weight column; leaves carry a leading
    node dim)."""
    def leaf(a):
        wb = w.reshape((w.shape[0],) + (1,) * (a.ndim - 1))
        return (a / wb.astype(a.dtype)).astype(a.dtype)
    return jax.tree.map(leaf, x)
