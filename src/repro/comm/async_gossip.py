"""Asynchronous bounded-staleness gossip: proceed on the freshest copy held.

Audited in EXPERIMENTS.md §Perf G; distributed acceptance in
tests/test_async_gossip.py.

Everything before this module was *synchronous*: a gossip round either
delivered a payload this step (static schedules, randomized matchings) or
dropped it outright (link failures).  Real interconnects have a third
behaviour — the payload arrives, but **late** — and nodes that wait for slow
links serialize the whole mesh on its worst edge.  This module models the
standard fix: every node proceeds every step using the freshest neighbour
copy it *has*, with the delay bounded by ``max_staleness`` (tau).

CHOCO-style error feedback is exactly the right substrate for this
(Koloskova et al. 2019, *Decentralized Deep Learning with Arbitrary
Communication Compression*, analyze the same machinery): a stale public copy
``x_hat_j^(t-d)`` differs from the fresh one by the last ``d`` compressed
increments, i.e. staleness is just *additional accumulated compression
error*, and the Theorem-2 Lyapunov argument tolerates it as long as the
bound tau is finite.

:class:`StalenessProcess` joins the ``TopologyProcess`` family
(comm/stochastic.py): per-edge delays ``d_e(t) in {0..tau}`` are drawn
i.i.d. from ``delay_probs`` via the shared pre-axis-fold exchange key
(``fold_in(key, SAMPLE_SALT + t)``), so every node — and the matrix
simulator — sees the identical delay draw with zero coordination bytes.
Both directions of a physical link share one delay (the canonical edge
indexing of :func:`~repro.comm.stochastic._index_schedule_edges`), which is
what keeps the update average-preserving (see below).

The algorithm (paper Algorithm 2 with delayed public copies); per node i,
per gossip round t:

    q_i      = Q(x_i - x_hat_i)        one compression, all rounds ship it
    x_hat_i += q_i                     own ring buffer records q_i
    S_r     += received q              per-round source replica (fresh)
    ring_r   records the received q    (per-round receive ring buffer)
    d        = sampled delay of node i's round-r edge
    x_i     += gamma * sum_r v_r[i] * (x_hat_src^(t-d) - x_hat_i^(t-d))

where the **stale pair** is reconstructed locally from the rings:

    x_hat_src^(t-d) = S_r     - sum_{j<d} ring_r[j]
    x_hat_i^(t-d)   = x_hat_i - sum_{j<d} own_ring[j]

Three properties fall out of this construction:

  * **Average preservation** — node i mixes toward its neighbour's stale
    copy *relative to its own equally-stale copy*; with w_ij = w_ji and the
    per-edge shared delay, the two endpoints' updates cancel pairwise, so
    ``1^T x`` is invariant step by step
    (``test_average_preserved_exactly``).
  * **Zero extra collectives** — every compiled round still ships every
    step (the payload is in flight; only *which snapshot the update reads*
    changes), and the arrived-vs-stale selection is a `where`-mask over the
    static-shape ring slots.  The compiled HLO therefore carries exactly
    the link-failure baseline's permute launches
    (``test_async_permute_count_equals_linkfail``).
  * **Subsumption** — a dropped link is staleness ``infinity`` for one
    step: the link-failure freshness factor (1 - p) is the p -> 1-p limit
    of this module's delay-averaged freshness phi (see
    :meth:`StalenessProcess.expected_matrix`).

Theorem-2 stepsize under staleness: gamma is re-derived from the
*delay-averaged* mixing matrix — per edge, ``E_eff`` delivers the edge
weight at its freshness rate ``phi_e = E[1/(1+d_e)]`` and folds the
remainder into the diagonal (with one global delay distribution this is
exactly ``phi W + (1 - phi) I``, mirroring ``LinkFailureProcess``'s
``E[W] = (1-p) W + p I``); and the delay distribution folds into omega as
the distribution-aware ``omega * phi`` (minimum per-edge phi_e when
straggler edges give links their own distributions; the point mass at tau
recovers the historical worst-case ``omega / (1 + tau)``) —
:meth:`StalenessProcess.effective_omega`.

Per-edge heterogeneity: ``straggler_edges`` / ``straggler_delay_probs``
give named physical links their own delay distribution (default point mass
at tau — a maximally slow link), so one straggler is expressible without
slowing the whole mesh; the engines and the matrix simulator pick this up
automatically because delays enter both ONLY through
:meth:`StalenessProcess.edge_delays`' per-edge cumulative table.

State cost: the engine keeps (1 + tau) own trees (public copy + ring) and
R * (1 + tau) source trees (replica + ring per round) — the per-round
replica machinery of PR 4's process engine extended by a depth-tau ring.
The trainer allocates ``x_hat`` / ``s`` as flat lists accordingly; the
matrix simulator (core/choco_gossip.py ``choco_stale_round``) needs only
(x, x_hat, ring) because the global view makes every replica a row of the
global state.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.schedule import GossipSchedule
from repro.comm.stochastic import TopologyProcess, _index_schedule_edges
from repro.core.compression import Compressor


@dataclasses.dataclass(frozen=True, eq=False)
class StalenessProcess(TopologyProcess):
    """Bounded-staleness delay process over a compiled schedule's edges.

    Each undirected edge of the schedule's support draws an i.i.d. delay
    ``d in {0..max_staleness}`` per gossip round from ``delay_probs``
    (``delay_probs[k]`` = P(d = k); None = uniform).  Both directions of a
    link share the draw, and every node derives the identical draw from the
    shared exchange key — the engines and the matrix simulator never
    exchange a byte of delay metadata.

    ``max_staleness = 0`` forces every edge fresh and reduces the engine to
    the static Algorithm-2 replica form (the link-failure engine at p = 0).

    Per-edge heterogeneity (stragglers): ``straggler_edges`` names physical
    links (canonical ``(min, max)`` node pairs from the schedule's edge
    support) whose delays are drawn from ``straggler_delay_probs`` instead
    of the global ``delay_probs`` — so a single slow link / straggler node
    is expressible without slowing the whole mesh.  ``straggler_delay_probs``
    defaults to the point mass at ``max_staleness`` (a maximally slow link);
    naming an edge outside the schedule's support raises ``ValueError``.
    """
    schedule: GossipSchedule
    max_staleness: int = 1
    delay_probs: Optional[Tuple[float, ...]] = None
    straggler_edges: Optional[Tuple[Tuple[int, int], ...]] = None
    straggler_delay_probs: Optional[Tuple[float, ...]] = None

    kind = "staleness"

    def _normalize_probs(self, probs, what: str) -> Tuple[float, ...]:
        """Validate and normalize a delay distribution over {0..tau}."""
        tau = self.max_staleness
        arr = np.asarray(probs, dtype=np.float64)
        if arr.shape != (tau + 1,):
            raise ValueError(
                f"{what} needs max_staleness + 1 = {tau + 1} "
                f"entries (P(d=0..{tau})), got shape {arr.shape}")
        if arr.min() < 0 or arr.sum() <= 0:
            raise ValueError(f"{what} must be nonnegative with "
                             f"positive mass, got {tuple(arr)}")
        return tuple(float(p) for p in arr / arr.sum())

    def __post_init__(self):
        tau = self.max_staleness
        if tau < 0:
            raise ValueError(f"max_staleness must be >= 0, got {tau}")
        if self.schedule.n_rounds == 0:
            raise ValueError("staleness process needs a schedule with at "
                             "least one round (n >= 2)")
        if self.delay_probs is None:
            probs = tuple(1.0 / (tau + 1) for _ in range(tau + 1))
        else:
            probs = self._normalize_probs(self.delay_probs, "delay_probs")
        object.__setattr__(self, "delay_probs", probs)
        edges, round_edge_ids, round_recv = _index_schedule_edges(
            self.schedule)
        object.__setattr__(self, "n_edges", len(edges))
        object.__setattr__(self, "_edges", edges)
        object.__setattr__(self, "round_edge_ids", round_edge_ids)
        object.__setattr__(self, "round_recv", round_recv)
        # per-edge delay distributions: global row everywhere, straggler
        # rows overridden (point mass at tau unless given explicitly)
        if self.straggler_delay_probs is not None \
                and self.straggler_edges is None:
            raise ValueError("straggler_delay_probs given without "
                             "straggler_edges")
        table = np.tile(np.asarray(probs), (max(len(edges), 1), 1))
        if self.straggler_edges is not None:
            if self.straggler_delay_probs is None:
                sprobs = tuple(0.0 for _ in range(tau)) + (1.0,)
            else:
                sprobs = self._normalize_probs(self.straggler_delay_probs,
                                               "straggler_delay_probs")
            object.__setattr__(self, "straggler_delay_probs", sprobs)
            canon = []
            edge_pos = {e: k for k, e in enumerate(edges)}
            for a, b in self.straggler_edges:
                e = (min(int(a), int(b)), max(int(a), int(b)))
                if e not in edge_pos:
                    raise ValueError(
                        f"unknown straggler edge {a}-{b}: the schedule's "
                        f"edge support is {list(edges)}")
                canon.append(e)
                table[edge_pos[e]] = np.asarray(sprobs)
            object.__setattr__(self, "straggler_edges", tuple(canon))
        object.__setattr__(self, "edge_delay_probs",
                           tuple(tuple(float(p) for p in row)
                                 for row in table))
        # per-round source node per destination (self when not receiving):
        # the simulator reads replicas as rows src_r of the global state
        n = self.schedule.n
        srcs = []
        for rnd in self.schedule.rounds:
            sv = np.arange(n)
            for src, dst in rnd.perm:
                sv[dst] = src
            srcs.append(tuple(int(v) for v in sv))
        object.__setattr__(self, "round_src", tuple(srcs))

    # -- delay statistics ---------------------------------------------------

    @property
    def mean_delay(self) -> float:
        """E[d] under ``delay_probs``."""
        return float(sum(k * p for k, p in enumerate(self.delay_probs)))

    @property
    def freshness(self) -> float:
        """phi = E[1/(1+d)] — the delay-averaged rate factor: a fixed
        delay-d exchange advances consensus at ~1/(1+d) the fresh rate, so
        phi is the expected fraction of a fresh exchange each edge delivers
        per step.  phi = 1 at tau = 0; a dropped link is the phi -> 0
        (d -> infinity) limit, recovering the LinkFailure model.  This is
        the GLOBAL distribution's phi; straggler edges carry their own
        (see :attr:`edge_freshness`)."""
        return float(sum(p / (1.0 + k)
                         for k, p in enumerate(self.delay_probs)))

    @property
    def edge_freshness(self) -> Tuple[float, ...]:
        """Per-edge phi_e = E[1/(1+d_e)] under each edge's own delay
        distribution — equals ``(freshness,) * n_edges`` when no straggler
        edges are configured."""
        return tuple(float(sum(p / (1.0 + k) for k, p in enumerate(row)))
                     for row in self.edge_delay_probs)

    # -- sampling (the shared-seed determinism contract) --------------------

    def edge_delays(self, key: jax.Array, t: int) -> jax.Array:
        """(n_edges,) int32 delays for gossip round t — identical on every
        node (pure function of the shared exchange key).  Inverse-CDF over
        each edge's static cumulative delay distribution, same lowering
        rationale as ``MatchingProcess.round_index`` (searchsorted-free).
        Without straggler edges every row of the cumulative table is the
        global distribution, so the draw is bit-identical to the historical
        single-distribution sampler (same uniforms, same thresholds)."""
        k = self._sample_key(key, t)
        u = jax.random.uniform(k, (max(self.n_edges, 1),))
        cum = np.cumsum(np.asarray(self.edge_delay_probs), axis=1)[:, :-1]
        return jnp.sum(u[:, None] >= jnp.asarray(cum, jnp.float32),
                       axis=1).astype(jnp.int32)

    def round_delays(self, delays: jax.Array):
        """Per-round (n,) per-destination delay vectors from the edge
        delays (0 where the round's partial permutation skips a node — the
        zero receive weight annihilates the term anyway)."""
        out = []
        for ids in self.round_edge_ids:
            idx = jnp.asarray(ids)
            out.append(jnp.where(idx >= 0, delays[jnp.clip(idx, 0)], 0))
        return out

    def round_delay_vecs(self, key: jax.Array, t: int):
        """Convenience for the matrix simulator: sampled per-round
        per-destination delays for gossip round t."""
        return self.round_delays(self.edge_delays(key, t))

    # -- theory surrogates for the trainer ----------------------------------

    def sample_matrix(self, key: jax.Array, t: int) -> jax.Array:
        raise NotImplementedError(
            "a bounded-staleness step mixes SNAPSHOTS from up to tau steps "
            "back — it is not a single (n, n) matrix on the current "
            "iterates.  Use core.choco_gossip.choco_stale_round (the "
            "delay-expanded simulator) for parity checks, and "
            "expected_matrix() for the delay-averaged theory surrogate.")

    def expected_matrix(self) -> np.ndarray:
        """Delay-averaged effective mixing matrix, built PER EDGE: each
        edge delivers its off-diagonal weight at its own
        freshness-discounted rate phi_e = E[1/(1+d_e)], the undelivered
        remainder ``(1 - phi_e) w`` folds into the destination's diagonal.
        With a single global delay distribution every phi_e = phi and this
        collapses to the historical ``phi W + (1 - phi) I`` exactly (rows
        of W sum to 1, so the folded remainders complete the diagonal).
        Same shape as the link-failure ``E[W] = (1-p) W + p I`` — a drop is
        the d -> infinity (phi -> 0) staleness limit — and what
        ``expected_delta_beta`` hands the Theorem-2 stepsize."""
        from repro.comm.schedule import round_recv_vec
        phis = self.edge_freshness
        E = np.diag(np.asarray(self.schedule.self_weights,
                               dtype=np.float64))
        for r, rnd in enumerate(self.schedule.rounds):
            recv = round_recv_vec(rnd, self.n)
            for src, dst in rnd.perm:
                e = self.round_edge_ids[r][dst]
                phi = phis[e] if e >= 0 else 1.0
                E[dst, src] += phi * recv[dst]
                E[dst, dst] += (1.0 - phi) * recv[dst]
        return E

    def effective_omega(self, omega: float) -> float:
        """Fold the delay distribution into the compression quality: a
        delay-d edge reads a snapshot missing the last d compressed
        increments, inflating the accumulated-compression-error term —
        exactly where omega enters the Theorem-2 Lyapunov recursion — by
        the same 1/(1+d) freshness factor that discounts the mixing.  The
        distribution-aware constant is ``omega_eff = omega * phi`` with
        phi = E[1/(1+d)] (the point mass at d = tau recovers the historical
        worst-case ``omega / (1 + tau)``; exact at tau = 0 where phi = 1).
        With straggler edges the SLOWEST edge governs the worst
        accumulated-error path, so the minimum per-edge phi_e is used."""
        return omega * min(self.edge_freshness)


# ---------------------------------------------------------------------------
# distributed engine (packed + per-leaf)
# ---------------------------------------------------------------------------

def make_async_choco_fn(*, axes: Tuple[str, ...], sizes: Tuple[int, ...],
                        process: StalenessProcess, compressor: Compressor,
                        gamma: float, gossip_steps: int = 1,
                        packed: bool = True,
                        pack_align: Optional[int] = None,
                        leaf_routes: Optional[list] = None) -> Callable:
    """Bounded-staleness CHOCO exchange for shard_map.

    Returns ``local_fn(key, x_half, hat_list, s_list)`` where

      * ``hat_list`` — (1 + tau) trees: the own public copy x_hat followed
        by the own ring (``hat_list[1 + j]`` = own q of j steps ago);
      * ``s_list`` — R * (1 + tau) trees: per-round source replicas S_r
        (``s_list[r]``) followed by the per-round receive rings
        (``s_list[R + r * tau + j]`` = round-r received q of j steps ago).

    Every compiled round ships the one shared payload every step — the wire
    schedule is IDENTICAL to the link-failure engine's (zero extra permute
    launches) — and the sampled per-edge delay only selects which ring
    prefix to subtract:

        stale_nbr - stale_own = (S_r - x_hat) - sum_{j<d} (ring_r[j] - own_ring[j])

    The masks ``[j < d]`` are where-style f32 scalars over static-shape ring
    slots, so the compiled step stays static-shape with no control flow.
    Replica consistency is the same argument as the link-failure engine's:
    the payload is ALWAYS sent and ALWAYS integrated (staleness gates only
    the snapshot the mixing update reads), so S_r tracks the round-r
    source's x_hat exactly and the rings hold its true last-tau increments.
    """
    n = 1
    for sz in sizes:
        n *= sz
    assert process.n == n, f"process n={process.n} != mesh extent {n}"
    assert gossip_steps >= 1
    from repro.comm.gossip import (_LazyFlatIndex, _ef_send_half,
                                   _make_compress_stage, _pack_align)
    from repro.comm.stages import stage
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    align = _pack_align(compressor, pack_align)
    rounds = process.schedule.rounds
    R = len(rounds)
    tau = process.max_staleness
    compress_stage = _make_compress_stage(compressor, packed=packed,
                                          align=align,
                                          leaf_routes=leaf_routes)

    def local_fn(key, x_half, hat_list, s_list):
        sample_key = key
        for a in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        leaves_x, treedef = jax.tree_util.tree_flatten(x_half)
        hat = treedef.flatten_up_to(hat_list[0])
        own_ring = [treedef.flatten_up_to(tr) for tr in hat_list[1:]]
        S = [treedef.flatten_up_to(s_list[r]) for r in range(R)]
        rings = [[treedef.flatten_up_to(s_list[R + r * tau + j])
                  for j in range(tau)] for r in range(R)]
        flat_idx = _LazyFlatIndex(axes, sizes)
        i = flat_idx()
        for t in range(gossip_steps):
            tkey = key if t == 0 else jax.random.fold_in(key, t)
            payloads, q_trees, hat, dense_fn = _ef_send_half(
                compress_stage, tkey, leaves_x, hat)
            if tau:
                own_ring = [q_trees] + own_ring[:-1]
            with stage("mix"):
                dvecs = process.round_delays(
                    process.edge_delays(sample_key, t))
            acc = [jnp.zeros((), a.dtype) for a in leaves_x]
            for r in range(R):
                with stage("mix"):
                    got = jax.lax.ppermute(payloads, axis_arg,
                                           list(rounds[r].perm))
                    recv_dense = dense_fn(got)
                with stage("ef_update"):
                    recv_trees = [rd.reshape(sv.shape).astype(sv.dtype)
                                  for sv, rd in zip(S[r], recv_dense)]
                    # the replica ALWAYS integrates (the payload was sent;
                    # the delay gates only which snapshot the update reads)
                    S[r] = [sv + rt for sv, rt in zip(S[r], recv_trees)]
                    if tau:
                        rings[r] = [recv_trees] + rings[r][:-1]
                    d = dvecs[r][i]
                    wv = jnp.asarray(process.round_recv[r], jnp.float32)[i]
                    diff = [sr - h for sr, h in zip(S[r], hat)]
                    for j in range(tau):
                        m = (d > j).astype(jnp.float32)
                        diff = [df - m * (rr - orr)
                                for df, rr, orr in zip(diff, rings[r][j],
                                                       own_ring[j])]
                    acc = [a + wv * df for a, df in zip(acc, diff)]
            with stage("ef_update"):
                # acc is f32 (strong per-node weights / masks): cast the
                # whole update back so bf16 params stay bf16
                leaves_x = [a + (gamma * ac).astype(a.dtype)
                            for a, ac in zip(leaves_x, acc)]
        u = treedef.unflatten
        new_hat_list = [u(hat)] + [u(tr) for tr in own_ring]
        new_s_list = ([u(S[r]) for r in range(R)]
                      + [u(rings[r][j]) for r in range(R)
                         for j in range(tau)])
        return u(leaves_x), new_hat_list, new_s_list

    return local_fn
