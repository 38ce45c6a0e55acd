"""Pipelined CHOCO gossip: hide the compressed exchange behind the backward pass.

Audited in EXPERIMENTS.md §Perf H (HLO overlap audit, benchmarks/
bench_overlap.py); distributed acceptance in tests/test_pipelined.py.

Every synchronous engine before this module puts the exchange on the
critical path: the payload is compressed from the POST-gradient iterate
``x_half``, so the collective cannot start until the backward pass has
finished, and the update cannot finish until the collective lands.  All the
wire bytes compression saves still serialize behind the matmuls.

This engine reorders one thing: the payload is compressed from the iterate
*before* the concurrent gradient is applied, and the received payload is
integrated into the update of the *next* round.  Per node i, per round t:

    q_t      = Q(x_t - x_hat_t)          compress BEFORE the update
    x_{t+1}  = x_t + gamma (s_t - x_hat_t)   <- round t-1's payload
    x_hat_{t+1} = x_hat_t + q_t
    s_{t+1}  = s_t + sum_j w_ij q_{t,j}      <- lands in the t+1 update

Inside the trainer's step function the ppermute of ``q_t`` therefore has NO
consumer in the current x-update — its result only feeds the carried state
``s`` — so the collective's start/done pair is free of any data dependency
on the forward/backward compute and XLA may schedule the transfer
concurrently with the gradient matmuls (the property bench_overlap.py
audits in the compiled HLO).  The wire schedule is byte-for-byte the static
engine's: same payloads, same permute rounds, zero extra collectives.

Why this is principled rather than a heuristic: the recursion above is
exactly PR 5's bounded-staleness algebra with a DETERMINISTIC delay of 1 on
every edge (``StalenessProcess(delay_probs=(0, 1))`` — see
:func:`pipeline_delay_process`).  The stale pair the update reads,
``(s_t, x_hat_t)``, is the depth-1 ring reconstruction
``(S_r - ring_r[0], x_hat - own_ring[0])`` summed over rounds; because the
delay is uniform and every round ships every step, the rings collapse into
the carry itself — the carry IS the stale snapshot and the freshly
integrated ``(s_{t+1}, x_hat_{t+1})`` is its double buffer.  No replica
trees, no ring state: the TrainState layout is identical to the static
engine's, which is what keeps old checkpoints structurally restorable.

Theorem-2 stepsize: gamma is re-derived from the tau=1 delay surrogate —
(delta, beta) from the delay-averaged mixing matrix
``E_eff = (W + I) / 2`` (freshness phi = E[1/(1+d)] = 1/2 at deterministic
d = 1) and the staleness fold ``omega_eff = omega / (1 + tau) = omega / 2``
from ``StalenessProcess.effective_omega``.  The matrix twin of this engine
is ``core.choco_gossip.choco_pipelined_round``; per-step engine==simulator
parity is asserted in tests/test_pipelined.py.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax

from repro.comm.schedule import GossipSchedule
from repro.comm.stages import stage
from repro.core.compression import Compressor, Identity


def pipeline_delay_process(schedule: GossipSchedule):
    """The tau=1 deterministic-delay surrogate the pipelined gamma is
    derived from: a :class:`~repro.comm.async_gossip.StalenessProcess` with
    ``delay_probs = (0, 1)`` (every edge's payload is exactly one round
    late).  The trainer reads ``expected_delta_beta()`` and
    ``effective_omega`` from it; tests drive the delay-expanded stale
    simulator with it to cross-check the compact pipelined recursion."""
    from repro.comm.async_gossip import StalenessProcess
    return StalenessProcess(schedule, max_staleness=1,
                            delay_probs=(0.0, 1.0))


def _pipelined_leaf_updates(leaves_x, leaves_s, leaves_hat, q_leaves,
                            nbr_leaves, w_self, w_nbr, gammas):
    """The pipelined twin of ``gossip._choco_leaf_updates``: x reads the
    PRE-round (s, x_hat) carry, s integrates this round's payload for the
    next update.  Elementwise per leaf; XLA fuses these."""
    new_s, new_x = [], []
    with stage("ef_update"):
        for lx, ls, lhat, qd, nb, g in zip(leaves_x, leaves_s, leaves_hat,
                                           q_leaves, nbr_leaves, gammas):
            new_x.append(lx + g * (ls - lhat).astype(lx.dtype))
            sn = ls + (w_self * qd + w_nbr * nb).reshape(
                lx.shape).astype(ls.dtype)
            new_s.append(sn)
    return new_s, new_x


def make_pipelined_choco_fn(*, axes: Tuple[str, ...], sizes: Tuple[int, ...],
                            schedule: GossipSchedule,
                            compressor: Compressor, gamma,
                            gossip_steps: int = 1,
                            exact_small_leaves: bool = False,
                            small_leaf_threshold: int = 8_192,
                            packed: bool = True,
                            pack_align: Optional[int] = None,
                            leaf_routes: Optional[list] = None,
                            kernel_backend: str = "jnp") -> Callable:
    """Returns local_fn(key, x, x_hat, s) -> (x, x_hat, s) for shard_map —
    same signature and state trees as the static choco engine, implementing
    the pipelined recursion of the module docstring ``gossip_steps`` times.

    The send half (compress + x_hat advance) and receive half (schedule
    replay) are the static engine's factored helpers
    (``_packed_self_half`` / ``_per_leaf_self_half`` + ``_neighbor_sum``),
    so packed/per-leaf wire formats, exact-small-leaf routing, and payload
    randomness are byte-identical to the serial exchange; only the update
    ordering differs.  ``gamma`` may be a float or a
    :class:`~repro.core.choco_gossip.GammaSpec` (per-bucket Theorem-2
    stepsizes, packed engine only).

    kernel_backend: resolved backend for the COMPRESS stage only
    (kernels/dispatch.py, threaded to ``_packed_self_half``).  The fused
    bucket-space EF path does not apply here: the pipelined x-update reads
    the PRE-round (s, x_hat) carry, not the freshly integrated pair the
    fused kernel produces, so pallas fuses the quantize and the update
    stays the leaf-wise jnp recursion above (bit-exact either way).
    """
    from repro.comm.gossip import (_LazyFlatIndex, _broadcast_gammas,
                                   _choco_leaf_updates, _flatten_states,
                                   _mix, _pack_align,
                                   _packed_self_half, _per_leaf_self_half,
                                   _resolve_leaf_gammas, _self_weight,
                                   _weight_groups)
    from repro.core.choco_gossip import GammaSpec
    del _choco_leaf_updates  # serial-order twin; documented contrast only
    identity = Identity()
    if isinstance(gamma, GammaSpec) and not packed:
        raise ValueError(
            "per-bucket gamma (GammaSpec) requires the packed engine: the "
            "legacy per-leaf exchange has no bucket spec to derive omegas "
            "from — pass a float gamma, or packed=True")
    n = 1
    for sz in sizes:
        n *= sz
    assert schedule.n == n, f"schedule n={schedule.n} != mesh extent {n}"
    assert gossip_steps >= 1
    axis_arg = axes[0] if len(axes) == 1 else tuple(axes)
    align = _pack_align(compressor, pack_align)
    groups = _weight_groups(schedule)

    def packed_local_fn(key, x, x_hat, s):
        from repro.comm.packing import (bucket_dense, make_bucket_spec,
                                        unpack_leaves)
        for a in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        leaves_x, leaves_hat, leaves_s, treedef = _flatten_states(x, x_hat, s)
        spec = make_bucket_spec(leaves_hat, align=align,
                                exact_small_leaves=exact_small_leaves,
                                small_leaf_threshold=small_leaf_threshold,
                                routes=leaf_routes)
        gammas = _broadcast_gammas(
            _resolve_leaf_gammas(gamma, spec, compressor), len(leaves_x))
        flat_idx = _LazyFlatIndex(axes, sizes)
        for t in range(gossip_steps):
            tkey = key if t == 0 else jax.random.fold_in(key, t)
            payloads, q_leaves, new_hat = _packed_self_half(
                compressor, tkey, leaves_x, leaves_hat, spec,
                backend=kernel_backend)
            dense_fn = lambda got: [bucket_dense(g, b) for g, b
                                    in zip(got, spec.buckets)]
            nbr_leaves, w_nbr = _mix(payloads, q_leaves, groups, axis_arg,
                                     dense_fn, flat_idx)
            if groups:                     # bucket space -> leaves
                with stage("unpack"):
                    nbr_leaves = unpack_leaves(spec, nbr_leaves)
            with stage("mix"):
                w_self = _self_weight(schedule, flat_idx)
            leaves_s, leaves_x = _pipelined_leaf_updates(
                leaves_x, leaves_s, leaves_hat, q_leaves, nbr_leaves,
                w_self, w_nbr, gammas)
            leaves_hat = new_hat
        u = treedef.unflatten
        return u(leaves_x), u(leaves_hat), u(leaves_s)

    if packed:
        return packed_local_fn

    def per_leaf_local_fn(key, x, x_hat, s):
        for a in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(a))
        leaves_x, leaves_hat, leaves_s, treedef = _flatten_states(x, x_hat, s)
        gammas = _broadcast_gammas(gamma, len(leaves_x))
        flat_idx = _LazyFlatIndex(axes, sizes)
        for t in range(gossip_steps):
            tkey = key if t == 0 else jax.random.fold_in(key, t)
            payloads, dense_fns, q_dense, new_hat = _per_leaf_self_half(
                compressor, identity, exact_small_leaves,
                small_leaf_threshold, tkey, leaves_x, leaves_hat)
            dense_fn = lambda got: [dfn(g) for dfn, g
                                    in zip(dense_fns, got)]
            nbr_sum, w_nbr = _mix(payloads, q_dense, groups, axis_arg,
                                  dense_fn, flat_idx)
            with stage("mix"):
                w_self = _self_weight(schedule, flat_idx)
            leaves_s, leaves_x = _pipelined_leaf_updates(
                leaves_x, leaves_s, leaves_hat, q_dense, nbr_sum,
                w_self, w_nbr, gammas)
            leaves_hat = new_hat
        u = treedef.unflatten
        return u(leaves_x), u(leaves_hat), u(leaves_s)

    return per_leaf_local_fn
