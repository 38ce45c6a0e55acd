"""In-graph Lyapunov / consensus diagnostics.

Computes the paper's quantities on the live TrainState, every
``--diag-every`` steps:

* consensus distance ``sum_i ||x_i - xbar||^2`` (the curve of Figs 2-3),
* error-feedback residual ``sum_i ||x_i - x_hat_i||^2`` — replica-aware:
  a matching process keeps R per-round reference trees (averaged), the
  bounded-staleness engine keeps [public copy + tau ring] (the public
  copy is the residual's x_hat),
* their sum Xi_t, the Theorem-2 Lyapunov that must contract linearly,
* a measured compression-error sample vs the Assumption-1 bound
  ``1 - omega``,
* the push-sum weight spread ``max w / min w``.

The diagnostics are a **separate** jitted function — the fast-path train
step is never touched, so with telemetry off the compiled train-step HLO
is byte-identical to the pre-telemetry build (``telemetry_off``
invariant, ``benchmarks/bench_telemetry.py``).  This module is traced
code: it lives under the same purity contract as ``comm``/``core`` (no
wall clocks, no host RNG, no file I/O) — host-side emission lives in
``obs/sinks.py``.
"""
from __future__ import annotations

from typing import Callable, List

import jax
import jax.numpy as jnp


def _sq(x) -> jax.Array:
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def _consensus_distance(params) -> jax.Array:
    """sum_i ||x_i - xbar||^2 over every leaf (node dim leading)."""
    total = jnp.zeros((), jnp.float32)
    for leaf in jax.tree.leaves(params):
        x = leaf.astype(jnp.float32)
        total = total + _sq(x - jnp.mean(x, axis=0, keepdims=True))
    return total


def _residual(params, hat_tree) -> jax.Array:
    """sum_i ||x_i - x_hat_i||^2 for one reference tree."""
    total = jnp.zeros((), jnp.float32)
    for x, h in zip(jax.tree.leaves(params), jax.tree.leaves(hat_tree)):
        total = total + _sq(x.astype(jnp.float32) - h.astype(jnp.float32))
    return total


def _ef_trees(trainer, x_hat) -> List:
    """Reference trees the EF residual averages over, engine-aware."""
    if not isinstance(x_hat, (list, tuple)):
        return [x_hat]
    if trainer.process is not None and trainer.process.kind == "staleness":
        return [x_hat[0]]   # [public copy + tau ring]: the copy is x_hat
    return list(x_hat)      # matching: R per-round references


def _compression_error(compressor, key, params, hat_tree):
    """One measured sample of ||Q(d) - d||^2 / ||d||^2 on the current
    deltas d = x - x_hat (per node, per leaf — the quantity Assumption 1
    bounds by 1 - omega in expectation)."""
    num = jnp.zeros((), jnp.float32)
    den = jnp.zeros((), jnp.float32)
    leaves = list(zip(jax.tree.leaves(params), jax.tree.leaves(hat_tree)))
    for idx, (x, h) in enumerate(leaves):
        d = (x.astype(jnp.float32) - h.astype(jnp.float32))
        d = d.reshape(d.shape[0], -1)          # (n_nodes, leaf)
        if compressor.stochastic:
            keys = jax.random.split(jax.random.fold_in(key, idx), d.shape[0])
            q = jax.vmap(compressor.apply)(keys, d)
        else:
            q = jax.vmap(lambda row: compressor.apply(None, row))(d)
        num = num + _sq(q - d)
        den = den + _sq(d)
    return num / jnp.maximum(den, jnp.float32(1e-30))


def make_diagnostics_fn(trainer) -> Callable:
    """Build the (unjitted) diagnostics function ``state -> {metric key:
    f32 scalar}`` for one trainer.  Keys are registry names
    (``obs/schema.py``); modes without error-feedback state (plain /
    allreduce) emit the consensus distance only."""
    ef = trainer.mode in ("choco", "pushsum")
    compressor = trainer.compressor
    bound = (1.0 - trainer._worst_omega()) if compressor is not None else None

    def diagnostics(state) -> dict:
        out = {"diag/consensus_dist": _consensus_distance(state.params)}
        if ef:
            trees = _ef_trees(trainer, state.x_hat)
            res = sum(_residual(state.params, t) for t in trees) / len(trees)
            out["diag/ef_residual"] = res
            out["diag/lyapunov"] = out["diag/consensus_dist"] + res
            # same key derivation as the exchange, salted so the measured
            # sample never replays a payload draw
            key = jax.random.fold_in(
                jax.random.fold_in(state.key, state.step), 0xD1A6)
            out["diag/compress_err"] = _compression_error(
                compressor, key, state.params, trees[0])
            out["diag/compress_err_bound"] = jnp.float32(bound)
        if state.psw is not None:
            w = state.psw.astype(jnp.float32)
            out["diag/psw_spread"] = jnp.max(w) / jnp.maximum(
                jnp.min(w), jnp.float32(1e-30))
        return out

    return diagnostics


def jitted_diagnostics(trainer, state_shape):
    """Jit the diagnostics under the trainer's state shardings — a
    SEPARATE executable from the train step (the fast path never pays for
    it, compiled or not).  Returns ``fn(state) -> {key: scalar array}``."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    specs = trainer.state_pspecs(state_shape)
    shard = jax.tree.map(lambda s: NamedSharding(trainer.mesh, s), specs,
                         is_leaf=lambda x: isinstance(x, P))
    return jax.jit(make_diagnostics_fn(trainer), in_shardings=(shard,))


def bucket_telemetry(trainer) -> dict:
    """Host-side static telemetry for the run header: per-bucket wire
    bytes, effective Theorem-2 gamma and, for top-k compressors, the
    selection path (``threshold`` or ``sort``) with its rows and k per
    row, of the packed exchange (empty bucket list for per-leaf /
    uncompressed modes)."""
    out = {"gamma": float(trainer.gamma), "wire_bytes_round": 0,
           "buckets": []}
    if trainer.compressor is None:
        return out
    spec = trainer._bucket_spec()
    if spec is None:    # legacy per-leaf engine: representative-d analytics
        out["wire_bytes_round"] = int(
            trainer.compressor.wire_bits(1 << 20)) // 8
        return out
    from repro.comm.packing import (bucket_omegas, bucket_selection,
                                    bucket_wire_bits)
    omegas = bucket_omegas(spec, trainer.compressor)
    bits = bucket_wire_bits(spec, trainer.compressor)
    picks = bucket_selection(spec, trainer.compressor)
    for b, omega, wb, pick in zip(spec.buckets, omegas, bits, picks):
        gamma = (trainer.gamma_spec.value(omega)
                 if trainer.gamma_spec is not None else trainer.gamma)
        out["buckets"].append({
            "index": int(b.index), "elems": int(b.logical),
            "exact": bool(b.exact), "omega": float(omega),
            "gamma": float(gamma), "wire_bytes": int(wb) // 8,
            **(pick or {})})
    out["wire_bytes_round"] = sum(e["wire_bytes"]
                                  for e in out["buckets"])
    return out

