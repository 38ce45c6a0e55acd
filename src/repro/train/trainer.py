"""Decentralized trainer: CHOCO-SGD over a device mesh.

State layout: every decentralized leaf (params, x_hat, s, optimizer moments)
carries a leading node dim of size n_nodes, sharded over the gossip mesh axis.
One train step =
    per-node grad (vmap over the node dim -> zero cross-node collectives)
  -> local optimizer half-step
  -> CHOCO gossip exchange (shard_map + ppermute of compressed payloads).
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ChocoConfig, parse_topology
from repro.core.compression import make_compressor
from repro.core.choco_gossip import GammaSpec, theorem2_stepsize
from repro.core.topology import is_directed, make_topology, torus2d
from repro.comm import stages
from repro.comm.gossip import make_gossip_exchange
from repro.comm.schedule import compile_directed_schedule, compile_schedules
from repro.models.transformer import Model
from repro.optim.sgd import Optimizer, OptState
from repro.launch.sharding import param_pspecs, batch_pspecs

#: ChocoConfig fields deliberately OUTSIDE the checkpoint fingerprint, with
#: the reason each omission is safe.  The fingerprint-coverage lint
#: (analysis/fingerprint_lint.py) enforces that every field is either read
#: by fingerprint() or listed here — silently un-fingerprinted fields are a
#: restore-correctness hazard.
FINGERPRINT_EXEMPT = {
    "kernel_backend": "execution detail: flipping jnp<->pallas changes "
                      "neither the state layout nor the wire bytes, so "
                      "resumes must stay backend-portable",
    "gossip_axis": "covered structurally: fingerprint() records the mesh "
                   "axis sizes and the resolved gossip_axes tuple, which "
                   "subsumes the raw axis-name string",
    "consensus_gamma": "stepsize override, like lr: it scales the mixing "
                       "update but changes no state layout, bucket spec, "
                       "or wire format — resuming under a different gamma "
                       "is a hyperparameter change, not a shape change",
    "data_skew_alpha": "data-pipeline knob: the Dirichlet shard shapes "
                       "which samples each node draws but touches no "
                       "state layout, mixing matrix, gamma, or wire "
                       "format — resuming under a different skew is a "
                       "data change, like swapping the input stream",
}


class TrainState(NamedTuple):
    params: Any      # (n_nodes, ...) leaves — the x_i of Algorithm 2
    x_hat: Any       # public copies (list of per-round reference trees when
                     #   a matching topology process is active)
    s: Any           # weighted neighbour aggregates (list of per-round
                     #   source-replica trees under a topology process)
    opt: OptState    # per-node optimizer moments
    step: jax.Array
    key: jax.Array
    psw: Any = None  # push-sum (n, 1) weight column; None outside pushsum
                     #   mode (None leaves vanish from the pytree, so every
                     #   non-pushsum state keeps its pre-PR structure)


@dataclasses.dataclass
class DecentralizedTrainer:
    model: Model
    choco: ChocoConfig
    mesh: Any
    n_nodes: int
    optimizer: Optimizer
    lr_fn: Callable[[jax.Array], jax.Array]
    mode: str = "choco"          # choco | plain | allreduce

    def __post_init__(self):
        cfg = self.model.cfg
        self.compressor = (make_compressor(self.choco.compressor, **self.choco.comp_dict())
                           if self.mode in ("choco", "pushsum") else None)
        axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        names = parse_topology(self.choco.topology)
        directed = [n for n in names if is_directed(n)]
        if directed and self.mode != "pushsum":
            raise ValueError(
                f"topology={self.choco.topology!r} is directed "
                f"(column-stochastic): the symmetric {self.mode!r} engine "
                f"would average with a non-row-stochastic matrix and "
                f"converge to a Perron-biased point, not the mean.  Directed "
                f"graphs require the push-sum engine: mode='pushsum' "
                f"(comm/pushsum.py, de-biased x/w).")
        if self.mode == "pushsum" and len(names) != 1:
            raise ValueError(
                f"push-sum runs one directed schedule; time-varying "
                f"sequences are not supported (got topology="
                f"{self.choco.topology!r})")
        if self.choco.topology_process is not None:
            if self.mode not in ("choco", "plain"):
                raise ValueError(
                    f"topology_process={self.choco.topology_process!r} runs "
                    f"on the choco/plain engines; mode={self.mode!r} (the "
                    f"push-sum engine owns its directed schedule, allreduce "
                    f"has no gossip graph)")
            if len(names) != 1:
                raise ValueError(
                    f"a topology process IS the per-step mixing "
                    f"distribution; combining it with the time-varying "
                    f"sequence {self.choco.topology!r} is ambiguous")
            if directed:
                raise ValueError(
                    f"topology processes sample symmetric mixing matrices; "
                    f"{self.choco.topology!r} is directed — use "
                    f"mode='pushsum' without a process")
        # torus on a multi-pod mesh maps onto the (pod, data) ICI grid —
        # paper Table 1 delta = O(1/n) instead of the ring's O(1/n^2); every
        # other topology (and single-pod torus) lives on one gossip axis
        # whose flat index carries the schedule's node ids.  A time-varying
        # sequence containing a torus lifts the WHOLE sequence onto the
        # (pod, data) pair (schedules address flat row-major ids, so any
        # graph runs on the axis tuple) — comma order never changes the
        # node set.
        self.torus = ("torus" in names and "pod" in self.mesh.axis_names)
        if self.torus:
            self.gossip_axis = ("pod", "data")
            n = axes["pod"] * axes["data"]
            self.fsdp_axis = None
            grid = (axes["pod"], axes["data"])
        else:
            self.gossip_axis = self.choco.gossip_axis
            n = axes[self.gossip_axis]
            self.fsdp_axis = "data" if self.gossip_axis == "pod" else None
            grid = None
        assert n == self.n_nodes, \
            f"gossip over {self.gossip_axis} = {n} nodes != n_nodes {self.n_nodes}"
        # compile the (possibly time-varying) topology sequence into static
        # permutation-round schedules — the engine replays them with one
        # lax.ppermute per round.  Directed topologies compile through the
        # bipartite-coloring compiler for the push-sum engine.
        self.topologies = tuple(
            torus2d(*grid) if (name == "torus" and grid is not None)
            else make_topology(name, n) for name in names)
        if directed:
            self.schedules = (compile_directed_schedule(self.topologies[0]),)
        else:
            self.schedules = compile_schedules(self.topologies, grid=grid)
        if (len(self.schedules) > 1
                and self.choco.gossip_steps % len(self.schedules) != 0):
            raise ValueError(
                f"topology={self.choco.topology!r} is a time-varying "
                f"sequence of {len(self.schedules)} graphs: gossip_steps "
                f"must be a multiple of the sequence length so every graph "
                f"runs each SGD step (got {self.choco.gossip_steps})")
        # stochastic topology process over the compiled schedule
        if self.choco.topology_process is not None:
            if (self.choco.topology_process == "staleness"
                    and self.mode != "choco"):
                raise ValueError(
                    f"topology_process='staleness' runs on the compressed "
                    f"choco engine only (mode={self.mode!r}): the stale "
                    f"snapshots are reconstructed from rings of compressed "
                    f"increments — the plain engine ships fresh iterates "
                    f"with no increment stream to ring-buffer")
            from repro.comm.stochastic import make_topology_process
            stragglers = sprobs = None
            if (self.choco.straggler_edges is not None
                    or self.choco.straggler_delay_probs is not None):
                if self.choco.topology_process != "staleness":
                    raise ValueError(
                        f"straggler edges model per-edge DELAYS — they "
                        f"require topology_process='staleness', got "
                        f"{self.choco.topology_process!r}")
                if self.choco.straggler_edges is None:
                    raise ValueError("straggler_delay_probs given without "
                                     "straggler_edges")
                from repro.configs.base import (parse_delay_probs,
                                                parse_straggler_edges)
                stragglers = parse_straggler_edges(
                    self.choco.straggler_edges)
                if self.choco.straggler_delay_probs is not None:
                    sprobs = parse_delay_probs(
                        self.choco.straggler_delay_probs)
            self.process = make_topology_process(
                self.choco.topology_process, self.schedules[0],
                matching_sampler=self.choco.matching_sampler,
                edge_drop_prob=self.choco.edge_drop_prob,
                max_staleness=self.choco.max_staleness,
                straggler_edges=stragglers,
                straggler_delay_probs=sprobs)
        else:
            if (self.choco.straggler_edges is not None
                    or self.choco.straggler_delay_probs is not None):
                raise ValueError(
                    "straggler edges model per-edge DELAYS — they require "
                    "topology_process='staleness', got no topology process")
            self.process = None
        # the kernel backend the exchange runs, resolved once from the
        # platform: fused Pallas kernels on a TPU for the packed static
        # choco engine, jnp elsewhere (kernels/dispatch.py)
        from repro.kernels.dispatch import resolve_backend
        self.kernel_backend = resolve_backend(
            self.choco.kernel_backend,
            engine_eligible=(self.mode == "choco" and self.choco.packed_gossip
                             and self.process is None))
        # pipelined engine (comm/pipelined.py): the exchange is issued on
        # the PRE-gradient iterate and its payload lands in the NEXT step's
        # update — validity requires the compressed-increment recursion
        # (choco), a single static graph, and no stochastic process (the
        # tau=1 delay surrogate below IS this engine's process)
        if self.choco.pipeline_gossip:
            if self.mode != "choco":
                raise ValueError(
                    f"pipeline_gossip hides the COMPRESSED exchange behind "
                    f"the backward pass via the error-feedback recursion; "
                    f"mode={self.mode!r} has no (x_hat, s) carry to "
                    f"double-buffer — it requires mode='choco'")
            if self.process is not None:
                raise ValueError(
                    f"pipeline_gossip is itself a deterministic delay-1 "
                    f"staleness process; stacking topology_process="
                    f"{self.choco.topology_process!r} on top would compound "
                    f"two delay models with no Theorem-2 gamma for the "
                    f"composite — run one or the other")
            if len(self.schedules) > 1:
                raise ValueError(
                    f"pipeline_gossip needs one static schedule: a payload "
                    f"compressed under graph W_k but integrated a step "
                    f"later under W_{{k+1}} breaks the recursion (got "
                    f"time-varying topology={self.choco.topology!r})")
        # Theorem-2 consensus stepsize from the topology and compression;
        # a time-varying sequence takes the conservative worst case, a
        # stochastic process the EXPECTED mixing matrix's (delta, beta)
        # (Koloskova et al. 2020 analyze exactly that quantity)
        self.gamma_spec = None
        if self.choco.consensus_gamma is not None:
            self.gamma = self.choco.consensus_gamma
        elif self.mode in ("choco", "pushsum"):
            omega = self._worst_omega()
            omega_scale = 1.0
            if self.process is not None:
                delta, beta = self.process.expected_delta_beta()
                # staleness folds its bound into the compression quality
                # (omega / (1 + tau)); matching/linkfail leave omega as-is
                omega = self.process.effective_omega(omega)
            elif self.choco.pipeline_gossip:
                # tau=1 surrogate: every payload is exactly one round late,
                # so (delta, beta) come from E_eff = (W + I) / 2 and the
                # staleness bound folds omega -> omega / 2
                from repro.comm.pipelined import pipeline_delay_process
                surrogate = pipeline_delay_process(self.schedules[0])
                delta, beta = surrogate.expected_delta_beta()
                omega_scale = 0.5
                omega = surrogate.effective_omega(omega)
            else:
                delta = min(t.delta for t in self.topologies)
                beta = max(t.beta for t in self.topologies)
            self.gamma = theorem2_stepsize(delta, beta, omega)
            # per-bucket Theorem-2 gamma (packed engine): ship the (delta,
            # beta, omega_scale) recipe instead of the worst-case scalar so
            # each bucket contracts at ITS omega — exact buckets stop being
            # dragged to the top-k stepsize.  self.gamma stays the scalar
            # worst case for logging and the per-leaf/pushsum engines;
            # single-bucket specs resolve to exactly that scalar.
            if (self.mode == "choco" and self.process is None
                    and self.choco.packed_gossip):
                self.gamma_spec = GammaSpec(delta=delta, beta=beta,
                                            omega_scale=omega_scale)
        else:
            self.gamma = 1.0

    def _bucket_spec(self):
        """The packed engine's BucketSpec, derived exactly as the exchange
        derives it (local shard shapes under the param PartitionSpecs);
        None for the legacy per-leaf engine.  Shared by the omega/gamma
        derivation below and the telemetry run header
        (``obs/metrics.py::bucket_telemetry``)."""
        if not self.choco.packed_gossip or self.compressor is None:
            return None
        from repro.comm.gossip import _leaf_routes, _pack_align
        from repro.comm.packing import make_bucket_spec
        shape = self.state_shape()
        specs = param_pspecs(shape.params, self.model.cfg,
                             node_axis=self.gossip_axis,
                             fsdp_axis=self.fsdp_axis, model_size=0)
        spec_leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        # under a matching process x_hat is a LIST of reference trees; the
        # engine compresses one tree's worth of deltas per round either way
        hat_shape = (shape.x_hat[0] if isinstance(shape.x_hat, (list, tuple))
                     else shape.x_hat)
        local = [jax.ShapeDtypeStruct(
                     _local_shape(l.shape, sp, dict(self.mesh.shape)), l.dtype)
                 for l, sp in zip(jax.tree.leaves(hat_shape), spec_leaves)]
        return make_bucket_spec(
            local, align=_pack_align(self.compressor, self.choco.pack_align),
            exact_small_leaves=self.choco.exact_small_leaves,
            small_leaf_threshold=self.choco.small_leaf_threshold,
            routes=_leaf_routes(specs, self.gossip_axis))

    def _worst_omega(self) -> float:
        """Assumption-1 omega for the stepsize: computed from the ACTUAL
        packed bucket sizes (the packed engine compresses per bucket, so the
        contraction is governed by the worst bucket), not a fixed
        representative dimension.  Legacy per-leaf engine keeps the old
        1M-coordinate representative value."""
        if not self.choco.packed_gossip:
            return self.compressor.omega(1 << 20)
        from repro.comm.packing import bucket_omega_worst
        return bucket_omega_worst(self._bucket_spec(), self.compressor)

    # -- state ----------------------------------------------------------------

    def _init_state_fn(self):
        model, n = self.model, self.n_nodes

        sdt = jnp.dtype(self.choco.state_dtype)
        # replica layout under a topology process (comm/gossip.py
        # make_process_choco_fn): matching keeps R per-round own references
        # in x_hat and R source replicas in s; linkfail keeps the single
        # public copy in x_hat and R replicas in s.  ONLY the compressed
        # engine needs replicas — the plain engine ships the fresh iterate,
        # so its x_hat/s stay the (unused) single trees.  Push-sum adds the
        # (n, 1) weight column, init 1.
        replicas = self.process is not None and self.mode == "choco"
        n_rounds = len(self.process.schedule.rounds) if replicas else 0
        matching = replicas and self.process.kind == "matching"
        # bounded staleness (comm/async_gossip.py): x_hat is the
        # [public copy + depth-tau own ring] list, s the [R replicas +
        # R*tau receive rings] list
        stale = replicas and self.process.kind == "staleness"
        tau = self.process.max_staleness if stale else 0
        pushsum = self.mode == "pushsum"

        def init(key):
            pkeys = jax.random.split(key, n)
            params = jax.vmap(model.init)(pkeys)
            ef_zeros = lambda: jax.tree.map(
                lambda p: jnp.zeros(p.shape, sdt if jnp.issubdtype(p.dtype, jnp.floating)
                                    else p.dtype), params)
            opt = self.optimizer.init(params)
            x_hat = ([ef_zeros() for _ in range(n_rounds)] if matching
                     else [ef_zeros() for _ in range(1 + tau)] if stale
                     else ef_zeros())
            s = ([ef_zeros() for _ in range(n_rounds * (1 + tau))] if stale
                 else [ef_zeros() for _ in range(n_rounds)] if n_rounds
                 else ef_zeros())
            psw = jnp.ones((n, 1), jnp.float32) if pushsum else None
            return TrainState(params=params, x_hat=x_hat, s=s,
                              opt=opt, step=jnp.zeros((), jnp.int32),
                              key=key, psw=psw)
        return init

    def state_shape(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.eval_shape(self._init_state_fn(), key)

    def state_pspecs(self, state_shape) -> TrainState:
        cfg = self.model.cfg
        pspec = lambda tree: param_pspecs(tree, cfg, node_axis=self.gossip_axis,
                                          fsdp_axis=self.fsdp_axis, model_size=0)
        opt_shape = state_shape.opt
        opt_spec = OptState(
            mu=None if opt_shape.mu is None else pspec(opt_shape.mu),
            nu=None if opt_shape.nu is None else pspec(opt_shape.nu),
            count=P())
        psw_spec = (None if state_shape.psw is None
                    else P(self.gossip_axis, None))
        return TrainState(params=pspec(state_shape.params),
                          x_hat=pspec(state_shape.x_hat),
                          s=pspec(state_shape.s),
                          opt=opt_spec, step=P(), key=P(), psw=psw_spec)

    def state_shardings(self, state_shape=None) -> TrainState:
        """NamedSharding pytree for the TrainState — the target layout the
        sharded checkpoint restore builds global arrays under directly."""
        shape = state_shape if state_shape is not None else self.state_shape()
        specs = self.state_pspecs(shape)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    def init_state(self, key) -> TrainState:
        shape = self.state_shape(key)
        return jax.jit(self._init_state_fn(),
                       out_shardings=self.state_shardings(shape))(key)

    def node_devices(self, state: TrainState) -> list:
        """Device ids holding each node's shard of the parameters, indexed
        by node: one entry per node, read from the arrays' shardings (no
        device transfer).  Distinct single ids mean one node per device."""
        held = [set() for _ in range(self.n_nodes)]
        for leaf in jax.tree.leaves(state.params):
            for dev, idx in leaf.sharding.devices_indices_map(
                    leaf.shape).items():
                start = idx[0].start or 0
                stop = self.n_nodes if idx[0].stop is None else idx[0].stop
                for node in range(start, stop):
                    held[node].add(dev.id)
        return [sorted(h) for h in held]

    # -- checkpointing ---------------------------------------------------------

    def fingerprint(self) -> dict:
        """Manifest fingerprint: everything the restore path needs to decide
        whether a checkpoint is resume-exact, elastic, or incompatible."""
        axes = (self.gossip_axis if isinstance(self.gossip_axis, tuple)
                else (self.gossip_axis,))
        return {
            "mesh": {a: int(s) for a, s in zip(self.mesh.axis_names,
                                               self.mesh.devices.shape)},
            "gossip_axes": list(axes),
            "n_nodes": int(self.n_nodes),
            "topology": self.choco.topology,
            "gossip_steps": int(self.choco.gossip_steps),
            "mode": self.mode,
            "compressor": self.choco.compressor,
            # hyperparameters behind the name: a resumed run with a
            # different fraction / qsgd_s has a different Assumption-1
            # omega, so its EF state and Theorem-2 gamma are NOT the
            # checkpoint's — restore routes mismatches through the elastic
            # re-mix path.  Packing knobs change the bucket spec the
            # per-bucket gammas are derived from, so they count too.
            "compressor_config": dict(self.choco.comp_kwargs),
            "packed_gossip": bool(self.choco.packed_gossip),
            "pack_align": self.choco.pack_align,
            "exact_small_leaves": bool(self.choco.exact_small_leaves),
            "small_leaf_threshold": int(self.choco.small_leaf_threshold),
            "pipeline_gossip": bool(self.choco.pipeline_gossip),
            "state_dtype": self.choco.state_dtype,
            "topology_process": self.choco.topology_process,
            "edge_drop_prob": self.choco.edge_drop_prob,
            "matching_sampler": self.choco.matching_sampler,
            "max_staleness": self._effective_staleness(),
            # per-edge delay heterogeneity changes the expected mixing
            # matrix (and hence the Theorem-2 gamma the EF state was built
            # under), same hazard class as edge_drop_prob; recorded so a
            # straggler change is visible in the manifest
            "straggler_edges": self.choco.straggler_edges,
            "straggler_delay_probs": self.choco.straggler_delay_probs,
        }

    def _effective_staleness(self) -> int:
        """Staleness bound the state layout actually depends on: tau under
        topology_process='staleness', else 0 — so pre-staleness manifests
        (missing key -> 0) stay resume-exact for every non-async config."""
        return (self.choco.max_staleness
                if self.choco.topology_process == "staleness" else 0)

    def save_checkpoint(self, path: str, state: TrainState,
                        metadata: Optional[dict] = None,
                        keep_last: Optional[int] = None) -> str:
        """Sharded per-host save of the full TrainState (including the CHOCO
        error-feedback states — Theorem 2 needs them across restarts).

        keep_last: after a successful save (manifest rename), delete all but
        the newest k sibling checkpoint dirs (never the one just written) —
        see checkpoint/checkpointing.py gc_checkpoints."""
        from repro.checkpoint.checkpointing import save_sharded
        return save_sharded(path, state, step=int(jax.device_get(state.step)),
                            fingerprint=self.fingerprint(),
                            metadata=metadata or {}, keep_last=keep_last)

    def restore_checkpoint(self, path: str) -> Tuple[TrainState, Any, int]:
        """Restore a sharded checkpoint directly under this trainer's
        shardings (no host-gather, no throwaway init_state donor).

        Returns (state, manifest, warmup_rounds): warmup_rounds > 0 means
        the checkpoint needed an elastic / re-mixed restore — params (and
        optimizer moments) were re-mapped across the node dim, x_hat and s
        were re-zeroed (old public copies are invalid under the new mixing
        matrix W and its Theorem-2 gamma), and the caller should run
        ``consensus_warmup(state, warmup_rounds)`` before training.
        """
        from repro.checkpoint.checkpointing import restore_sharded
        from repro.checkpoint.manifest import read_manifest
        from repro.checkpoint.elastic import (consensus_warmup_rounds,
                                              elastic_ratio)
        man = read_manifest(path)
        shape = self.state_shape()
        shardings = self.state_shardings(shape)
        n_old = man.n_nodes
        saved_topo = man.fingerprint.get("topology")
        same_nodes = n_old is None or n_old == self.n_nodes
        same_graph = saved_topo is None or saved_topo == self.choco.topology
        # a topology-process change re-shapes the replica state (x_hat / s
        # become per-round lists), so it takes the same re-mix path as a
        # graph change; likewise a staleness-bound change re-shapes the
        # ring buffers (stale-buffer subtrees live under the x_hat / s
        # reset prefixes, so the re-shaped lists restore clean)
        fp = man.fingerprint
        same_proc = (fp.get("topology_process", None)
                     == self.choco.topology_process
                     and fp.get("max_staleness", 0)
                     == self._effective_staleness())
        # compression / packing fingerprint: the EF state (x_hat, s) and
        # gamma were built under the checkpoint's omega — a changed
        # compression ratio or bucket layout re-mixes like a graph change.
        # Every key compares with missing-key-matches (.get with the
        # CURRENT value as default) so pre-PR-6 manifests stay resume-exact.
        same_comp = (fp.get("compressor", self.choco.compressor)
                     == self.choco.compressor
                     and fp.get("compressor_config", self.choco.comp_dict())
                     == self.choco.comp_dict()
                     and fp.get("packed_gossip", self.choco.packed_gossip)
                     == bool(self.choco.packed_gossip)
                     and fp.get("pack_align", self.choco.pack_align)
                     == self.choco.pack_align
                     and fp.get("pipeline_gossip",
                                self.choco.pipeline_gossip)
                     == bool(self.choco.pipeline_gossip)
                     and fp.get("exact_small_leaves",
                                self.choco.exact_small_leaves)
                     == bool(self.choco.exact_small_leaves)
                     and fp.get("small_leaf_threshold",
                                self.choco.small_leaf_threshold)
                     == self.choco.small_leaf_threshold)
        same_graph = same_graph and same_proc and same_comp
        if self.mode == "pushsum" and not (same_nodes and same_graph):
            from repro.checkpoint.manifest import ElasticRestoreError
            raise ElasticRestoreError(
                f"elastic restore is not supported for push-sum: the weight "
                f"column w encodes conserved mass (1^T w = n) that a node-"
                f"count or graph change would corrupt (checkpoint "
                f"n_nodes={n_old}, topology={saved_topo!r} -> "
                f"n_nodes={self.n_nodes}, topology={self.choco.topology!r})")
        if same_nodes and (self.mode != "choco" or same_graph):
            return restore_sharded(path, shape, shardings), man, 0
        if not same_nodes:
            elastic_ratio(n_old, self.n_nodes)   # fail fast on bad resize
            state = restore_sharded(path, shape, shardings,
                                    node_remap=(n_old, self.n_nodes),
                                    reset_prefixes=("x_hat", "s"))
        else:
            # same n, different gossip graph: s = sum_j w_ij x_hat_j is an
            # OLD-W aggregate — stale under the new schedule, so re-mix too
            state = restore_sharded(path, shape, shardings,
                                    reset_prefixes=("x_hat", "s"))
        if self.mode != "choco":      # no EF state to re-seed in exact modes
            return state, man, 0
        # warmup contracts at the graph the warmup actually runs on: the
        # process's EXPECTED eigengap when one is active (matching the gamma
        # derivation), the static worst case otherwise
        if self.process is not None:
            delta = self.process.expected_delta_beta()[0]
        else:
            delta = min(t.delta for t in self.topologies)
        return state, man, consensus_warmup_rounds(delta)

    def consensus_warmup(self, state: TrainState, rounds: int) -> TrainState:
        """k rounds of CHOCO-GOSSIP (Algorithm 1) on the current params, no
        gradient step: rebuilds the public copies x_hat and the neighbour
        aggregates s under the CURRENT mixing matrix / Theorem-2 gamma after
        an elastic restore.  Key folds are salted so warmup randomness never
        collides with a training step's fold_in(key, step)."""
        if rounds <= 0 or self.mode != "choco":
            return state
        exchange = self._exchange(state.params)

        def warm(st):
            x, xh, s = st.params, st.x_hat, st.s
            base = jax.random.fold_in(st.key, 0x5EED)
            for r in range(rounds):
                x, xh, s = exchange(jax.random.fold_in(base, r), x, xh, s)
            return st._replace(params=x, x_hat=xh, s=s)

        shardings = self.state_shardings(jax.eval_shape(lambda: state))
        return jax.jit(warm, out_shardings=shardings,
                       donate_argnums=0)(state)

    # -- step -----------------------------------------------------------------

    def make_train_step(self, phase_scopes: bool = False):
        model, opt, lr_fn = self.model, self.optimizer, self.lr_fn
        pushsum = self.mode == "pushsum"
        pipelined = self.choco.pipeline_gossip and self.mode == "choco"
        # jax.named_scope lands in HLO op metadata, so phase names are
        # opt-in (--profile-dir): the default build keeps the compiled step
        # byte-identical to the pre-telemetry HLO (telemetry_off invariant).
        # The exchange's own stages (comm/stages.py) follow the same switch.
        scope = (jax.named_scope if phase_scopes
                 else (lambda name: contextlib.nullcontext()))

        def pipelined_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
            # Two-phase carry (comm/pipelined.py).  Phase A traces the
            # round-k exchange FIRST, on the PRE-gradient iterate: its
            # ppermute payload is Q(x_k - x_hat_k) and its output
            # gx = x_k + gamma (s_k - x_hat_k) consumes only last round's
            # carry — nothing downstream of the batch.  Phase B (grad +
            # optimizer half-step) therefore shares no data dependency
            # with the collective, and XLA overlaps the transfer with the
            # backward matmuls (benchmarks/bench_overlap.py audits this).
            gkey = jax.random.fold_in(state.key, state.step)
            exchange = self._exchange(state.params)
            with scope("obs:exchange"), stages.scoped(phase_scopes):
                gx, new_hat, new_s = exchange(gkey, state.params,
                                              state.x_hat, state.s)

            def loss_fn(p, b):
                loss, metrics = model.loss(p, b)
                return loss, metrics
            with scope("obs:grad"):
                (losses, metrics), grads = jax.vmap(
                    jax.value_and_grad(loss_fn, has_aux=True))(state.params,
                                                               batch)
            lr = lr_fn(state.step)
            with scope("obs:optimizer"):
                x_half, new_opt = opt.update(state.params, grads,
                                             state.opt, lr)

            # merge the independent halves elementwise:
            #   x_{k+1} = x_k - lr g + gamma (s_k - x_hat_k)
            #           = gx + (x_half - x_k)
            new_params = jax.tree.map(lambda g, xh, x: g + (xh - x),
                                      gx, x_half, state.params)
            out = TrainState(params=new_params, x_hat=new_hat, s=new_s,
                             opt=new_opt, step=state.step + 1, key=state.key,
                             psw=state.psw)
            mets = {"loss": jnp.mean(losses), "lr": lr,
                    "grad_norm": _global_norm(grads),
                    # per-node loss dispersion: the first-order symptom of
                    # non-IID shards (diag/node_loss_spread in the run log)
                    "node_loss_spread": (jnp.max(losses) - jnp.min(losses))}
            for k, v in metrics.items():
                mets[k] = jnp.mean(v)
            return out, mets

        if pipelined:
            return pipelined_step

        def train_step(state: TrainState, batch) -> Tuple[TrainState, dict]:
            # 1. per-node stochastic gradient (no cross-node collectives).
            # Push-sum (SGP, Assran et al. 2019): x <- x - lr * gradF(z) —
            # the gradient is EVALUATED at the de-biased estimate z = x / w
            # (x itself is Perron-biased mid-consensus) but is the gradient
            # w.r.t. z, NOT d/dx F(x/w): differentiating through the
            # division would scale node i's step by a spurious 1/w_i.
            def loss_fn(p, b):
                loss, metrics = model.loss(p, b)
                return loss, metrics
            if pushsum:
                from repro.comm.pushsum import debias
                z = debias(state.params, state.psw)
            else:
                z = state.params
            with scope("obs:grad"):
                (losses, metrics), grads = jax.vmap(
                    jax.value_and_grad(loss_fn, has_aux=True))(z, batch)

            # 2. local optimizer half-step  x^{t+1/2}
            lr = lr_fn(state.step)
            with scope("obs:optimizer"):
                x_half, new_opt = opt.update(state.params, grads,
                                             state.opt, lr)

            # 3. gossip exchange (CHOCO / plain / all-reduce / push-sum)
            gkey = jax.random.fold_in(state.key, state.step)
            exchange = self._exchange(state.params)   # specs from leaf ndims
            with scope("obs:exchange"), stages.scoped(phase_scopes):
                if pushsum:
                    new_params, new_hat, new_s, new_w = exchange(
                        gkey, x_half, state.x_hat, state.s, state.psw)
                else:
                    new_params, new_hat, new_s = exchange(
                        gkey, x_half, state.x_hat, state.s)
                    new_w = state.psw

            out = TrainState(params=new_params, x_hat=new_hat, s=new_s,
                             opt=new_opt, step=state.step + 1, key=state.key,
                             psw=new_w)
            mets = {"loss": jnp.mean(losses), "lr": lr,
                    "grad_norm": _global_norm(grads),
                    # per-node loss dispersion: the first-order symptom of
                    # non-IID shards (diag/node_loss_spread in the run log)
                    "node_loss_spread": (jnp.max(losses) - jnp.min(losses))}
            for k, v in metrics.items():
                mets[k] = jnp.mean(v)
            return out, mets

        return train_step

    def _exchange(self, params_shape):
        specs = param_pspecs(params_shape, self.model.cfg,
                             node_axis=self.gossip_axis, fsdp_axis=self.fsdp_axis,
                             model_size=0)
        gamma = (self.gamma_spec if self.gamma_spec is not None
                 else self.gamma)
        return make_gossip_exchange(
            mode=self.mode, mesh=self.mesh, state_specs=specs,
            axis=self.gossip_axis, compressor=self.compressor, gamma=gamma,
            exact_small_leaves=self.choco.exact_small_leaves,
            small_leaf_threshold=self.choco.small_leaf_threshold,
            packed=self.choco.packed_gossip,
            pack_align=self.choco.pack_align,
            schedules=self.schedules,
            gossip_steps=self.choco.gossip_steps,
            process=self.process,
            pipelined=self.choco.pipeline_gossip,
            weight_specs=(P(self.gossip_axis, None)
                          if self.mode == "pushsum" else None),
            kernel_backend=self.kernel_backend)

    # -- jit with shardings -----------------------------------------------------

    def jitted_train_step(self, state_shape, batch_shape,
                          phase_scopes: bool = False):
        state_specs = self.state_pspecs(state_shape)
        bspecs = batch_pspecs(batch_shape, node_axis=self.gossip_axis,
                              dp_axis=self.fsdp_axis)
        shard = lambda tree: jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), tree,
            is_leaf=lambda x: isinstance(x, P))
        step = self.make_train_step(phase_scopes=phase_scopes)
        return jax.jit(step,
                       in_shardings=(shard(state_specs), shard(bspecs)),
                       out_shardings=(shard(state_specs), None),
                       donate_argnums=(0,))

    def jitted_diagnostics(self, state_shape):
        """Jitted Lyapunov/consensus diagnostics (``obs/metrics.py``) — a
        SEPARATE executable from the train step; the lazy import keeps
        ``obs`` entirely out of the telemetry-off import path."""
        from repro.obs import metrics as obs_metrics
        return obs_metrics.jitted_diagnostics(self, state_shape)


def _global_shape_error(shape, sp, axes, dim, extent):
    return ValueError(
        f"leaf of global shape {tuple(shape)} cannot be sharded by "
        f"PartitionSpec {sp}: dim {dim} of size {shape[dim]} is not "
        f"divisible by the mesh extent {extent} of axes {axes} — a floored "
        f"local size would mis-derive the bucket spec and its Theorem-2 "
        f"omega.  Pad the dimension to a multiple of {extent} or change "
        f"the partitioning.")


def _local_shape(shape, sp, mesh_axis_sizes) -> Tuple[int, ...]:
    """Per-shard leaf shape under a PartitionSpec — what the exchange's
    bucket spec actually sees inside shard_map.  Raises on non-divisible
    partitioning: XLA would pad such shards, so silently flooring here
    hands the bucket-spec builder (and the omega / gamma derivation built
    on it) a local size the engine never actually sees."""
    dims = list(shape)
    if isinstance(sp, P):
        for i, entry in enumerate(sp):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            f = 1
            for a in axes:
                f *= mesh_axis_sizes[a]
            if f > 1 and dims[i] % f != 0:
                raise _global_shape_error(shape, sp, axes, i, f)
            dims[i] //= f
    return tuple(dims)


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))
