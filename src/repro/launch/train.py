"""Production training launcher.

One process drives every chip of a single host (1 or 4 TPU chips); there
is no ``jax.distributed`` initialisation, and a single host needs none.
Without ``--mesh`` every visible device is one gossip node.  On CPU it
simulates the mesh with host devices for integration testing.  A
published config that does not fit the chip is cut in depth only
(``--n-layers``); widths are never touched.  At published widths the
default ``--lr 0.1`` diverges; 0.01 trains:

    python -m repro.launch.train --arch qwen3-1.7b --n-layers 4 \
        --mode choco --compressor top_k --fraction 0.01 --lr 0.01 --steps 100

Checkpoints are sharded directories (manifest.json + per-host shard files;
see checkpoint/checkpointing.py).  ``--steps`` is the TOTAL step budget:
resuming a step-60 checkpoint with ``--steps 100`` trains 40 more steps and
the cosine schedule continues from step 60 (anchored by the manifest step),
it does not restart.  A checkpoint saved with a different ``n_nodes`` is
restored elastically (params tiled/averaged across the node dim, CHOCO
x_hat/s re-zeroed + consensus warmup — checkpoint/elastic.py):

    python -m repro.launch.train --arch qwen3-1.7b --smoke --steps 100 \
        --simulate-devices 8 --mesh 8x1 \
        --resume ckpts/step60 --checkpoint-dir ckpts --checkpoint-every 20
"""
import argparse
import dataclasses
import os
import sys

# jax-free imports: safe before XLA_FLAGS is frozen by the first jax import
from repro.configs.base import (get_config, parse_delay_probs,
                                parse_straggler_edges, parse_topology)
from repro.launch.env import place_compile_cache, simulate_host_devices
from repro.obs.sinks import (DivergenceMonitor, JsonlSink, MetricLog,
                             StdoutSink)
from repro.obs.timers import StepTimer
from repro.obs.trace import ProfileSession, annotate

# mirrors core.topology._TOPOLOGIES; kept literal so arg validation never
# imports jax before XLA_FLAGS is set
TOPOLOGY_CHOICES = ("ring", "torus", "hypercube", "star", "chain",
                    "fully_connected", "directed_ring", "random_digraph")
# mirrors core.topology.DIRECTED_TOPOLOGIES (column-stochastic: push-sum only)
DIRECTED_CHOICES = ("directed_ring", "random_digraph")
PROCESS_CHOICES = ("none", "matching", "linkfail", "staleness")


def _stdout_line(record):
    """Stdout rendering of structured records: log lines verbatim, train
    metric records in the historical ``[train] step ...`` format, diag
    records as one compact line; header records are file-only."""
    kind = record.get("kind")
    if kind == "log":
        return record.get("msg", "")
    if kind != "metrics":
        return None
    if "train/loss" in record:
        tail = (f"{record['train/s_per_step']:.2f}s/step"
                if "train/s_per_step" in record
                else f"compile {record['train/compile_s']:.2f}s")
        return (f"[train] step {record['step']:5d} "
                f"loss {record['train/loss']:.4f} "
                f"lr {record['train/lr']:.4f} ({tail})")
    if "diag/consensus_dist" in record:
        parts = " ".join(f"{k.split('/', 1)[1]} {v:.3e}"
                         for k, v in sorted(record.items())
                         if k.startswith("diag/"))
        return f"[diag] step {record['step']} {parts}"
    return None


def main(argv=None):
    """CLI driver: validate args jax-free, then build the mesh/trainer and
    run the decentralized training loop."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="cut the published config to its first K layers "
                         "(depth only, every width unchanged) so it fits "
                         "the chips at hand; not with --smoke")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--batch-per-node", type=int, default=None)
    ap.add_argument("--mode", default="choco",
                    choices=["choco", "plain", "allreduce", "pushsum"])
    ap.add_argument("--topology", default="ring",
                    help="gossip graph (one of "
                         f"{'/'.join(TOPOLOGY_CHOICES)}), or a "
                         "comma-separated sequence for time-varying mixing, "
                         "cycled across the --gossip-steps rounds of each "
                         "SGD step; directed graphs "
                         f"({'/'.join(DIRECTED_CHOICES)}) require "
                         "--mode pushsum")
    ap.add_argument("--topology-process", default="none",
                    choices=list(PROCESS_CHOICES),
                    help="stochastic topology process: 'matching' samples "
                         "one schedule round per gossip round (one permute "
                         "launch/step), 'linkfail' drops each edge i.i.d. "
                         "with --edge-drop-prob per round, 'staleness' runs "
                         "the bounded-staleness async engine (per-edge "
                         "delays up to --max-staleness rounds; nodes "
                         "proceed on the freshest copy they hold)")
    ap.add_argument("--edge-drop-prob", type=float, default=None,
                    help="Bernoulli link-failure probability in [0, 1) "
                         "(requires --topology-process linkfail)")
    ap.add_argument("--matching-sampler", default=None,
                    choices=["uniform", "weighted"],
                    help="round sampler for --topology-process matching "
                         "(default uniform)")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="staleness bound tau >= 0 for --topology-process "
                         "staleness: per-edge payload delays are sampled "
                         "uniformly from {0..tau} (default 1; tau=0 is the "
                         "always-fresh replica engine)")
    ap.add_argument("--straggler-edges", default=None,
                    help="comma-separated slow links ('0-1,2-3') whose "
                         "delays come from --straggler-delay-probs instead "
                         "of the global distribution (requires "
                         "--topology-process staleness; edge ids are node "
                         "pairs in the gossip graph's edge support)")
    ap.add_argument("--straggler-delay-probs", default=None,
                    help="comma-separated P(d=0..tau) for the straggler "
                         "edges ('0.1,0.2,0.7'; needs --max-staleness + 1 "
                         "entries); default: point mass at tau — a "
                         "maximally slow link")
    ap.add_argument("--pipeline-gossip", action="store_true",
                    help="pipelined CHOCO engine (comm/pipelined.py): "
                         "compress the pre-gradient iterate and integrate "
                         "the received payload at the NEXT step's update so "
                         "the collective overlaps the backward pass (tau=1 "
                         "staleness gamma); requires --mode choco, a single "
                         "static --topology, and no --topology-process")
    ap.add_argument("--gossip-steps", type=int, default=1,
                    help="CHOCO gossip rounds per SGD step (k>1 trades wire "
                         "bytes for consensus; one pack amortizes the k "
                         "compressions)")
    ap.add_argument("--compressor", default="top_k")
    ap.add_argument("--fraction", type=float, default=0.01,
                    help="coordinate fraction for top_k/rand_k/block_top_k")
    ap.add_argument("--qsgd-s", type=int, default=None,
                    help="quantization levels (required with --compressor qsgd)")
    ap.add_argument("--state-dtype", default="float32")
    ap.add_argument("--gossip-engine", default="packed",
                    choices=["packed", "per-leaf"],
                    help="bucketed flat-buffer exchange (default) vs legacy "
                         "per-leaf compress+ppermute")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "pallas", "jnp"],
                    help="kernel backend for the gossip hot path "
                         "(kernels/dispatch.py): 'auto' uses the fused "
                         "Pallas kernels on a TPU and jnp elsewhere, "
                         "'pallas'/'jnp' force; "
                         "pallas requires --mode choco with the packed "
                         "engine and no --topology-process")
    ap.add_argument("--exact-small-leaves", action="store_true",
                    help="route leaves <= 8192 elems to the uncompressed "
                         "exact bucket (norm scales, biases)")
    ap.add_argument("--optimizer", default="momentum")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--heterogeneity", type=float, default=1.0)
    ap.add_argument("--data-skew-alpha", type=float, default=None,
                    help="Dirichlet(alpha) non-IID vocab shards "
                         "(data/partition.py): alpha -> inf is IID "
                         "('shuffled'), alpha -> 0 disjoint shards "
                         "('sorted'); overrides --heterogeneity")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--keep-checkpoints", type=int, default=None,
                    help="retain only the newest K checkpoint dirs under "
                         "--checkpoint-dir (GC runs after each successful "
                         "manifest write, never deletes the step just saved)")
    ap.add_argument("--resume", default=None,
                    help="sharded checkpoint dir (manifest.json) or a legacy "
                         "flat .npz; --steps stays the TOTAL budget")
    ap.add_argument("--elastic-warmup-rounds", type=int, default=None,
                    help="CHOCO-GOSSIP warmup rounds after an elastic "
                         "restore (default: derived from the new topology's "
                         "spectral gap)")
    ap.add_argument("--simulate-devices", type=int, default=0,
                    help=">0: simulate N host devices (CPU testing)")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 4x2 => (data=4, model=2); default: every "
                         "visible device is one node, (data=N, model=1)")
    ap.add_argument("--metrics-dir", default=None,
                    help="write a structured JSONL run log (metrics.jsonl: "
                         "header record + registry-validated metric records, "
                         "obs/schema.py) alongside the stdout lines")
    ap.add_argument("--diag-every", type=int, default=0,
                    help="run the jitted Lyapunov/consensus diagnostics "
                         "(obs/metrics.py) every k steps; 0 (default) "
                         "disables them — the fast-path train step is a "
                         "separate executable and stays byte-identical")
    ap.add_argument("--divergence-action", default=None,
                    choices=["warn", "abort"],
                    help="watch the diagnosed Lyapunov Xi_t: 'warn' logs "
                         "when it stops contracting, 'abort' exits nonzero "
                         "(overscaled --consensus-gamma detector); requires "
                         "--diag-every >= 1")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a TensorBoard-loadable jax.profiler trace "
                         "of steady-state steps into this directory (also "
                         "enables in-graph obs: phase scopes)")
    ap.add_argument("--profile-steps", type=int, default=None,
                    help="steps to trace under --profile-dir (default 3; "
                         "the compiling step 0 is always skipped)")
    args = ap.parse_args(argv)

    # fail fast on bad combinations, before any jax/device work
    topo_names = parse_topology(args.topology)
    bad = [t for t in topo_names if t not in TOPOLOGY_CHOICES]
    if bad or not topo_names:
        ap.error(f"--topology {args.topology!r}: unknown graph(s) {bad}; "
                 f"choose from {', '.join(TOPOLOGY_CHOICES)}")
    if args.gossip_steps < 1:
        ap.error("--gossip-steps must be >= 1")
    if len(topo_names) > 1 and args.gossip_steps % len(topo_names) != 0:
        ap.error(f"--topology {args.topology!r} is a {len(topo_names)}-graph "
                 f"time-varying sequence: --gossip-steps must be a multiple "
                 f"of {len(topo_names)} so every graph runs each SGD step "
                 f"(got {args.gossip_steps})")
    if args.compressor == "qsgd" and args.qsgd_s is None:
        ap.error("--compressor qsgd requires --qsgd-s (quantization levels); "
                 "it takes no --fraction")
    # directed topologies are column-stochastic: the symmetric choco/plain
    # engines would converge to a Perron-biased point, never the average
    directed = [t for t in topo_names if t in DIRECTED_CHOICES]
    if directed and args.mode != "pushsum":
        ap.error(f"--topology {args.topology!r} is directed "
                 f"(column-stochastic); --mode {args.mode} assumes a "
                 f"symmetric W. Directed graphs need the push-sum engine: "
                 f"--mode pushsum (de-biased x/w, comm/pushsum.py)")
    if args.mode == "pushsum":
        if len(topo_names) > 1:
            ap.error("--mode pushsum runs one directed schedule; "
                     f"time-varying sequences are unsupported "
                     f"(got --topology {args.topology!r})")
        if args.topology_process != "none":
            ap.error("--mode pushsum owns its directed schedule; combining "
                     "it with --topology-process is unsupported")
        if args.gossip_engine != "packed":
            ap.error("--mode pushsum is packed-only (the weight scalar "
                     "rides in-band with the bucket payloads); drop "
                     "--gossip-engine per-leaf")
    if args.topology_process != "none":
        if len(topo_names) > 1:
            ap.error(f"--topology-process {args.topology_process} is itself "
                     f"the per-step mixing distribution; a time-varying "
                     f"--topology sequence ({args.topology!r}) is ambiguous")
        if args.mode == "allreduce":
            ap.error("--topology-process has no effect under --mode "
                     "allreduce (no gossip graph); drop one of the two")
    if args.edge_drop_prob is not None:
        if args.topology_process != "linkfail":
            ap.error("--edge-drop-prob only applies to --topology-process "
                     "linkfail")
        if not 0.0 <= args.edge_drop_prob < 1.0:
            ap.error(f"--edge-drop-prob must be in [0, 1), got "
                     f"{args.edge_drop_prob} (p = 1 never mixes)")
    if args.matching_sampler is not None \
            and args.topology_process != "matching":
        ap.error("--matching-sampler only applies to --topology-process "
                 "matching")
    # bounded staleness reconstructs stale snapshots from rings of
    # compressed increments: only the compressed choco engine has that
    # increment stream (plain ships fresh iterates, allreduce/pushsum are
    # rejected for any process above)
    if args.topology_process == "staleness" and args.mode != "choco":
        ap.error(f"--topology-process staleness requires --mode choco "
                 f"(got --mode {args.mode}): the async engine ring-buffers "
                 f"compressed increments, which only the choco engine ships")
    if args.max_staleness is not None:
        if args.topology_process != "staleness":
            ap.error("--max-staleness only applies to --topology-process "
                     "staleness")
        if args.max_staleness < 0:
            ap.error(f"--max-staleness must be >= 0, got "
                     f"{args.max_staleness}")
    if args.straggler_delay_probs is not None and args.straggler_edges is None:
        ap.error("--straggler-delay-probs names the straggler links' delay "
                 "distribution; it requires --straggler-edges")
    if args.straggler_edges is not None:
        if args.topology_process != "staleness":
            ap.error("--straggler-edges models per-edge DELAYS; it requires "
                     "--topology-process staleness")
        try:
            parse_straggler_edges(args.straggler_edges)
        except ValueError as e:
            ap.error(f"--straggler-edges: {e}")
        if args.straggler_delay_probs is not None:
            try:
                probs = parse_delay_probs(args.straggler_delay_probs)
            except ValueError as e:
                ap.error(f"--straggler-delay-probs: {e}")
            tau = args.max_staleness if args.max_staleness is not None else 1
            if len(probs) != tau + 1:
                ap.error(f"--straggler-delay-probs needs max_staleness + 1 "
                         f"= {tau + 1} entries (P(d=0..{tau})), got "
                         f"{len(probs)}")
    if args.data_skew_alpha is not None and not args.data_skew_alpha > 0:
        ap.error(f"--data-skew-alpha must be > 0 (Dirichlet concentration), "
                 f"got {args.data_skew_alpha}")
    if args.pipeline_gossip:
        if args.mode != "choco":
            ap.error(f"--pipeline-gossip hides the COMPRESSED exchange "
                     f"behind the backward pass via the error-feedback "
                     f"carry; --mode {args.mode} has no (x_hat, s) state to "
                     f"double-buffer — it requires --mode choco")
        if args.topology_process != "none":
            ap.error(f"--pipeline-gossip is itself a deterministic delay-1 "
                     f"staleness process; stacking --topology-process "
                     f"{args.topology_process} on top compounds two delay "
                     f"models with no Theorem-2 gamma for the composite")
        if len(topo_names) > 1:
            ap.error(f"--pipeline-gossip needs one static schedule: a "
                     f"payload compressed under graph W_k but integrated a "
                     f"step later under W_k+1 breaks the recursion (got "
                     f"--topology {args.topology!r})")
    if args.kernel_backend == "pallas":
        # mirror kernels/dispatch.py's engine-eligibility rule pre-jax so a
        # bad launch dies in argparse, not after devices initialise
        if args.mode != "choco":
            ap.error(f"--kernel-backend pallas fuses the CHOCO "
                     f"quantize/error-feedback hot path; --mode {args.mode} "
                     f"never runs it — drop the flag or use --mode choco")
        if args.gossip_engine != "packed":
            ap.error("--kernel-backend pallas requires the packed engine "
                     "(the kernels run on bucket buffers); drop "
                     "--gossip-engine per-leaf")
        if args.topology_process != "none":
            ap.error(f"--kernel-backend pallas runs on the static choco "
                     f"engines only; --topology-process "
                     f"{args.topology_process} uses the replica/async "
                     f"engines, which stay jnp")
    if args.keep_checkpoints is not None:
        if args.keep_checkpoints < 1:
            ap.error(f"--keep-checkpoints must be >= 1, got "
                     f"{args.keep_checkpoints}")
        if not args.checkpoint_dir:
            ap.error("--keep-checkpoints requires --checkpoint-dir")
    if args.diag_every < 0:
        ap.error(f"--diag-every must be >= 0 (0 disables diagnostics), got "
                 f"{args.diag_every}")
    if args.divergence_action is not None and args.diag_every == 0:
        ap.error("--divergence-action watches the Lyapunov diagnostics; it "
                 "requires --diag-every >= 1")
    if args.profile_steps is not None:
        if not args.profile_dir:
            ap.error("--profile-steps only applies with --profile-dir")
        if args.profile_steps < 1:
            ap.error(f"--profile-steps must be >= 1, got "
                     f"{args.profile_steps}")
    # configs/ is jax-free: the depth cut is validated before device init
    cfg = get_config(args.arch, smoke=args.smoke)
    reduced = None
    if args.n_layers is not None:
        if args.smoke:
            ap.error("--n-layers cuts a published config; --smoke already "
                     "selects the reduced CPU config — drop one of the two")
        if not 1 <= args.n_layers <= cfg.n_layers:
            ap.error(f"--n-layers must be in [1, {cfg.n_layers}] (the "
                     f"published depth of {cfg.name}), got {args.n_layers}")
        reduced = f"n_layers {cfg.n_layers}->{args.n_layers}"
        cfg = dataclasses.replace(cfg, n_layers=args.n_layers)

    if args.simulate_devices:
        simulate_host_devices(args.simulate_devices)

    sinks = [StdoutSink(formatter=_stdout_line)]
    if args.metrics_dir:
        sinks.append(JsonlSink(os.path.join(args.metrics_dir,
                                            "metrics.jsonl")))
    mlog = MetricLog(sinks)

    import jax
    import jax.numpy as jnp
    from repro.configs.base import ChocoConfig
    from repro.models import build_model
    from repro.models.transformer import count_params
    from repro.train.trainer import DecentralizedTrainer
    from repro.optim import make_optimizer, cosine_schedule
    from repro.data.synthetic import make_lm_batch_fn
    from repro.launch.mesh import make_default_mesh, make_mesh, gossip_axis_for
    from repro.checkpoint.checkpointing import restore_pytree
    from repro.checkpoint.manifest import (is_sharded_checkpoint,
                                           read_manifest)

    place_compile_cache()
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        mesh = make_mesh(dims, axes)
    else:
        mesh = make_default_mesh()
    gossip_axis = gossip_axis_for(mesh)
    n_nodes = mesh.shape[gossip_axis]

    model = build_model(cfg)

    if args.compressor == "qsgd":
        comp_kwargs = (("s", args.qsgd_s),)
    elif args.compressor in ("sign", "identity"):
        comp_kwargs = ()
    else:
        comp_kwargs = (("fraction", args.fraction),)
    trainer = DecentralizedTrainer(
        model=model,
        choco=ChocoConfig(compressor=args.compressor, comp_kwargs=comp_kwargs,
                          gossip_axis=gossip_axis, state_dtype=args.state_dtype,
                          topology=args.topology,
                          gossip_steps=args.gossip_steps,
                          packed_gossip=(args.gossip_engine == "packed"),
                          exact_small_leaves=args.exact_small_leaves,
                          topology_process=(None if args.topology_process == "none"
                                            else args.topology_process),
                          edge_drop_prob=(args.edge_drop_prob
                                          if args.edge_drop_prob is not None
                                          else 0.1),
                          matching_sampler=(args.matching_sampler or "uniform"),
                          max_staleness=(args.max_staleness
                                         if args.max_staleness is not None
                                         else 1),
                          pipeline_gossip=args.pipeline_gossip,
                          kernel_backend=args.kernel_backend,
                          data_skew_alpha=args.data_skew_alpha,
                          straggler_edges=args.straggler_edges,
                          straggler_delay_probs=args.straggler_delay_probs),
        mesh=mesh, n_nodes=n_nodes,
        optimizer=make_optimizer(args.optimizer),
        lr_fn=cosine_schedule(args.lr, warmup=min(100, args.steps // 10 + 1),
                              total=args.steps),
        mode=args.mode)
    proc_info = ("" if args.topology_process == "none" else
                 f" process={args.topology_process}")
    proc_info += " pipelined" if args.pipeline_gossip else ""
    proc_info += f" reduced: {reduced}" if reduced else ""
    mlog.log(f"[train] arch={cfg.name} params={count_params(cfg)/1e6:.1f}M "
             f"mesh={dict(zip(mesh.axis_names, mesh.devices.shape))} "
             f"nodes={n_nodes} mode={args.mode} topology={args.topology} "
             f"gossip_steps={args.gossip_steps} "
             f"kernel_backend={trainer.kernel_backend}{proc_info}")

    def budget_check(resumed):
        if resumed >= args.steps:
            raise SystemExit(
                f"[train] --steps {args.steps} is the TOTAL step budget, but "
                f"{args.resume} is already at step {resumed}: nothing to do "
                f"(raise --steps; the LR schedule stays anchored at step 0 "
                f"over the full budget)")

    resumed = 0
    if args.resume:
        # a directory is always the sharded format: a torn save (no
        # manifest) surfaces as ManifestError, never as a bogus .npz lookup
        if os.path.isdir(args.resume) or is_sharded_checkpoint(args.resume):
            # budget check BEFORE restore/warmup — an exhausted resume must
            # not pay compilation + gossip rounds just to exit
            budget_check(read_manifest(args.resume).step)
            # restore directly under the trainer's shardings: no host-gather,
            # no throwaway init_state allocation
            state, man, warmup = trainer.restore_checkpoint(args.resume)
            resumed = man.step
            rounds = (args.elastic_warmup_rounds
                      if args.elastic_warmup_rounds is not None else warmup)
            if warmup and rounds:
                mlog.log(f"[train] elastic restore: checkpoint "
                         f"n_nodes={man.n_nodes} "
                         f"topology={man.fingerprint.get('topology')} -> "
                         f"n_nodes={n_nodes} topology={args.topology}; x_hat/s "
                         f"re-zeroed, consensus warmup {rounds} CHOCO-GOSSIP "
                         f"rounds (re-derived Theorem-2 "
                         f"gamma={trainer.gamma:.3e})")
                state = trainer.consensus_warmup(state, rounds)
        else:   # legacy flat npz
            state = jax.device_put(
                restore_pytree(args.resume, trainer.state_shape()),
                trainer.state_shardings())
            resumed = int(jax.device_get(state.step))
            budget_check(resumed)
        mlog.log(f"[train] resumed from {args.resume} at step {resumed}")
    else:
        state = trainer.init_state(jax.random.PRNGKey(0))

    seq = args.seq_len or min(cfg.n_layers * 64, 512)
    bpn = args.batch_per_node or 4
    next_batch = make_lm_batch_fn(cfg, seq, bpn, n_nodes, args.heterogeneity,
                                  skew_alpha=args.data_skew_alpha)
    batch0 = jax.tree.map(jnp.asarray, next_batch())
    state_shape = jax.eval_shape(lambda: state)
    # phase scopes change HLO op metadata, so they ride the profiler flag:
    # the default build keeps the compiled step byte-identical (the
    # telemetry_off invariant, benchmarks/bench_telemetry.py)
    step_fn = trainer.jitted_train_step(state_shape,
                                        jax.eval_shape(lambda: batch0),
                                        phase_scopes=bool(args.profile_dir))

    from repro.obs.metrics import bucket_telemetry
    buckets = bucket_telemetry(trainer)
    mlog.header(arch=cfg.name, mode=args.mode, topology=args.topology,
                fingerprint=trainer.fingerprint(),
                jax_version=jax.__version__,
                kernel_backend=trainer.kernel_backend, reduced=reduced,
                node_devices=trainer.node_devices(state),
                mesh=dict(zip(mesh.axis_names, mesh.devices.shape)),
                gamma=buckets["gamma"], buckets=buckets["buckets"],
                wire_bytes_round=buckets["wire_bytes_round"])
    diag_fn = (trainer.jitted_diagnostics(state_shape)
               if args.diag_every else None)
    monitor = (DivergenceMonitor() if args.divergence_action else None)
    prof = ProfileSession(args.profile_dir,
                          n_steps=(args.profile_steps or 3))

    timer = StepTimer()
    timer.start()
    remaining = args.steps - resumed       # --steps is the TOTAL budget
    try:
        # host phases are annotate() spans: under --profile-dir the trace
        # puts each idle gap of the device down to the host work in it
        for i in range(remaining):
            prof.maybe_start(i)
            with annotate("train:input"):
                batch = jax.tree.map(jnp.asarray, next_batch())
            with annotate("train:dispatch"):
                state, mets = step_fn(state, batch)
            if i == 0 or i % 10 == 0 or i == remaining - 1:
                with annotate("train:tap"):
                    # honest async-dispatch timing: block only on tap
                    # steps; the first (compiling) step is reported once
                    # as train/compile_s and never averaged into s/step
                    metrics = {"train/loss": float(mets["loss"]),
                               "train/lr": float(mets["lr"]),
                               "train/grad_norm": float(mets["grad_norm"]),
                               "diag/node_loss_spread":
                                   float(mets["node_loss_spread"])}
                    blocker = lambda: jax.block_until_ready(state)
                    if i == 0:
                        metrics["train/compile_s"] = timer.mark_compile(
                            blocker)
                    else:
                        sps = timer.tap(i, blocker)
                        if sps is not None:
                            metrics["train/s_per_step"] = sps
                    extra = {k: float(v) for k, v in mets.items()
                             if k not in ("loss", "lr", "grad_norm",
                                          "node_loss_spread")}
                    mlog.emit(int(state.step), metrics, extra=extra or None)
            if diag_fn is not None and (i + 1) % args.diag_every == 0:
                with annotate("train:diag"):
                    diag = {k: float(v) for k, v in diag_fn(state).items()}
                    diag["diag/gamma"] = buckets["gamma"]
                    diag["diag/wire_bytes_round"] = float(
                        buckets["wire_bytes_round"])
                    diag["diag/data_skew_tv"] = float(next_batch.skew_tv)
                    mlog.emit(int(state.step), diag)
                xi = diag.get("diag/lyapunov",
                              diag["diag/consensus_dist"])
                msg = monitor.update(int(state.step), xi) if monitor else None
                if msg is not None:
                    if args.divergence_action == "abort":
                        raise SystemExit(f"[train] {msg}")
                    mlog.log(f"[train] WARNING: {msg}")
            if (args.checkpoint_dir and args.checkpoint_every
                    and (i + 1) % args.checkpoint_every == 0):
                path = os.path.join(args.checkpoint_dir,
                                    f"step{int(state.step)}")
                with annotate("train:checkpoint"):
                    trainer.save_checkpoint(path, state,
                                            metadata={"arch": cfg.name},
                                            keep_last=args.keep_checkpoints)
                mlog.log(f"[train] checkpointed {path}")
            if prof.active and i + 1 >= prof.stop_after:
                jax.block_until_ready(state)
            prof.maybe_stop(i)
    finally:
        prof.close()
        mlog.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
