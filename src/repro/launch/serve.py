"""Serving launcher: batched prefill + decode loop against the default
mesh (or a simulated CPU mesh).

    python -m repro.launch.serve --arch gemma2-9b --smoke \
        --simulate-devices 8 --mesh 4x2 --batch 8 --gen-len 16

Latency is reported per request, not as one run-wide aggregate: TTFT
(prompt ingest + first generated token, blocked on the token) p50/p99
across ``--requests``, and per-token decode time p50/p99 across every
generated step.  ``--metrics-dir`` writes the same numbers as
registry-validated records (obs/schema.py).
"""
import argparse
import os
import sys
import time

# jax-free imports: safe before XLA_FLAGS is frozen by the first jax import
from repro.launch.env import simulate_host_devices
from repro.obs.sinks import JsonlSink, MetricLog, StdoutSink
from repro.obs.timers import percentile
from repro.obs.trace import annotate


def _stdout_line(record):
    """Log lines verbatim; the serve summary as one compact line."""
    kind = record.get("kind")
    if kind == "log":
        return record.get("msg", "")
    if kind != "metrics":
        return None
    parts = " ".join(f"{k.split('/', 1)[1]} {v:.4f}"
                     for k, v in sorted(record.items())
                     if k.startswith("serve/"))
    return f"[serve] {parts}" if parts else None


def main(argv=None):
    """CLI driver: batched prefill then a greedy decode loop per request,
    reporting TTFT and per-token latency percentiles."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--kv-layout", default="head", choices=["head", "seq"])
    ap.add_argument("--requests", type=int, default=1,
                    help="decode requests to run (fresh cache each); "
                         "latency percentiles aggregate across them")
    ap.add_argument("--metrics-dir", default=None,
                    help="write latency records to metrics.jsonl "
                         "(obs/schema.py registry)")
    ap.add_argument("--simulate-devices", type=int, default=0)
    ap.add_argument("--mesh", default=None)
    args = ap.parse_args(argv)
    if args.requests < 1:
        ap.error(f"--requests must be >= 1, got {args.requests}")

    if args.simulate_devices:
        simulate_host_devices(args.simulate_devices)

    sinks = [StdoutSink(formatter=_stdout_line)]
    if args.metrics_dir:
        sinks.append(JsonlSink(os.path.join(args.metrics_dir,
                                            "metrics.jsonl")))
    mlog = MetricLog(sinks)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.launch.mesh import make_default_mesh, make_mesh
    from repro.launch.sharding import param_pspecs, cache_pspecs

    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        mesh = make_mesh(dims, axes)
    else:
        mesh = make_default_mesh()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family == "audio":
        raise SystemExit("encoder-only arch has no decode step (DESIGN.md §4)")
    model = build_model(cfg)
    B, Pl, G = args.batch, args.prompt_len, args.gen_len
    max_seq = Pl + G

    pspecs = param_pspecs(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                          cfg, node_axis=None)
    shard = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                      is_leaf=lambda x: isinstance(x, P))
    params = jax.jit(model.init, out_shardings=shard(pspecs))(jax.random.PRNGKey(0))

    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    cache0 = model.init_cache(B, max_seq)
    cspecs = cache_pspecs(jax.eval_shape(lambda: cache0), cfg, batch=B,
                          dp_axes=("data",), mesh_shape=mesh_shape,
                          kv_layout=args.kv_layout)

    decode = jax.jit(model.decode_step,
                     in_shardings=(shard(pspecs), None, shard(cspecs), None),
                     out_shardings=(None, shard(cspecs)),
                     donate_argnums=(2,))

    mlog.header(arch=cfg.name, kv_layout=args.kv_layout, batch=B,
                prompt_len=Pl, gen_len=G, requests=args.requests,
                jax_version=jax.__version__, mesh=mesh_shape)

    key = jax.random.PRNGKey(1)
    ttfts, tok_times = [], []
    out = []
    t_all = time.perf_counter()
    try:
        for r in range(args.requests):
            cache = jax.device_put(model.init_cache(B, max_seq),
                                   shard(cspecs))
            prompt = jax.random.randint(jax.random.fold_in(key, r),
                                        (B, Pl), 0, cfg.vocab_size)
            tok = prompt[:, :1]
            out = []
            req_t0 = time.perf_counter()
            last = req_t0
            with annotate("serve:request"):
                for t in range(max_seq - 1):
                    pos = jnp.full((B,), t, jnp.int32)
                    logits, cache = decode(params, tok, cache, pos)
                    nxt = jnp.argmax(logits[:, -1],
                                     axis=-1).astype(jnp.int32)[:, None]
                    tok = prompt[:, t + 1:t + 2] if t + 1 < Pl else nxt
                    if t + 1 >= Pl:
                        # block per generated token: per-token latency is
                        # the serving metric, async dispatch would hide it
                        nxt.block_until_ready()
                        now = time.perf_counter()
                        if t + 1 == Pl:
                            ttfts.append(now - req_t0)   # TTFT
                        else:
                            tok_times.append(now - last)
                        last = now
                        out.append(nxt)
        dt = time.perf_counter() - t_all
        total_tok = B * len(out) * args.requests
        summary = {"serve/ttft_p50_s": percentile(ttfts, 50),
                   "serve/ttft_p99_s": percentile(ttfts, 99),
                   "serve/throughput_tok_s": total_tok / dt}
        if tok_times:   # gen-len 1: TTFT is the only per-token sample
            summary["serve/tok_p50_s"] = percentile(tok_times, 50)
            summary["serve/tok_p99_s"] = percentile(tok_times, 99)
        mlog.emit(0, summary)
        mlog.log(f"[serve] arch={cfg.name} kv_layout={args.kv_layout} "
                 f"decoded {len(out) * args.requests}x{B} tokens in "
                 f"{dt:.2f}s ({total_tok / dt:.1f} tok/s)")
    finally:
        mlog.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
