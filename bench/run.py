"""One run of one benchmark cell on the chips of this host.

    python bench/run.py --workload qwen3-1.7b.topk.1chip --seed 7 \\
        --seconds 10 --trace 0

Builds the cell's trainer as the training launcher does, gives it the
seed's weights, compiles its one train step (the persistent compilation
cache lives in ``.jax_cache`` at the root of the checkout, one directory
for traced runs and one for the others), drives three
steps that the reference follows, then measures ``--seconds`` of steps.
With ``--trace 1`` the step carries the trainer's phase scopes and the
window is traced; the per-layer metrics come from that trace.  After the
window the program's state is freed and the reference runs the same three
steps; the compared numbers and their limits end standard error and the
result line, which is the last line of standard output.

Exits nonzero, printing no result, without a TPU, with fewer chips than
the cell asks for, or without the program (``src/``) beside ``bench/``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: persistent compilation caches, one per kind of run: the cache's key
#: leaves out op metadata, so a traced step (whose phase scopes live only
#: in that metadata) found under an untraced step's key would lose them
CACHE = os.path.join(ROOT, ".jax_cache", "untraced")
TRACED_CACHE = os.path.join(ROOT, ".jax_cache", "traced")
TRACE_DIR = os.path.join(ROOT, "bench_out", "trace")


def _fail(msg):
    print(f"[bench] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _say(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class _CacheHits(logging.Handler):
    """Names of the modules the persistent compilation cache served."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.hits = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Persistent compilation cache hit for"):
            self.hits.append(msg.split("'")[1])
        elif record.levelno >= logging.WARNING:
            print(f"[jax] {msg}", file=sys.stderr, flush=True)


def seed_key(seed: int):
    import jax
    seed %= 1 << 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def compile_step(cell, trace: bool):
    """The cell's program and its compiled train step (the launcher's
    jitted step, lowered for the cell's one batch shape)."""
    import jax
    import jax.numpy as jnp
    from bench import program
    tr = cell.traffic
    prog = program.build(cell.config, tr, phase_scopes=trace)
    batch = {k: jax.ShapeDtypeStruct(
        (tr["nodes"], tr["batch_per_node"], tr["seq_len"]), jnp.int32)
        for k in ("tokens", "labels")}
    hits = _CacheHits()
    log = logging.getLogger("jax._src.compiler")
    level, propagate = log.level, log.propagate
    log.addHandler(hits)
    log.setLevel(logging.DEBUG)
    log.propagate = False
    try:
        t = time.perf_counter()
        compiled = prog.step.lower(prog.trainer.state_shape(),
                                   batch).compile()
        compile_s = time.perf_counter() - t
    finally:
        log.removeHandler(hits)
        log.setLevel(level)
        log.propagate = propagate
    cached = any("train_step" in h for h in hits.hits)
    _say(f"train step {'loaded from the compilation cache' if cached else 'compiled'}"
         f" in {compile_s:.3f} s")
    return prog, compiled


def _by_node(leaves):
    """Per-leaf arrays (n, ...) -> one array (n, leaves, ...)."""
    import numpy as np
    return np.stack([np.asarray(v) for v in leaves], axis=1)


def _peak_in_use():
    import jax
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def first_steps(cell, prog, compiled, seed: int):
    """State from the seed's weights, driven through three steps of the
    window's own call and feed.  Returns (state, feed, put, the three
    batches, key, the program's readings for the comparison)."""
    import jax
    import numpy as np
    from bench import program, traffic
    model, tr = cell.model, cell.traffic
    key = seed_key(seed)
    feed = traffic.batches(tr, model["vocab_size"], seed % (1 << 64))
    state = program.seeded_state(prog, model, key)
    batch_sharding = compiled.input_shardings[0][1]
    put = lambda b: jax.device_put(b, batch_sharding)
    first = [next(feed) for _ in range(3)]
    losses = []
    for i, b in enumerate(first):
        state, mets = compiled(state, put(b))
        losses.append(float(mets["loss"]))
        if i == 0:
            grad, grad_proj = program.momentum_norms(state)
            grad, grad_proj = _by_node(grad), _by_node(grad_proj)
    _say(f"peak_bytes_in_use after the three steps: {_peak_in_use()}")
    dx, dx_proj, hat, s = program.end_norms(prog, model)(state, key)
    _say(f"peak_bytes_in_use after the end norms: {_peak_in_use()}")
    read = {"loss": losses, "grad": grad, "grad_proj": grad_proj,
            "step": _by_node(dx), "step_proj": _by_node(dx_proj),
            "hat": _by_node(hat), "s": _by_node(s)}
    _say(f"set-up losses {losses}")
    return state, feed, put, first, key, read


def run(cell, seed: int, seconds: float, trace: bool, t_start: float):
    """Set-up, window and comparison of one run; returns the result dict.
    ``t_start`` is when the process began."""
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation
    from bench import compare, counts
    from bench.reference import Reference

    seed %= 1 << 64
    model, tr = cell.model, cell.traffic
    prog, compiled = compile_step(cell, trace)
    state, feed, put, first, key, prog_read = first_steps(cell, prog,
                                                          compiled, seed)

    tokens_per_step = tr["nodes"] * tr["batch_per_node"] * tr["seq_len"]
    trace_dir = os.path.join(TRACE_DIR, f"{cell.name}.{seed}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    setup_s = time.perf_counter() - t_start
    t0 = time.perf_counter()
    steps = failed = 0
    pending = None
    while True:
        with TraceAnnotation("bench:input"):
            b = put(next(feed))
        with TraceAnnotation("bench:dispatch"):
            state, mets = compiled(state, b)
        steps += 1
        if pending is not None:
            with TraceAnnotation("bench:wait"):
                failed += not np.isfinite(float(pending))
        pending = mets["loss"]
        if time.perf_counter() - t0 >= seconds:
            break
    with TraceAnnotation("bench:wait"):
        jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    failed += not np.isfinite(float(pending))
    if trace:
        jax.profiler.stop_trace()
    _say(f"window: {steps} steps in {window_s:.6f} s, last loss "
         f"{float(pending)}")

    devs = jax.devices()[:cell.chips]
    stats = [d.memory_stats() or {} for d in devs]
    for d, st in zip(devs, stats):
        _say(f"memory_stats {d.id}: {st}")
    ma = compiled.memory_analysis()
    _say(f"memory_analysis: arguments {ma.argument_size_in_bytes} outputs "
         f"{ma.output_size_in_bytes} aliased {ma.alias_size_in_bytes} "
         f"temporaries {ma.temp_size_in_bytes}")
    # the TPU runtime keeps an executable's temporaries in a reserved
    # region apart from its allocations.  The footprint of the window is
    # what is allocated while it runs (the state, live between steps: a
    # step's outputs take its donated inputs' place) plus that region;
    # peak_bytes_in_use would also count the set-up's passing copies.
    peak = max(st.get("bytes_in_use", 0) + st.get("peak_bytes_reserved", 0)
               for st in stats)
    hlo = compiled.as_text()
    wire = counts.wire_bytes(hlo)
    _say(f"collective-permute operand bytes per step: {wire}")

    del state, mets, pending, b, compiled
    gc.collect()

    ref = Reference(model, tr, cell.config["lr"], devs).run(key, first)
    values = compare.gaps(prog_read, ref)
    correct = compare.judge(values, cell.limits)
    result = {
        "correct": bool(correct), "attempted": steps, "failed": int(failed),
        "metrics": {},
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    rate = steps * tokens_per_step / window_s
    if not trace:
        e2e = {"setup_s": (setup_s, "s"), "tokens_per_s": (rate, "tokens/s"),
               "peak_hbm_gb": (peak / 1e9, "GB")}
        for m in cell.end_to_end:
            value, unit = e2e[m["name"]]
            result["metrics"][m["name"]] = {"value": value, "unit": unit}
    else:
        from bench import trace as btrace
        with gzip.open(os.path.join(trace_dir, "hlo.txt.gz"), "wt") as f:
            f.write(hlo)
        red = btrace.reduce(trace_dir, hlo)
        ctx = btrace.Context(cell=cell, reduced=red, tokens_per_s=rate,
                             device_kind=devs[0].device_kind, hlo=hlo)
        for m in cell.per_layer:
            value = btrace.read_metric(m["name"], ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = red.busy_s()
        result["device"]["window_s"] = window_s
        result["breakdown"] = btrace.breakdown(red)
    for k, v in values.items():
        if k not in cell.limits:
            _say(f"reading {k} {v!r} (not compared)")
    result["checks"] = {k: {"value": values[k], "limit": v}
                        for k, v in cell.limits.items()}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import repro  # noqa: F401
    except ImportError as e:
        _fail(f"the program (src/repro) is not beside bench/: {e}")
    from bench import cell as cells
    cell = cells.load(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < cell.chips:
        _fail(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
    _say(f"devices {len(devices)} x {devices[0].device_kind}")
    jax.config.update("jax_compilation_cache_dir",
                      TRACED_CACHE if args.trace else CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run(cell, args.seed, args.seconds, bool(args.trace), T0)
    for k, c in result["checks"].items():
        print(f"[bench] check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
