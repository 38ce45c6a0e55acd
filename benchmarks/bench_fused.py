"""Fused-kernel HBM audit for the gossip hot path (EXPERIMENTS.md §Perf I).

Counts the full-size memory streams one compressed gossip round moves per
device, comparing the serial jnp engine against the fused Pallas path
(``--kernel-backend pallas``) on the real qwen3-1.7b smoke exchange, 8
simulated devices:

  * serial side — MEASURED from the compiled HLO: every f32 tensor at or
    above the stream threshold that an entry-computation instruction
    defines (a write) or consumes as an operand (a read) is one HBM
    stream.  Post-fusion, so elementwise chains XLA already fused into
    one pass are not double-counted; shapes are the per-device local
    shapes after SPMD partitioning.
  * fused side — the interpret-mode Pallas HLO lowers to grid loops on
    CPU and is unrepresentative of the TPU lowering, so the fused path
    is audited STRUCTURALLY: the jaxpr is walked for ``pallas_call``
    launches (asserted == n_buckets x 2 per round: one fused
    quantize+pack, one fused dequant+EF-update — the registered
    choco_serial/pallas invariant in repro.analysis.invariants) and the
    kernel + glue streams are itemized analytically per bucket
    (delta/xi/norm/dense glue in jnp, 2 reads + 1 code write in the
    quantize kernel, 5 reads + 3 writes in the EF kernel).

The HLO/jaxpr parsers live in ``repro.analysis.hlo_audit`` /
``repro.analysis.jaxpr_audit`` (shared with tests and the lint CLI).

Both engines run in the same subprocess and the parity contract is
asserted on real arrays: identical round-1 x_hat (the wire-payload
witness) and ulp-bounded x/s drift.  Emits BENCH_fused.json at the repo
root (schema in the JSON itself) plus CSV rows.
"""
import json
import os
import textwrap

from repro.analysis.hlo_audit import STREAM_THRESHOLD

from .common import HBM_BW, emit, run_cpu_child

OUT_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_fused.json")


def fused_bucket_streams(bucket_bytes: int, code_bytes: int) -> dict:
    """Analytic per-bucket per-round streams of the fused path, itemized.

    jnp glue: delta (read h, hat / write d), xi (write), norm (read d),
    self/neighbour dense q (read codes / write q) x2.  Kernels: quantize
    reads d + xi and writes codes; EF reads (h, hat, s, q_self, q_nbr)
    and writes (x, hat', s').  Collective wire bytes are excluded (the
    wire audit is §Perf D/E)."""
    B, C = bucket_bytes, code_bytes
    glue = {"delta": 3 * B, "xi": B, "norm": B,
            "dense_q": 2 * (C + B)}
    kernels = {"quantize_kernel": 2 * B + C, "ef_kernel": 8 * B}
    return {"glue_bytes": glue, "kernel_bytes": kernels,
            "bytes": sum(glue.values()) + sum(kernels.values()),
            # one full-size stream per B-sized read/write above
            "full_streams": 3 + 1 + 1 + 2 + 2 + 8}


_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp

    from repro.configs.base import get_config, ChocoConfig
    from repro.models import build_model
    from repro.train.trainer import DecentralizedTrainer
    from repro.optim import make_optimizer, cosine_schedule
    from repro.launch.mesh import make_mesh
    from benchmarks.bench_fused import fused_bucket_streams
    from repro.analysis.hlo_audit import entry_stream_audit
    from repro.analysis.invariants import CONTEXT_VARS, assert_invariant
    from repro.analysis.jaxpr_audit import count_pallas_calls

    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build_model(cfg)
    mesh = make_mesh((8, 1), ("data", "model"))

    out = {}
    exchanges = {}
    states = {}
    for bk in ("jnp", "pallas"):
        tr = DecentralizedTrainer(
            model=model,
            choco=ChocoConfig(compressor="qsgd", comp_kwargs=(("s", 16),),
                              gossip_axis="data", kernel_backend=bk),
            mesh=mesh, n_nodes=8, optimizer=make_optimizer("momentum"),
            lr_fn=cosine_schedule(0.1, warmup=10, total=100), mode="choco")
        state = tr.init_state(jax.random.PRNGKey(0))
        pshape = jax.eval_shape(lambda: state.params)
        ex = tr._exchange(pshape)
        key = jax.random.PRNGKey(7)
        args = (key, state.params, jax.tree.map(jnp.zeros_like, state.params),
                jax.tree.map(jnp.zeros_like, state.params))
        rec = {}
        if bk == "jnp":
            hlo = jax.jit(ex).lower(*args).compile().as_text()
            rec.update(entry_stream_audit(hlo))
        else:
            jaxpr = jax.make_jaxpr(ex)(*args)
            rec["pallas_calls"] = count_pallas_calls(jaxpr.jaxpr)
            # reproduce the engine's local bucket spec (shard_map view:
            # gossip axis dim contracted to 1) for the analytic streams
            from repro.comm.gossip import _leaf_routes
            from repro.comm.packing import make_bucket_spec
            from repro.launch.sharding import param_pspecs
            specs = param_pspecs(pshape, cfg, node_axis="data",
                                 fsdp_axis=None, model_size=0)
            leaves = jax.tree_util.tree_leaves(pshape)
            local = [jax.ShapeDtypeStruct((1,) + l.shape[1:], l.dtype)
                     for l in leaves]
            spec = make_bucket_spec(local,
                                    routes=_leaf_routes(specs, ("data",)))
            rec["n_buckets"] = spec.n_buckets
            per_bucket = [fused_bucket_streams(b.size * 4, b.size)
                          for b in spec.buckets]
            rec["bytes"] = sum(p["bytes"] for p in per_bucket)
            rec["streams"] = sum(p["full_streams"] for p in per_bucket)
            rec["per_bucket"] = per_bucket
            assert_invariant("choco_serial", "pallas",
                             {"pallas_calls": rec["pallas_calls"]},
                             dict(CONTEXT_VARS, buckets=spec.n_buckets,
                                  steps=1))
        exchanges[bk] = jax.jit(ex)
        states[bk] = args
        out[bk] = rec

    # parity contract on real arrays: round-1 x_hat is the wire witness
    res = {bk: exchanges[bk](*states[bk]) for bk in exchanges}
    hat_exact = all(
        bool(jnp.all(a == b)) for a, b in
        zip(jax.tree.leaves(res["jnp"][1]), jax.tree.leaves(res["pallas"][1])))
    drift = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(res["jnp"]),
                    jax.tree.leaves(res["pallas"])))
    out["parity"] = {"round1_xhat_bit_exact": hat_exact,
                     "max_abs_drift": drift}
    assert hat_exact, "wire payloads diverged across kernel backends"
    assert drift < 1e-5, drift
    print("BENCH_FUSED_JSON=" + json.dumps(out))
""")


def fused_audit():
    """Run the subprocess audit and emit CSV rows + BENCH_fused.json."""
    stdout = run_cpu_child("fused/audit", _SCRIPT)
    line = [l for l in stdout.splitlines()
            if l.startswith("BENCH_FUSED_JSON=")][-1]
    out = json.loads(line.split("=", 1)[1])
    for name in ("jnp", "pallas"):
        rec = out[name]
        emit(f"fused/{name}", rec["bytes"] / HBM_BW * 1e6,
             f"streams={rec['streams']};bytes={rec['bytes']};"
             f"hbm_bw={HBM_BW:.0f}")
    out["config"] = {"arch": "qwen3-1.7b-smoke", "devices": 8,
                     "compressor": "qsgd", "s": 16, "topology": "ring",
                     "stream_threshold": STREAM_THRESHOLD,
                     "hbm_bw": HBM_BW,
                     "us_per_round_roofline": {
                         name: out[name]["bytes"] / HBM_BW * 1e6
                         for name in ("jnp", "pallas")}}
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    return out


def run():
    """Benchmark entry point (python -m benchmarks.run)."""
    fused_audit()


if __name__ == "__main__":
    run()
