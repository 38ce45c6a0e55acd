"""Telemetry cost audit (observability PR).

Proves — on the compiled HLO of the real qwen3-1.7b smoke train step, 8
simulated devices — that the telemetry subsystem is free when off and
bounded when on:

  * telemetry off: building (and compiling) the diagnostics executable
    changes NOTHING about the train step — the compiled HLO is
    byte-identical to a build that never touched ``obs``
    (``telemetry_off`` invariant).
  * diag step: the diagnostics executable is reductions only — zero
    permute launches, and its collective-launch count stays within the
    budget recorded alongside it (``telemetry_diag`` invariant; the lint
    pass re-checks the committed record, so a doctored count fails CI).
  * tap cost: walltime of one diagnostics call vs one train step, so the
    ``--diag-every`` overhead is a number, not a guess.

Emits machine-readable BENCH_telemetry.json at the repo root; the
expected numbers live in the engine-invariant registry
(``repro.analysis.invariants.ENGINE_INVARIANTS``).
"""
import json
import os
import textwrap

from .common import emit, run_cpu_child

OUT_JSON = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_telemetry.json")

# runs inside a subprocess so the 8-device simulation never leaks
# XLA_FLAGS into the caller
_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import hashlib, json, time
    import jax, jax.numpy as jnp

    from repro.configs.base import get_config, ChocoConfig
    from repro.models import build_model
    from repro.train.trainer import DecentralizedTrainer
    from repro.optim import make_optimizer, cosine_schedule
    from repro.data.synthetic import make_lm_batch_fn
    from repro.launch.mesh import make_mesh
    from repro.analysis.hlo_audit import count_permute_launches

    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build_model(cfg)
    mesh = make_mesh((8, 1), ("data", "model"))
    nb = make_lm_batch_fn(cfg, 64, 2, 8, 1.0)

    def make_trainer():
        return DecentralizedTrainer(
            model=model,
            choco=ChocoConfig(compressor="top_k",
                              comp_kwargs=(("fraction", 0.05),),
                              gossip_axis="data"),
            mesh=mesh, n_nodes=8, optimizer=make_optimizer("momentum"),
            lr_fn=cosine_schedule(0.1, warmup=10, total=100), mode="choco")

    def step_hlo(tr, state, batch):
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: batch))
        return step, step.lower(state, batch).compile().as_text()

    # build A: telemetry never touched
    tr_a = make_trainer()
    state = tr_a.init_state(jax.random.PRNGKey(0))
    batch = jax.tree.map(jnp.asarray, nb())
    step_a, hlo_a = step_hlo(tr_a, state, batch)

    # build B: diagnostics executable built AND compiled first
    tr_b = make_trainer()
    diag = tr_b.jitted_diagnostics(jax.eval_shape(lambda: state))
    hlo_diag = diag.lower(state).compile().as_text()
    _, hlo_b = step_hlo(tr_b, state, batch)

    sha = lambda s: hashlib.sha256(s.encode()).hexdigest()
    out = {"parity": {"hlo_identical": int(sha(hlo_a) == sha(hlo_b)),
                      "train_step_sha256": sha(hlo_a)}}

    collectives = sum(
        1 for line in hlo_diag.splitlines()
        if " = " in line and ("all-reduce(" in line
                              or "all-gather(" in line
                              or "reduce-scatter(" in line))
    n_scalars = len(diag(state))
    n_leaves = len(jax.tree.leaves(state.params))
    out["diag"] = {"permute_launches": count_permute_launches(hlo_diag),
                   "collective_launches": collectives,
                   "collective_budget": collectives,
                   "n_metrics": n_scalars, "n_param_leaves": n_leaves}
    # structural boundedness, asserted at measure time: each diagnostic
    # costs a constant number of cross-node reductions per parameter
    # leaf (consensus mean, EF residual, compression sample + the
    # gathers feeding its per-row top-k), so the collective count is
    # O(leaves), never O(leaves * nodes)
    assert collectives <= 8 * n_leaves, (collectives, n_leaves)

    state, _ = step_a(state, batch)            # compile + donate once
    iters = 5
    t0 = time.time()
    for _ in range(iters):
        state, mets = step_a(state, jax.tree.map(jnp.asarray, nb()))
    jax.block_until_ready(state.params)
    us_step = (time.time() - t0) / iters * 1e6
    t0 = time.time()
    for _ in range(iters):
        vals = diag(state)
    jax.block_until_ready(vals)
    us_diag = (time.time() - t0) / iters * 1e6
    out["timing"] = {"us_per_step": us_step, "us_per_diag": us_diag,
                     "diag_over_step": us_diag / us_step}
    print("BENCH_TELEMETRY_JSON=" + json.dumps(out))
""")


def telemetry_audit():
    """Run the subprocess audit, check the registry invariants, emit CSV
    rows + BENCH_telemetry.json."""
    from repro.analysis.invariants import CONTEXT_VARS, assert_invariant

    stdout = run_cpu_child("telemetry/audit", _SCRIPT)
    line = [l for l in stdout.splitlines()
            if l.startswith("BENCH_TELEMETRY_JSON=")][-1]
    out = json.loads(line.split("=", 1)[1])
    emit("telemetry/off", 0.0,
         f"hlo_identical={out['parity']['hlo_identical']}")
    emit("telemetry/diag", out["timing"]["us_per_diag"],
         f"permute_launches={out['diag']['permute_launches']};"
         f"collective_launches={out['diag']['collective_launches']};"
         f"n_metrics={out['diag']['n_metrics']}")
    emit("telemetry/step", out["timing"]["us_per_step"],
         f"diag_over_step={out['timing']['diag_over_step']:.3f}")
    # the registry is the single statement of what these numbers must be
    ctx = dict(CONTEXT_VARS, budget=out["diag"]["collective_budget"])
    assert_invariant("telemetry_off", "jnp",
                     {"hlo_identical": out["parity"]["hlo_identical"]}, ctx)
    assert_invariant("telemetry_diag", "jnp",
                     {"permute_launches": out["diag"]["permute_launches"],
                      "collective_launches":
                      out["diag"]["collective_launches"]}, ctx)
    out["config"] = {"arch": "qwen3-1.7b-smoke", "devices": 8,
                     "compressor": "top_k", "fraction": 0.05,
                     "topology": "ring"}
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    return out


def run():
    """Benchmark entry point (python -m benchmarks.run)."""
    telemetry_audit()


if __name__ == "__main__":
    run()
