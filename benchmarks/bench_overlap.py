"""Pipelined-gossip overlap audit (EXPERIMENTS.md §Perf H).

Proves — on the compiled HLO of the real qwen3-1.7b smoke train step, 8
simulated devices — that the pipelined engine (comm/pipelined.py) removes
the data dependency that serializes compressed communication behind the
backward pass.

The dependency analysis itself lives in
``repro.analysis.hlo_audit.collective_dependency_audit`` (shared with
``tests/test_pipelined.py`` and the invariant lint); the expected numbers
live in the engine-invariant registry
(``repro.analysis.invariants.ENGINE_INVARIANTS``):

  * serial engine:    every forward/backward dot feeds the collective —
    the wire transfer cannot begin until the backward pass has finished.
  * pipelined engine: ZERO dots feed the collective — it is launchable at
    step start, concurrent with the entire forward/backward, and adds no
    permute launches over serial.

Sections:
  * overlap_audit — dots_feeding_collective for serial vs pipelined on the
    qwen3-1.7b smoke config, checked against the registry, plus
    walltime/step.  Emits machine-readable BENCH_overlap.json at the repo
    root so the perf trajectory is tracked from PR 6 onward.
"""
import json
import os
import textwrap

from .common import emit, run_cpu_child

OUT_JSON = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_overlap.json")

# runs inside a subprocess so the 8-device simulation never leaks
# XLA_FLAGS into the caller
_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, time
    import jax, jax.numpy as jnp

    from repro.configs.base import get_config, ChocoConfig
    from repro.models import build_model
    from repro.train.trainer import DecentralizedTrainer
    from repro.optim import make_optimizer, cosine_schedule
    from repro.data.synthetic import make_lm_batch_fn
    from repro.launch.mesh import make_mesh
    from repro.analysis.hlo_audit import collective_dependency_audit

    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build_model(cfg)
    mesh = make_mesh((8, 1), ("data", "model"))
    nb = make_lm_batch_fn(cfg, 64, 2, 8, 1.0)

    out = {}
    for pipe in (False, True):
        tr = DecentralizedTrainer(
            model=model,
            choco=ChocoConfig(compressor="top_k",
                              comp_kwargs=(("fraction", 0.05),),
                              gossip_axis="data", pipeline_gossip=pipe),
            mesh=mesh, n_nodes=8, optimizer=make_optimizer("momentum"),
            lr_fn=cosine_schedule(0.1, warmup=10, total=100), mode="choco")
        state = tr.init_state(jax.random.PRNGKey(0))
        batch = jax.tree.map(jnp.asarray, nb())
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: batch))
        hlo = step.lower(state, batch).compile().as_text()
        rec = collective_dependency_audit(hlo).as_dict()
        state, _ = step(state, batch)          # compile + donate once
        t0 = time.time()
        iters = 5
        for _ in range(iters):
            state, mets = step(state, jax.tree.map(jnp.asarray, nb()))
        jax.block_until_ready(state.params)
        rec["us_per_step"] = (time.time() - t0) / iters * 1e6
        out["pipelined" if pipe else "serial"] = rec
    print("BENCH_OVERLAP_JSON=" + json.dumps(out))
""")


def overlap_audit():
    """Run the subprocess audit, check the registry invariants, emit CSV
    rows + BENCH_overlap.json."""
    from repro.analysis.invariants import CONTEXT_VARS, assert_invariant

    stdout = run_cpu_child("overlap/audit", _SCRIPT)
    line = [l for l in stdout.splitlines()
            if l.startswith("BENCH_OVERLAP_JSON=")][-1]
    out = json.loads(line.split("=", 1)[1])
    for name, rec in out.items():
        emit(f"overlap/{name}", rec["us_per_step"],
             f"permute_launches={rec['permute_launches']};"
             f"dots_total={rec['dots_total']};"
             f"dots_feeding_collective={rec['dots_feeding_collective']}")
    # the registry is the single statement of what these numbers must be
    ctx = dict(CONTEXT_VARS, dots_total=out["serial"]["dots_total"],
               baseline=out["serial"]["permute_launches"])
    assert_invariant("choco_serial", "jnp",
                     {"dots_feeding_collective":
                      out["serial"]["dots_feeding_collective"]}, ctx)
    ctx["dots_total"] = out["pipelined"]["dots_total"]
    assert_invariant("choco_pipelined", "jnp",
                     {"dots_feeding_collective":
                      out["pipelined"]["dots_feeding_collective"],
                      "permute_launches":
                      out["pipelined"]["permute_launches"]}, ctx)
    out["config"] = {"arch": "qwen3-1.7b-smoke", "devices": 8,
                     "compressor": "top_k", "fraction": 0.05,
                     "topology": "ring"}
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
        f.write("\n")
    return out


def run():
    """Benchmark entry point (python -m benchmarks.run)."""
    overlap_audit()


if __name__ == "__main__":
    run()
