"""Non-IID scenario suite (EXPERIMENTS.md §Scenarios).

Sections:
  * skew_sweep — the declarative scenario harness (tests/scenarios.py) on
    the Dirichlet alpha sweep: final consensus loss of CHOCO-SGD at
    alpha in {0.1, 1, 100} vs the IID control and the no-gossip negative
    control, plus the gossip_steps=3 variant.  The derived column carries
    the contract observables (final loss, node-loss spread, consensus
    distance) so the EXPERIMENTS.md table regenerates from this output.
  * hlo_audit — compiled-HLO permute-launch parity of the per-edge
    straggler staleness engine vs the global-staleness baseline on an
    8-device simulated mesh: a heterogeneous delay table changes WHICH
    ring slot each edge reads, never how much is shipped, so the launch
    count must be identical (choco_staleness_stragglers registry row).
    Emits machine-readable BENCH_scenarios.json at the repo root; the
    committed copy is re-validated by ``python -m repro.analysis.lint``.
"""
import json
import os
import sys
import textwrap

from .common import emit, time_fn, run_cpu_child

TESTS = os.path.join(os.path.dirname(__file__), "..", "tests")
OUT_JSON = os.path.join(os.path.dirname(__file__), "..",
                        "BENCH_scenarios.json")

#: the sweep cells the benchmark reports (a subset of the full matrix the
#: slow test tier runs — rings only, both compressors, all alphas)
SWEEP = ("a0.1-ring-topk", "a1-ring-topk", "a100-ring-topk",
         "iid-ring-topk", "a0.1-ring-qsgd", "a0.1-ring-topk-k3")


def skew_sweep():
    """Run the scenario harness over the alpha sweep; returns the records
    for BENCH_scenarios.json."""
    sys.path.insert(0, TESTS)
    try:
        from scenarios import get_scenario, no_gossip_control, run_scenario
    finally:
        sys.path.pop(0)
    records = {}
    for name in SWEEP:
        sc = get_scenario(name)
        us = time_fn(lambda: run_scenario(sc), iters=1, warmup=0)
        r = run_scenario(sc)
        records[name] = r
        emit(f"scenarios/{name}", us,
             f"final_loss={r['final_loss']:.4f};"
             f"node_loss_spread={r['node_loss_spread']:.2e};"
             f"consensus={r['consensus_dist']:.2e}")
    ng = run_scenario(no_gossip_control(get_scenario("a0.1-ring-topk")))
    records["a0.1-ring-topk-nogossip"] = ng
    emit("scenarios/a0.1-ring-topk-nogossip", 0.0,
         f"final_loss={ng['final_loss']:.4f};"
         f"node_loss_spread={ng['node_loss_spread']:.2e};"
         f"consensus={ng['consensus_dist']:.2e}")
    return records


_AUDIT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.comm.gossip import make_gossip_exchange
    from repro.comm.schedule import compile_schedule
    from repro.comm.async_gossip import StalenessProcess
    from repro.core import make_topology, TopK
    from repro.analysis.hlo_audit import count_permute_launches
    from repro.analysis.invariants import CONTEXT_VARS, assert_invariant

    def permutes(proc):
        ex = make_gossip_exchange(mode="choco", mesh=mesh,
                                  state_specs=P("data", None), axis="data",
                                  compressor=comp, gamma=0.3, process=proc)
        z = lambda: jnp.zeros_like(x0)
        args = (jax.random.PRNGKey(0), x0,
                [z() for _ in range(1 + tau)],
                [z() for _ in range(R * (1 + tau))])
        return count_permute_launches(
            jax.jit(ex).lower(*args).compile().as_text())

    n, d, tau = 8, 4096, 2
    sched = compile_schedule(make_topology("ring", n))
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8, 1), ("data", "model"))
    comp = TopK(fraction=0.05)
    x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    R = sched.n_rounds

    n_global = permutes(StalenessProcess(sched, max_staleness=tau))
    n_strag = permutes(StalenessProcess(
        sched, max_staleness=tau, straggler_edges=((0, 1),),
        straggler_delay_probs=(0.1, 0.2, 0.7)))
    # registered contract: per-edge delay tables add ZERO permute launches
    assert_invariant("choco_staleness_stragglers", "jnp",
                     {"permute_launches": n_strag},
                     dict(CONTEXT_VARS, baseline=n_global))
    print("BENCH_SCENARIOS_JSON=" + json.dumps(
        {"global_staleness": n_global, "straggler_staleness": n_strag}))
""")


def hlo_audit():
    """Run the subprocess parity audit; returns the straggler record."""
    stdout = run_cpu_child("scenarios/hlo_audit", _AUDIT)
    line = [l for l in stdout.splitlines()
            if l.startswith("BENCH_SCENARIOS_JSON=")][-1]
    rec = json.loads(line.split("=", 1)[1])
    emit("scenarios/hlo_straggler", 0.0,
         f"permute_launches={rec['straggler_staleness']};"
         f"global_baseline={rec['global_staleness']};"
         f"extra_launches="
         f"{rec['straggler_staleness'] - rec['global_staleness']}")
    return rec


def run():
    """Benchmark entry point (python -m benchmarks.run)."""
    skew = skew_sweep()
    straggler = hlo_audit()
    out = {"straggler": straggler,
           "skew": {k: {"final_loss": round(v["final_loss"], 4),
                        "consensus_dist": round(v["consensus_dist"], 4)}
                    for k, v in skew.items()},
           "config": {"devices": 8, "topology": "ring", "tau": 2,
                      "straggler_edges": [[0, 1]],
                      "straggler_delay_probs": [0.1, 0.2, 0.7]}}
    with open(OUT_JSON, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")


if __name__ == "__main__":
    run()
