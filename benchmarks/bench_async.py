"""Bounded-staleness async gossip (EXPERIMENTS.md §Perf G).

Sections:
  * staleness_rate — consensus error after T gossip rounds for
    tau in {0, 1, 2, 4} on ring and torus (delay-expanded matrix simulator,
    core/choco_gossip.py).  The derived column carries the delay-averaged
    freshness phi = E[1/(1+d)], the effective Theorem-2 eigengap, and the
    per-step permute-round cost — identical to the static schedule's, the
    whole point of the bounded-staleness design.
  * hlo_audit — compiled-HLO collective-permute launch count of the async
    engine vs the link-failure baseline on an 8-device simulated mesh
    (subprocess, like bench_collectives.compiled): async must add ZERO
    launches (the arrived-vs-stale selection is where-mask arithmetic over
    ring slots, never control flow or extra collectives).
"""
import json
import textwrap

import jax

from repro.core import TopK, make_topology
from repro.core.choco_gossip import run_choco_stale_gossip
from repro.comm.schedule import compile_schedule
from repro.comm.async_gossip import StalenessProcess
from .common import time_fn, emit, run_cpu_child

N, D, STEPS = 8, 256, 300


def staleness_rate():
    """Consensus error vs staleness bound tau on ring/torus."""
    comp = TopK(k=64)
    gamma = 0.25
    x0 = jax.random.normal(jax.random.PRNGKey(0), (N, D))
    for name in ("ring", "torus"):
        sched = compile_schedule(make_topology(name, N))
        for tau in (0, 1, 2, 4):
            proc = StalenessProcess(sched, max_staleness=tau)
            fn = lambda p=proc: run_choco_stale_gossip(
                x0, p, gamma, comp, STEPS)
            us = time_fn(fn, iters=1, warmup=1)
            _, errs = fn()
            emit(f"async/staleness_{name}_tau{tau}", us,
                 f"err={float(errs[-1]):.3e};"
                 f"err_mid={float(errs[STEPS // 2]):.3e};"
                 f"freshness={proc.freshness:.3f};"
                 f"expected_delta={proc.expected_delta_beta()[0]:.4f};"
                 f"permute_rounds_per_step={sched.n_rounds}")


def hlo_audit():
    """Permute-launch parity audit: async engine vs linkfail baseline,
    checked in-subprocess against the choco_staleness registry entry."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.comm.async_gossip import StalenessProcess
        from repro.comm.stochastic import LinkFailureProcess
        from repro.core import make_topology, TopK
        from repro.analysis.hlo_audit import count_permute_launches
        from repro.analysis.invariants import (CONTEXT_VARS,
                                               assert_invariant)

        def permutes(ex, *args):
            hlo = jax.jit(ex).lower(*args).compile().as_text()
            return count_permute_launches(hlo)

        n, d = 8, 4096
        sched = compile_schedule(make_topology("ring", n))
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(fraction=0.05)
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        R = sched.n_rounds
        k = jax.random.PRNGKey(0)
        z = lambda: jnp.zeros_like(x0)

        lf = LinkFailureProcess(sched, drop_prob=0.1)
        ex = make_gossip_exchange(mode="choco", mesh=mesh,
                                  state_specs=P("data", None), axis="data",
                                  compressor=comp, gamma=0.3, process=lf)
        n_lf = permutes(ex, k, x0, z(), [z() for _ in range(R)])
        out = {"linkfail": n_lf}
        for tau in (1, 2, 4):
            sp = StalenessProcess(sched, max_staleness=tau)
            ex = make_gossip_exchange(mode="choco", mesh=mesh,
                                      state_specs=P("data", None),
                                      axis="data", compressor=comp,
                                      gamma=0.3, process=sp)
            n_tau = permutes(
                ex, k, x0, [z() for _ in range(1 + tau)],
                [z() for _ in range(R * (1 + tau))])
            # registered contract: staleness adds ZERO permute launches
            # over the link-failure baseline
            assert_invariant("choco_staleness", "jnp",
                             {"permute_launches": n_tau},
                             dict(CONTEXT_VARS, baseline=n_lf))
            out[f"async_tau{tau}"] = n_tau
        print(json.dumps(out))
    """)
    stdout = run_cpu_child("async/hlo_audit", script)
    out = json.loads(stdout.strip().splitlines()[-1])
    base = out["linkfail"]
    for name, cnt in out.items():
        if name == "linkfail":
            continue
        emit(f"async/hlo_{name}", 0.0,
             f"permute_launches={cnt};linkfail_baseline={base};"
             f"extra_launches={cnt - base}")


def run():
    """Benchmark entry point (python -m benchmarks.run)."""
    staleness_rate()
    hlo_audit()


if __name__ == "__main__":
    run()
