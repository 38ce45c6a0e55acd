"""Pipelined gossip engine (comm/pipelined.py) + PR-6 satellite fixes.

Fast tier: the pipelined matrix recursion's invariants (mean preservation,
convergence, equivalence to the depth-1 bounded-staleness algebra it is
derived from), per-bucket Theorem-2 gamma resolution (GammaSpec /
bucket_omegas), and the `_local_shape` non-divisible-shard guard.

Slow tier (8-device subprocesses, tests/test_distributed.py pattern): the
shard_map engine == matrix simulator per step (packed and per-leaf), the
per-bucket gamma engine against independent per-bucket simulators, the
compressor-fingerprint restore regression, and the dependency audit proving
the pipelined collective is independent of the batch (the overlap property
benchmarks/bench_overlap.py quantifies on compiled HLO).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SRC = os.path.join(ROOT, "src")


def run_sub(body: str, timeout=560):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC + os.pathsep + ROOT)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# fast tier — matrix simulator + gamma plumbing + shard-shape guard
# ---------------------------------------------------------------------------

def test_gamma_spec_value_is_scaled_theorem2():
    from repro.core.choco_gossip import GammaSpec, theorem2_stepsize
    gs = GammaSpec(delta=0.4, beta=0.7, omega_scale=0.5)
    assert gs.value(0.25) == pytest.approx(theorem2_stepsize(0.4, 0.7, 0.125))
    assert (GammaSpec(delta=0.4, beta=0.7).value(0.25)
            == pytest.approx(theorem2_stepsize(0.4, 0.7, 0.25)))


def test_bucket_omegas_per_bucket_vs_worst():
    """bucket_omegas gives each bucket its own Assumption-1 omega (exact
    buckets = 1); bucket_omega_worst is the min over COMPRESSED buckets."""
    import jax
    import jax.numpy as jnp
    from repro.comm.packing import (bucket_omega_worst, bucket_omegas,
                                    make_bucket_spec)
    from repro.core import TopK
    leaves = [jax.ShapeDtypeStruct((4096,), jnp.float32),
              jax.ShapeDtypeStruct((64,), jnp.float32)]
    spec = make_bucket_spec(leaves, align=128, exact_small_leaves=True,
                            small_leaf_threshold=1024)
    assert len(spec.buckets) == 2
    comp = TopK(fraction=0.05)
    oms = bucket_omegas(spec, comp)
    assert len(oms) == len(spec.buckets)
    exact = [b.exact for b in spec.buckets]
    for om, ex in zip(oms, exact):
        if ex:
            assert om == 1.0
        else:
            assert 0.0 < om < 1.0
    assert bucket_omega_worst(spec, comp) == min(
        om for om, ex in zip(oms, exact) if not ex)


def test_resolve_leaf_gammas_maps_buckets_to_leaves():
    import jax
    import jax.numpy as jnp
    from repro.comm.gossip import _resolve_leaf_gammas
    from repro.comm.packing import bucket_omegas, make_bucket_spec
    from repro.core import TopK
    from repro.core.choco_gossip import GammaSpec
    leaves = [jax.ShapeDtypeStruct((4096,), jnp.float32),
              jax.ShapeDtypeStruct((64,), jnp.float32)]
    spec = make_bucket_spec(leaves, align=128, exact_small_leaves=True,
                            small_leaf_threshold=1024)
    comp = TopK(fraction=0.05)
    gs = GammaSpec(delta=0.4, beta=0.9)
    gammas = _resolve_leaf_gammas(gs, spec, comp)
    oms = bucket_omegas(spec, comp)
    by_bucket = [gs.value(om) for om in oms]
    expect = [by_bucket[slot.bucket]
              for slot in sorted(spec.slots, key=lambda sl: sl.leaf)]
    assert gammas == expect
    # exact leaf contracts at omega=1, strictly faster than the top-k leaf
    assert max(gammas) > min(gammas)
    # a float passes through untouched (legacy single global gamma)
    assert _resolve_leaf_gammas(0.25, spec, comp) == 0.25


def test_local_shape_divides_or_raises():
    from jax.sharding import PartitionSpec as P
    from repro.train.trainer import _local_shape
    assert _local_shape((8, 64), P("data", None), {"data": 4}) == (2, 64)
    assert _local_shape((16, 3), P(("pod", "data"), None),
                        {"pod": 2, "data": 4}) == (2, 3)
    assert _local_shape((5, 7), P(None, None), {"data": 4}) == (5, 7)
    with pytest.raises(ValueError, match="not\\s+divisible"):
        _local_shape((6, 64), P("data", None), {"data": 4})
    with pytest.raises(ValueError, match="not\\s+divisible"):
        # the old code silently floored this to (1,) via max(1, 1 // 4)
        _local_shape((1,), P("data"), {"data": 4})


def test_pipelined_simulator_preserves_mean_and_converges():
    import jax
    import jax.numpy as jnp
    from repro.core import TopK, make_topology
    from repro.core.choco_gossip import run_choco_pipelined_gossip
    topo = make_topology("ring", 8)
    W = jnp.asarray(topo.W)
    comp = TopK(k=24)
    # practical stepsize (the Theorem-2 bound is orders of magnitude too
    # conservative on ring(8) to show contraction within a unit test)
    gamma = 0.3
    x0 = jax.random.normal(jax.random.PRNGKey(0), (8, 96))
    st, errs = run_choco_pipelined_gossip(x0, W, gamma, comp, steps=200)
    np.testing.assert_allclose(np.mean(np.asarray(st.x), axis=0),
                               np.mean(np.asarray(x0), axis=0),
                               rtol=1e-4, atol=1e-5)
    assert float(errs[-1]) < 0.05 * float(errs[0])


def test_pipelined_recursion_equals_depth1_stale():
    """The compact (x, x_hat, s) pipelined carry IS the bounded-staleness
    engine at deterministic delay 1: against the delay-expanded ring
    simulator driven by pipeline_delay_process, iterates must agree (the
    depth-1 rings collapse into the carry)."""
    import jax
    import jax.numpy as jnp
    from repro.comm.pipelined import pipeline_delay_process
    from repro.comm.schedule import compile_schedule
    from repro.core import TopK, make_topology
    from repro.core.choco_gossip import (run_choco_pipelined_gossip,
                                         run_choco_stale_gossip)
    topo = make_topology("ring", 8)
    proc = pipeline_delay_process(compile_schedule(topo))
    assert proc.max_staleness == 1
    assert proc.freshness == pytest.approx(0.5)
    assert proc.effective_omega(0.3) == pytest.approx(0.15)
    comp = TopK(k=9)                       # deterministic: no RNG divergence
    x0 = jax.random.normal(jax.random.PRNGKey(1), (8, 96))
    st_stale, _ = run_choco_stale_gossip(x0, proc, 0.2, comp, steps=7)
    st_pipe, _ = run_choco_pipelined_gossip(x0, jnp.asarray(topo.W), 0.2,
                                            comp, steps=7)
    np.testing.assert_allclose(np.asarray(st_stale.x), np.asarray(st_pipe.x),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_gossip_config_default_off():
    from repro.configs.base import ChocoConfig
    assert ChocoConfig().pipeline_gossip is False


def test_trainer_rejects_gamma_spec_on_per_leaf_engine():
    from repro.comm.gossip import make_choco_schedule_fn
    from repro.comm.schedule import compile_schedule
    from repro.core import TopK, make_topology
    from repro.core.choco_gossip import GammaSpec
    sched = compile_schedule(make_topology("ring", 8))
    with pytest.raises(ValueError, match="packed"):
        make_choco_schedule_fn(axes=("data",), sizes=(8,),
                               schedules=(sched,), compressor=TopK(k=4),
                               gamma=GammaSpec(delta=0.3, beta=0.9),
                               packed=False)


# ---------------------------------------------------------------------------
# slow tier — 8-device engine parity, trainer restore, dependency audit
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("packed", [True, False])
def test_pipelined_engine_matches_matrix_simulator(packed):
    """Per-step parity of the shard_map pipelined engine (stochastic top_k,
    engine key folds replicated on the simulator side) with the
    choco_pipelined_round recursion."""
    run_sub(f"""
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.core import make_topology
        from repro.core.choco_gossip import (PipelinedGossipState,
                                             init_pipelined_state)
        from repro.core.compression import make_compressor

        N, D, STEPS = 8, 96, 5
        topo = make_topology("ring", N)
        sched = compile_schedule(topo)
        W = jnp.asarray(topo.W, jnp.float32)
        comp = make_compressor("top_k", fraction=0.25)
        gamma = 0.3
        mesh = make_mesh((8, 1), ("data", "model"))
        key = jax.random.PRNGKey(0)
        x0 = jax.random.normal(jax.random.fold_in(key, 1), (N, D))

        ex = make_gossip_exchange(
            mode="choco", mesh=mesh, state_specs=P("data", None),
            axis="data", compressor=comp, gamma=gamma, schedules=(sched,),
            packed={packed}, pipelined=True)
        x, hat, s = x0, jnp.zeros_like(x0), jnp.zeros_like(x0)
        st = init_pipelined_state(x0)
        for t in range(STEPS):
            gk = jax.random.fold_in(key, 100 + t)
            x, hat, s = ex(gk, x, hat, s)
            pk = jax.vmap(lambda i: jax.random.fold_in(gk, i))(jnp.arange(N))
            q = jax.vmap(comp)(pk, st.x - st.x_hat)
            st = PipelinedGossipState(
                x=st.x + gamma * (st.s - st.x_hat),
                x_hat=st.x_hat + q, s=st.s + W @ q)
            np.testing.assert_allclose(np.asarray(x), np.asarray(st.x),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(hat), np.asarray(st.x_hat),
                                       rtol=1e-4, atol=1e-5)
        print("MATCH")
    """)


@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("pipelined", [False, True])
def test_per_bucket_gamma_engine_matches_per_bucket_simulator(pipelined):
    """GammaSpec on the packed engine: a two-leaf tree (large top-k bucket +
    exact small bucket) must evolve as two INDEPENDENT matrix recursions,
    each at its own bucket's Theorem-2 gamma — the satellite-2 bugfix (one
    worst-case global gamma would damp the exact leaf)."""
    run_sub(f"""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.packing import bucket_omegas, make_bucket_spec
        from repro.comm.schedule import compile_schedule
        from repro.core import TopK, make_topology
        from repro.core.choco_gossip import (GammaSpec, PipelinedGossipState,
                                             init_pipelined_state,
                                             init_efficient_state,
                                             choco_gossip_round_efficient)
        from repro.core.compression import Identity

        N, DBIG, DSMALL, STEPS = 8, 1024, 64, 4
        topo = make_topology("ring", N)
        sched = compile_schedule(topo)
        W = jnp.asarray(topo.W, jnp.float32)
        comp = TopK(fraction=0.05)          # deterministic
        gs = GammaSpec(delta=topo.delta, beta=topo.beta,
                       omega_scale={0.5 if pipelined else 1.0})
        mesh = make_mesh((8, 1), ("data", "model"))
        k0 = jax.random.PRNGKey(3)
        big = jax.random.normal(jax.random.fold_in(k0, 0), (N, DBIG))
        small = jax.random.normal(jax.random.fold_in(k0, 1), (N, DSMALL))

        leaves = [jax.ShapeDtypeStruct((DBIG,), jnp.float32),
                  jax.ShapeDtypeStruct((DSMALL,), jnp.float32)]
        spec = make_bucket_spec(leaves, align=128, exact_small_leaves=True,
                                small_leaf_threshold=256)
        oms = bucket_omegas(spec, comp)
        by_bucket = [gs.value(om) for om in oms]
        slot = sorted(spec.slots, key=lambda sl: sl.leaf)
        g_big, g_small = (by_bucket[slot[0].bucket],
                          by_bucket[slot[1].bucket])
        assert g_small > g_big, (g_small, g_big)

        ex = make_gossip_exchange(
            mode="choco", mesh=mesh,
            state_specs={{"big": P("data", None), "small": P("data", None)}},
            axis="data", compressor=comp, gamma=gs, schedules=(sched,),
            packed=True, exact_small_leaves=True, small_leaf_threshold=256,
            pipelined={pipelined})
        z = lambda t: jax.tree.map(jnp.zeros_like, t)
        x = {{"big": big, "small": small}}
        hat, s = z(x), z(x)
        # independent per-bucket simulators: top-k on the big leaf (the
        # packed bucket budget equals the per-leaf budget: one slot), exact
        # (Identity) on the small leaf
        from repro.core.compression import _resolve_k
        kb = _resolve_k(DBIG, None, 0.05)   # the compressor's own fraction->k
        sims = {{"big": (TopK(k=kb), g_big), "small": (Identity(), g_small)}}
        if {pipelined}:
            st = {{n: init_pipelined_state(v) for n, v in x.items()}}
        else:
            st = {{n: init_efficient_state(v) for n, v in x.items()}}
        for t in range(STEPS):
            gk = jax.random.fold_in(k0, 100 + t)
            x, hat, s = ex(gk, x, hat, s)
            for n, (c, g) in sims.items():
                if {pipelined}:
                    q = jax.vmap(c)(jax.random.split(gk, N), st[n].x - st[n].x_hat)
                    st[n] = PipelinedGossipState(
                        x=st[n].x + g * (st[n].s - st[n].x_hat),
                        x_hat=st[n].x_hat + q, s=st[n].s + W @ q)
                else:
                    st[n] = choco_gossip_round_efficient(st[n], W, g, c)
            for n in x:
                np.testing.assert_allclose(
                    np.asarray(x[n]), np.asarray(st[n].x),
                    rtol=1e-4, atol=1e-5, err_msg=f"leaf {{n}} step {{t}}")
        print("MATCH")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_fingerprint_compression_change_routes_elastic():
    """Satellite-1 regression: resuming with a different compression ratio
    (or packing layout) is NOT resume-exact — x_hat/s re-zero and consensus
    warmup engages; an identical config stays warmup-0; a pre-PR-6 manifest
    (keys absent) stays resume-exact."""
    run_sub("""
        import json, os, tempfile
        from repro.configs.base import ChocoConfig, get_config
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import make_optimizer, cosine_schedule
        from repro.checkpoint.manifest import manifest_path

        cfg = get_config("qwen3-1.7b", smoke=True)
        model = build_model(cfg)
        mesh = make_mesh((8, 1), ("data", "model"))

        def trainer(frac):
            return DecentralizedTrainer(
                model=model,
                choco=ChocoConfig(compressor="top_k",
                                  comp_kwargs=(("fraction", frac),),
                                  gossip_axis="data"),
                mesh=mesh, n_nodes=8, optimizer=make_optimizer("momentum"),
                lr_fn=cosine_schedule(0.1, warmup=10, total=100),
                mode="choco")

        ta = trainer(0.05)
        fp = ta.fingerprint()
        assert fp["compressor_config"] == {"fraction": 0.05}, fp
        assert fp["packed_gossip"] is True and fp["pipeline_gossip"] is False

        state = ta.init_state(jax.random.PRNGKey(0))
        d = tempfile.mkdtemp()
        path = os.path.join(d, "step0")
        ta.save_checkpoint(path, state)

        _, _, warm_same = ta.restore_checkpoint(path)
        assert warm_same == 0, warm_same

        tb = trainer(0.2)          # different ratio -> different omega
        st_b, _, warm_diff = tb.restore_checkpoint(path)
        assert warm_diff > 0, warm_diff
        assert float(jnp.sum(jnp.abs(
            jax.tree.leaves(st_b.x_hat)[0]))) == 0.0   # EF state re-zeroed

        # pre-PR-6 manifest: drop the new fingerprint keys -> resume-exact
        mp = manifest_path(path)
        man = json.load(open(mp))
        for k in ("compressor_config", "packed_gossip", "pack_align",
                  "pipeline_gossip"):
            man["fingerprint"].pop(k, None)
        json.dump(man, open(mp, "w"))
        _, _, warm_legacy = ta.restore_checkpoint(path)
        assert warm_legacy == 0, warm_legacy
        print("OK")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_pipelined_collective_is_batch_independent():
    """The overlap property as a dependency fact on the compiled HLO of the
    qwen3-1.7b smoke train step (benchmarks/bench_overlap.py audit): in the
    serial engine every forward/backward dot feeds the collective-permute;
    in the pipelined engine none do — so an async backend may schedule the
    whole transfer concurrently with the backward pass.  Launch counts must
    match (pipelining adds zero collectives)."""
    out = run_sub("""
        import json
        from repro.configs.base import ChocoConfig, get_config
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import make_optimizer, cosine_schedule
        from repro.data.synthetic import make_lm_batch_fn
        from repro.analysis.hlo_audit import collective_dependency_audit

        cfg = get_config("qwen3-1.7b", smoke=True)
        model = build_model(cfg)
        mesh = make_mesh((8, 1), ("data", "model"))
        nb = make_lm_batch_fn(cfg, 64, 2, 8, 1.0)
        res = {}
        for pipe in (False, True):
            tr = DecentralizedTrainer(
                model=model,
                choco=ChocoConfig(compressor="top_k",
                                  comp_kwargs=(("fraction", 0.05),),
                                  gossip_axis="data", pipeline_gossip=pipe),
                mesh=mesh, n_nodes=8, optimizer=make_optimizer("momentum"),
                lr_fn=cosine_schedule(0.1, warmup=10, total=100),
                mode="choco")
            state = tr.init_state(jax.random.PRNGKey(0))
            batch = jax.tree.map(jnp.asarray, nb())
            step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                        jax.eval_shape(lambda: batch))
            hlo = step.lower(state, batch).compile().as_text()
            res["pipelined" if pipe else "serial"] = collective_dependency_audit(hlo).as_dict()
        print("AUDIT=" + json.dumps(res))
    """)
    import json
    res = json.loads([l for l in out.splitlines()
                      if l.startswith("AUDIT=")][-1][len("AUDIT="):])
    serial, pipe = res["serial"], res["pipelined"]
    assert serial["permute_launches"] == pipe["permute_launches"] > 0
    assert serial["dots_total"] == pipe["dots_total"] > 0
    assert serial["dots_feeding_collective"] == serial["dots_total"]
    assert pipe["dots_feeding_collective"] == 0


@pytest.mark.slow
@pytest.mark.distributed
def test_pipelined_trainer_end_to_end_converges():
    """Full pipelined trainer on the smoke config: loss decreases and the
    tau=1 gamma is strictly below the serial trainer's (omega folds to
    omega/2 and (W+I)/2 halves the eigengap)."""
    run_sub("""
        from repro.configs.base import ChocoConfig, get_config
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import make_optimizer, cosine_schedule
        from repro.data.synthetic import make_lm_batch_fn
        from repro.core.choco_gossip import GammaSpec

        cfg = get_config("qwen3-1.7b", smoke=True)
        model = build_model(cfg)
        mesh = make_mesh((8, 1), ("data", "model"))

        def trainer(pipe):
            return DecentralizedTrainer(
                model=model,
                choco=ChocoConfig(compressor="top_k",
                                  comp_kwargs=(("fraction", 0.05),),
                                  gossip_axis="data", pipeline_gossip=pipe),
                mesh=mesh, n_nodes=8, optimizer=make_optimizer("momentum"),
                lr_fn=cosine_schedule(0.1, warmup=2, total=12),
                mode="choco")

        ts, tp = trainer(False), trainer(True)
        assert tp.gamma < ts.gamma, (tp.gamma, ts.gamma)
        assert isinstance(tp.gamma_spec, GammaSpec)
        assert tp.gamma_spec.omega_scale == 0.5

        nb = make_lm_batch_fn(cfg, 64, 2, 8, 1.0)
        state = tp.init_state(jax.random.PRNGKey(0))
        batch0 = jax.tree.map(jnp.asarray, nb())
        step = tp.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: batch0))
        losses = []
        for _ in range(12):
            state, mets = step(state, jax.tree.map(jnp.asarray, nb()))
            losses.append(float(mets["loss"]))
        assert losses[-1] < losses[0] - 0.5, losses
        print("OK", losses[0], losses[-1])
    """)
