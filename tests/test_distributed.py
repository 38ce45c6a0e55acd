"""Distributed gossip / trainer tests.

These need >1 device, so each test runs a short script in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (never set globally —
the assignment requires smoke tests to see 1 device)."""
import os
import subprocess
import sys
import textwrap

import pytest

# every test here compiles shard_map graphs in an 8-device subprocess:
# deselect with -m "not slow" for the fast inner loop (see pytest.ini)
pytestmark = [pytest.mark.slow, pytest.mark.distributed]

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str, timeout=420):
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.parametrize("packed", [True, False])
def test_distributed_choco_matches_matrix_simulator(packed):
    """The shard_map/ppermute gossip — both the bucketed flat-buffer engine
    and the legacy per-leaf exchange — reproduces the (n,d) matrix simulator
    (injecting identical compressor randomness via fold-ins is impractical,
    so we use the deterministic top_k operator)."""
    run_sub(f"""
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.comm.gossip import make_gossip_exchange
        from repro.core.choco_gossip import (choco_gossip_round_efficient,
                                             init_efficient_state)
        from repro.core import ring, TopK

        n, d = 8, 96
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(k=9)            # deterministic: no RNG divergence
        gamma = 0.07
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))

        # matrix simulator (W = uniform ring)
        W = jnp.asarray(ring(n).W)
        st = init_efficient_state(x0)
        for _ in range(5):
            st = choco_gossip_round_efficient(st, W, gamma, comp)

        # distributed: leaves (n, d) sharded over 'data'
        specs = {{"w": P("data", None)}}
        ex = make_gossip_exchange(mode="choco", mesh=mesh, state_specs=specs,
                                  axis="data", compressor=comp, gamma=gamma,
                                  packed={packed})
        x = {{"w": x0}}
        xh = {{"w": jnp.zeros_like(x0)}}
        s = {{"w": jnp.zeros_like(x0)}}
        for i in range(5):
            x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
        np.testing.assert_allclose(np.asarray(x["w"]), np.asarray(st.x),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(xh["w"]), np.asarray(st.x_hat),
                                   rtol=1e-4, atol=1e-5)
        print("MATCH")
    """)


def test_distributed_packed_multi_leaf_matches_per_leaf():
    """Bucketed engine == legacy per-leaf exchange, bit for bit, on a
    multi-leaf tree with unaligned sizes (blockwise operator commutes with
    the engine's block-aligned packing)."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.core import BlockTopK

        n = 8
        mesh = make_mesh((8, 1), ("data", "model"))
        tree0 = {"a": jax.random.normal(jax.random.PRNGKey(1), (n, 384)),
                 "b": jax.random.normal(jax.random.PRNGKey(2), (n, 130)),
                 "c": jax.random.normal(jax.random.PRNGKey(3), (n, 512))}
        specs = {k: P("data", None) for k in tree0}
        comp = BlockTopK(k_per_block=5, block=128)
        outs = {}
        for packed in (True, False):
            ex = make_gossip_exchange(mode="choco", mesh=mesh,
                                      state_specs=specs, axis="data",
                                      compressor=comp, gamma=0.07,
                                      packed=packed)
            x = dict(tree0)
            xh = jax.tree.map(jnp.zeros_like, tree0)
            s = jax.tree.map(jnp.zeros_like, tree0)
            for i in range(3):
                x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
            outs[packed] = (x, xh, s)
        for j in range(3):
            for k in tree0:
                np.testing.assert_array_equal(np.asarray(outs[True][j][k]),
                                              np.asarray(outs[False][j][k]))
        print("PACKED == PER-LEAF")
    """)


def test_distributed_allreduce_is_exact_mean():
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        mesh = make_mesh((8, 1), ("data", "model"))
        x0 = jax.random.normal(jax.random.PRNGKey(0), (8, 33))
        ex = make_gossip_exchange(mode="allreduce", mesh=mesh,
                                  state_specs=P("data", None), axis="data")
        x, _, _ = ex(jax.random.PRNGKey(0), x0, x0 * 0, x0 * 0)
        np.testing.assert_allclose(np.asarray(x),
                                   np.broadcast_to(np.asarray(x0).mean(0), x0.shape),
                                   rtol=1e-5, atol=1e-6)
        print("OK")
    """)


def test_trainer_choco_loss_decreases():
    run_sub("""
        from repro.configs.base import get_config, ChocoConfig, InputShape
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.launch.specs import train_batch_specs
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        tr = DecentralizedTrainer(model=m, choco=ChocoConfig(
                compressor="top_k", comp_kwargs=(("fraction", 0.05),)),
            mesh=mesh, n_nodes=4, optimizer=sgd(),
            lr_fn=constant_schedule(0.05))
        state = tr.init_state(jax.random.PRNGKey(0))
        next_batch = make_lm_batch_fn(cfg, seq_len=32, batch_per_node=4,
                                      n_nodes=4, heterogeneity=1.0)
        b0 = jax.tree.map(jnp.asarray, next_batch())
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: b0))
        losses = []
        for i in range(30):
            state, mets = step(state, jax.tree.map(jnp.asarray, next_batch()))
            losses.append(float(mets["loss"]))
        assert all(np.isfinite(losses)), losses
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses
        # x_hat tracks params (error feedback engages)
        xh = jnp.concatenate([a.ravel() for a in jax.tree.leaves(state.x_hat)])
        assert float(jnp.sum(jnp.abs(xh))) > 0
        print("LOSS", losses[0], "->", losses[-1])
    """)


def test_trainer_modes_plain_and_allreduce():
    run_sub("""
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("yi-9b", smoke=True)
        m = build_model(cfg)
        next_batch = make_lm_batch_fn(cfg, 32, 4, 4)
        for mode in ("plain", "allreduce"):
            tr = DecentralizedTrainer(model=m, choco=ChocoConfig(), mesh=mesh,
                                      n_nodes=4, optimizer=sgd(),
                                      lr_fn=constant_schedule(0.05), mode=mode)
            state = tr.init_state(jax.random.PRNGKey(0))
            b = jax.tree.map(jnp.asarray, next_batch())
            step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                        jax.eval_shape(lambda: b))
            for i in range(5):
                state, mets = step(state, jax.tree.map(jnp.asarray, next_batch()))
            assert np.isfinite(float(mets["loss"])), mode
            if mode == "allreduce":
                # exact averaging keeps replicas identical
                p = jax.tree.leaves(state.params)[0]
                np.testing.assert_allclose(np.asarray(p[0]), np.asarray(p[1]),
                                           rtol=1e-4, atol=1e-5)
        print("MODES OK")
    """)


def test_multipod_style_gossip_axis():
    """2-node gossip over 'pod' with FSDP over 'data' (multi-pod layout)."""
    run_sub("""
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        tr = DecentralizedTrainer(model=m,
            choco=ChocoConfig(gossip_axis="pod",
                              compressor="top_k", comp_kwargs=(("fraction", 0.1),)),
            mesh=mesh, n_nodes=2, optimizer=sgd(), lr_fn=constant_schedule(0.05))
        state = tr.init_state(jax.random.PRNGKey(0))
        next_batch = make_lm_batch_fn(cfg, 32, 4, 2)
        b = jax.tree.map(jnp.asarray, next_batch())
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: b))
        for i in range(3):
            state, mets = step(state, jax.tree.map(jnp.asarray, next_batch()))
        assert np.isfinite(float(mets["loss"]))
        print("MULTIPOD OK", float(mets["loss"]))
    """)


def test_schedule_engine_bitmatches_legacy_ring_torus():
    """Regression for the schedule refactor: the compiled ring and torus
    schedules must reproduce the pre-refactor hardcoded engines bit for bit
    (same ppermute data movement, same accumulation order, same weak-typed
    uniform weights).  The legacy engines are inlined here verbatim from the
    PR-1 comm/gossip.py."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import (_choco_leaf_updates, _flatten_states,
                                       _packed_self_half,
                                       make_gossip_exchange)
        from repro.comm.packing import (bucket_dense, make_bucket_spec,
                                        unpack_leaves)
        from repro.core import BlockTopK

        comp = BlockTopK(k_per_block=5, block=128)
        gamma = 0.07

        def ring_perm(n, shift):
            return [(i, (i + shift) % n) for i in range(n)]

        def legacy_ring_packed(axis, axis_size):
            w_self = w_nbr = 1.0 / 3.0
            fwd, bwd = ring_perm(axis_size, 1), ring_perm(axis_size, -1)
            def local_fn(key, x_half, x_hat, s):
                key = jax.random.fold_in(key, jax.lax.axis_index(axis))
                leaves_h, leaves_hat, leaves_s, treedef = _flatten_states(
                    x_half, x_hat, s)
                spec = make_bucket_spec(leaves_hat, align=128)
                payloads, q_leaves, new_hat = _packed_self_half(
                    comp, key, leaves_h, leaves_hat, spec)
                got_l = jax.lax.ppermute(payloads, axis, fwd)
                got_r = jax.lax.ppermute(payloads, axis, bwd)
                nbr_bufs = [bucket_dense(l, b) + bucket_dense(r, b)
                            for l, r, b in zip(got_l, got_r, spec.buckets)]
                nbr_leaves = unpack_leaves(spec, nbr_bufs)
                new_s, new_x = _choco_leaf_updates(
                    leaves_h, leaves_s, q_leaves, nbr_leaves, new_hat,
                    w_self, w_nbr, gamma)
                u = treedef.unflatten
                return u(new_x), u(new_hat), u(new_s)
            return local_fn

        def legacy_torus_packed(axes, sizes):
            n_edges = sum(2 if n > 2 else (1 if n == 2 else 0) for n in sizes)
            w = 1.0 / (1.0 + n_edges)
            def local_fn(key, x_half, x_hat, s):
                for a in axes:
                    key = jax.random.fold_in(key, jax.lax.axis_index(a))
                leaves_h, leaves_hat, leaves_s, treedef = _flatten_states(
                    x_half, x_hat, s)
                spec = make_bucket_spec(leaves_hat, align=128)
                payloads, q_leaves, new_hat = _packed_self_half(
                    comp, key, leaves_h, leaves_hat, spec)
                nbr_bufs = [jnp.zeros((b.size,), b.dtype) for b in spec.buckets]
                for a, n in zip(axes, sizes):
                    if n < 2:
                        continue
                    got = jax.lax.ppermute(payloads, a, ring_perm(n, 1))
                    nbr_bufs = [acc + bucket_dense(g, b)
                                for acc, g, b in zip(nbr_bufs, got, spec.buckets)]
                    if n > 2:
                        got = jax.lax.ppermute(payloads, a, ring_perm(n, -1))
                        nbr_bufs = [acc + bucket_dense(g, b)
                                    for acc, g, b in zip(nbr_bufs, got, spec.buckets)]
                nbr_leaves = unpack_leaves(spec, nbr_bufs)
                new_s, new_x = _choco_leaf_updates(
                    leaves_h, leaves_s, q_leaves, nbr_leaves, new_hat,
                    w, w, gamma)
                u = treedef.unflatten
                return u(new_x), u(new_hat), u(new_s)
            return local_fn

        tree0 = {"a": jax.random.normal(jax.random.PRNGKey(1), (8, 384)),
                 "b": jax.random.normal(jax.random.PRNGKey(2), (8, 130)),
                 "c": jax.random.normal(jax.random.PRNGKey(3), (8, 512))}

        def run(ex, specs_tree):
            x = dict(tree0)
            xh = jax.tree.map(jnp.zeros_like, tree0)
            s = jax.tree.map(jnp.zeros_like, tree0)
            outs = []
            for i in range(3):
                x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
                outs.append((x, xh, s))
            return outs

        # -- ring on one axis ------------------------------------------------
        mesh = make_mesh((8, 1), ("data", "model"))
        specs = {k: P("data", None) for k in tree0}
        legacy = jax.shard_map(legacy_ring_packed("data", 8), mesh=mesh,
                           in_specs=(P(), specs, specs, specs),
                           out_specs=(specs, specs, specs))
        new = make_gossip_exchange(mode="choco", mesh=mesh, state_specs=specs,
                                   axis="data", compressor=comp, gamma=gamma)
        for (xo, xho, so), (xn, xhn, sn) in zip(run(legacy, specs),
                                                run(new, specs)):
            for k in tree0:
                np.testing.assert_array_equal(np.asarray(xo[k]), np.asarray(xn[k]))
                np.testing.assert_array_equal(np.asarray(xho[k]), np.asarray(xhn[k]))
                np.testing.assert_array_equal(np.asarray(so[k]), np.asarray(sn[k]))

        # -- torus on a (pod, data) axis pair --------------------------------
        mesh = make_mesh((2, 4, 1), ("pod", "data", "model"))
        specs = {k: P(("pod", "data"), None) for k in tree0}
        legacy = jax.shard_map(legacy_torus_packed(("pod", "data"), (2, 4)),
                           mesh=mesh, in_specs=(P(), specs, specs, specs),
                           out_specs=(specs, specs, specs))
        new = make_gossip_exchange(mode="choco", mesh=mesh, state_specs=specs,
                                   axis=("pod", "data"), compressor=comp,
                                   gamma=gamma)
        for (xo, xho, so), (xn, xhn, sn) in zip(run(legacy, specs),
                                                run(new, specs)):
            for k in tree0:
                np.testing.assert_array_equal(np.asarray(xo[k]), np.asarray(xn[k]))
                np.testing.assert_array_equal(np.asarray(xho[k]), np.asarray(xhn[k]))
                np.testing.assert_array_equal(np.asarray(so[k]), np.asarray(sn[k]))
        print("BITMATCH OK")
    """)


def test_multi_step_gossip_beats_single_step():
    """gossip_steps=3 (three CHOCO consensus rounds per SGD step, one packed
    spec) must contract consensus error strictly further than one round —
    the Hashemi et al. multiple-gossip-steps effect."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.core import make_topology, TopK

        n, d = 8, 256
        topo = make_topology("hypercube", n)
        comp = TopK(k=64)
        # practical consensus stepsize: the Theorem-2 worst-case gamma
        # contracts by <0.2% per round, far too slow to separate k in one
        # SGD step (it is a safety bound, not the tuned value)
        gamma = 0.4
        mesh = make_mesh((8, 1), ("data", "model"))
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        xbar = jnp.mean(x0, axis=0, keepdims=True)

        def consensus_err(x):
            return float(jnp.mean(jnp.sum((x - xbar) ** 2, axis=-1)))

        errs = {}
        for k in (1, 3):
            ex = make_gossip_exchange(
                mode="choco", mesh=mesh, state_specs=P("data", None),
                axis="data", compressor=comp, gamma=gamma,
                schedules=(compile_schedule(topo),), gossip_steps=k)
            x, _, _ = ex(jax.random.PRNGKey(0), x0, jnp.zeros_like(x0),
                         jnp.zeros_like(x0))
            errs[k] = consensus_err(x)
        print("consensus err k=1:", errs[1], "k=3:", errs[3])
        assert errs[3] < errs[1] * 0.9, errs
        print("MULTI-STEP OK")
    """)


def test_hypercube_packed_launch_count_end_to_end():
    """Acceptance: hypercube on n=8 simulated devices runs end-to-end
    through the packed engine, and the compiled train step issues at most
    2*log2(n) collective-permute launches per gossip round (payload pairs
    per bucket; one ppermute per dimension-exchange round)."""
    run_sub("""
        import math
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn
        from repro.analysis.roofline import parse_collectives

        mesh = make_mesh((8, 1), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        tr = DecentralizedTrainer(model=m, choco=ChocoConfig(
                compressor="top_k", comp_kwargs=(("fraction", 0.01),),
                topology="hypercube"),
            mesh=mesh, n_nodes=8, optimizer=sgd(),
            lr_fn=constant_schedule(0.05))
        n_rounds = tr.schedules[0].n_rounds
        assert n_rounds == 3, n_rounds                    # log2(8)

        state = tr.init_state(jax.random.PRNGKey(0))
        nb = make_lm_batch_fn(cfg, 32, 4, 8)
        b = jax.tree.map(jnp.asarray, nb())
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: b))
        compiled = step.lower(state, b).compile()
        st = parse_collectives(compiled.as_text(), 8)
        permutes = st.counts["collective-permute"]
        per_round = permutes / n_rounds
        bound = 2 * math.log2(8)
        print("permute launches:", permutes, "rounds:", n_rounds,
              "per-round:", per_round, "bound:", bound)
        assert 0 < per_round <= bound, (permutes, n_rounds, bound)

        losses = []
        for i in range(8):
            state, mets = step(state, jax.tree.map(jnp.asarray, nb()))
            losses.append(float(mets["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        print("HYPERCUBE E2E OK", losses[0], "->", losses[-1])
    """)


def test_trainer_gossip_steps_and_time_varying():
    """Trainer end-to-end with gossip_steps=2 cycling a time-varying
    ring,hypercube schedule sequence."""
    run_sub("""
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        tr = DecentralizedTrainer(model=m, choco=ChocoConfig(
                compressor="top_k", comp_kwargs=(("fraction", 0.05),),
                topology="ring,hypercube", gossip_steps=2),
            mesh=mesh, n_nodes=4, optimizer=sgd(),
            lr_fn=constant_schedule(0.05))
        assert [s.name for s in tr.schedules] == ["ring", "hypercube"]
        state = tr.init_state(jax.random.PRNGKey(0))
        nb = make_lm_batch_fn(cfg, 32, 4, 4)
        b = jax.tree.map(jnp.asarray, nb())
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: b))
        losses = []
        for i in range(10):
            state, mets = step(state, jax.tree.map(jnp.asarray, nb()))
            losses.append(float(mets["loss"]))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
        print("TIME-VARYING K-STEP OK")
    """)
