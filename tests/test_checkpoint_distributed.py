"""Resume-exactness and elastic restore on a simulated 8-device mesh.

Acceptance for the sharded checkpoint subsystem:
  * checkpoint at step k then resume is BIT-identical to the uninterrupted
    run on the same mesh (params, x_hat, s, optimizer moments) — the CHOCO
    error-feedback state survives restarts exactly, as Theorem 2 requires;
  * elastic restore n=4 -> n=8 runs end-to-end with the re-derived
    Theorem-2 gamma: params cyclic-tiled, x_hat/s re-zeroed, and after the
    logged consensus warmup the tiled state is no worse-mixed than a fresh
    init put through the same warmup;
  * the launcher's --resume treats --steps as the TOTAL budget (the cosine
    schedule continues from the manifest step instead of replaying from 0).
"""
import pytest

from test_distributed import run_sub

pytestmark = [pytest.mark.slow, pytest.mark.distributed]


def test_resume_bit_exact_same_mesh():
    run_sub("""
        import tempfile
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import momentum_sgd, cosine_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((8, 1), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)

        def make_trainer():
            # bfloat16 EF state: the bit-exact check covers the manifest's
            # uint16 bit-cast round trip, not just f32 passthrough
            return DecentralizedTrainer(model=m, choco=ChocoConfig(
                    compressor="top_k", comp_kwargs=(("fraction", 0.05),),
                    state_dtype="bfloat16"),
                mesh=mesh, n_nodes=8, optimizer=momentum_sgd(),
                lr_fn=cosine_schedule(0.1, warmup=2, total=6))

        tr = make_trainer()
        nb = make_lm_batch_fn(cfg, 32, 2, 8)
        batches = [jax.tree.map(jnp.asarray, nb()) for _ in range(6)]
        st0 = tr.init_state(jax.random.PRNGKey(0))
        shapes = (jax.eval_shape(lambda: st0), jax.eval_shape(lambda: batches[0]))
        step = tr.jitted_train_step(*shapes)

        ref = tr.init_state(jax.random.PRNGKey(0))
        for b in batches:
            ref, _ = step(ref, b)
        ref = jax.device_get(ref)

        state = st0
        for b in batches[:3]:
            state, _ = step(state, b)
        ckpt = tempfile.mkdtemp() + "/step3"
        tr.save_checkpoint(ckpt, state, metadata={"arch": cfg.name})

        tr2 = make_trainer()
        got, man, warmup = tr2.restore_checkpoint(ckpt)
        assert warmup == 0 and man.step == 3, (warmup, man.step)
        assert man.fingerprint["n_nodes"] == 8
        step2 = tr2.jitted_train_step(*shapes)
        for b in batches[3:]:
            got, _ = step2(got, b)
        got = jax.device_get(got)

        def bits(x):
            return np.asarray(x).reshape(-1).view(np.uint8)
        for name in ("params", "x_hat", "s", "opt"):
            for a, b in zip(jax.tree.leaves(getattr(ref, name)),
                            jax.tree.leaves(getattr(got, name))):
                np.testing.assert_array_equal(bits(a), bits(b), err_msg=name)
        assert int(ref.step) == int(got.step) == 6
        np.testing.assert_array_equal(np.asarray(ref.key), np.asarray(got.key))
        print("RESUME BIT-EXACT")
    """)


def test_elastic_restore_4_to_8():
    run_sub("""
        import tempfile
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        choco = lambda: ChocoConfig(compressor="top_k",
                                    comp_kwargs=(("fraction", 0.05),))

        mesh4 = make_mesh((4, 2), ("data", "model"))
        tr4 = DecentralizedTrainer(model=m, choco=choco(), mesh=mesh4,
                                   n_nodes=4, optimizer=sgd(),
                                   lr_fn=constant_schedule(0.05))
        nb4 = make_lm_batch_fn(cfg, 32, 2, 4)
        st = tr4.init_state(jax.random.PRNGKey(0))
        b4 = jax.tree.map(jnp.asarray, nb4())
        step4 = tr4.jitted_train_step(jax.eval_shape(lambda: st),
                                      jax.eval_shape(lambda: b4))
        for i in range(4):
            st, _ = step4(st, jax.tree.map(jnp.asarray, nb4()))
        ck = tempfile.mkdtemp() + "/step4"
        tr4.save_checkpoint(ck, st)
        old = jax.device_get(st)

        mesh8 = make_mesh((8, 1), ("data", "model"))
        tr8 = DecentralizedTrainer(model=m, choco=choco(), mesh=mesh8,
                                   n_nodes=8, optimizer=sgd(),
                                   lr_fn=constant_schedule(0.05))
        # gamma re-derived from the NEW graph (ring n=8) by __post_init__
        got, man, warmup = tr8.restore_checkpoint(ck)
        assert man.n_nodes == 4 and warmup > 0, (man.n_nodes, warmup)
        assert 0 < tr8.gamma < 1 and tr8.gamma != tr4.gamma

        # cyclic tile: new node j holds old node j % 4, bit for bit
        for po, pn in zip(jax.tree.leaves(old.params),
                          jax.tree.leaves(got.params)):
            np.testing.assert_array_equal(np.asarray(pn),
                                          np.asarray(po)[np.arange(8) % 4])
        # stale public copies re-zeroed; step survives
        for l in jax.tree.leaves(got.x_hat) + jax.tree.leaves(got.s):
            assert float(jnp.sum(jnp.abs(l.astype(jnp.float32)))) == 0.0
        assert int(got.step) == 4

        def cerr(state):
            rows = jnp.concatenate(
                [jnp.reshape(l, (8, -1)).astype(jnp.float32)
                 for l in jax.tree.leaves(state.params)], axis=1)
            mu = jnp.mean(rows, 0, keepdims=True)
            return float(jnp.mean(jnp.sum((rows - mu) ** 2, -1)))

        fresh = tr8.init_state(jax.random.PRNGKey(1))
        e_fresh0 = cerr(fresh)
        warmed = tr8.consensus_warmup(got, warmup)
        e_warm = cerr(warmed)
        warmed_fresh = tr8.consensus_warmup(fresh, warmup)
        e_fresh = cerr(warmed_fresh)
        print("consensus err after warmup: elastic", e_warm,
              "fresh", e_fresh, "(fresh pre-warmup", e_fresh0, ")")
        # acceptance: contraction no worse than fresh init after the warmup
        assert e_warm <= e_fresh + 1e-6, (e_warm, e_fresh)

        # end-to-end: training continues under the new mesh / gamma
        nb8 = make_lm_batch_fn(cfg, 32, 2, 8)
        b8 = jax.tree.map(jnp.asarray, nb8())
        step8 = tr8.jitted_train_step(jax.eval_shape(lambda: warmed),
                                      jax.eval_shape(lambda: b8))
        s8 = warmed
        for i in range(3):
            s8, mets = step8(s8, jax.tree.map(jnp.asarray, nb8()))
        assert np.isfinite(float(mets["loss"]))
        assert int(s8.step) == 7
        print("ELASTIC 4->8 OK")
    """)


def test_launcher_resume_total_steps():
    """--steps is the TOTAL budget: a resumed run trains steps-resumed more
    steps with the cosine schedule anchored at the manifest step (the
    pre-fix launcher re-ran the full --steps at terminal LR); an exhausted
    budget fails fast."""
    run_sub("""
        import os, tempfile
        from repro.launch.train import main
        from repro.checkpoint.manifest import read_manifest

        d = tempfile.mkdtemp()
        base = ["--arch", "qwen3-1.7b", "--smoke", "--mesh", "8x1",
                "--simulate-devices", "8", "--seq-len", "32",
                "--batch-per-node", "2", "--compressor", "top_k",
                "--fraction", "0.05", "--optimizer", "sgd", "--lr", "0.05",
                "--checkpoint-dir", d, "--checkpoint-every", "2"]
        assert main(base + ["--steps", "4"]) == 0
        ck4 = os.path.join(d, "step4")
        assert read_manifest(ck4).step == 4

        # resume with TOTAL budget 6 -> exactly 2 more steps, lands on 6
        assert main(base + ["--steps", "6", "--resume", ck4]) == 0
        assert read_manifest(os.path.join(d, "step6")).step == 6

        # budget already consumed: fail fast instead of terminal-LR retrain
        try:
            main(base + ["--steps", "4", "--resume", ck4])
            raise AssertionError("expected SystemExit")
        except SystemExit as e:
            assert "TOTAL step budget" in str(e), e
        print("CLI RESUME OK")
    """)
