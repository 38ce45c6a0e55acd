"""Fused Pallas gossip path (kernels/dispatch.py + comm/gossip.py).

Cross-backend parity contract for `--kernel-backend`, asserted per
compressor on the real 8-device shard_map engine:

  * wire payloads are identical — witnessed by round-1 ``x_hat`` being
    bit-exact across backends (x_hat moves only by the dequantized wire
    codes, so equal x_hat == equal codes+scales);
  * all state accumulates only FMA-contraction rounding across rounds —
    bounded at 1e-5 over 5 rounds, measured drift is ~1e-6.  The drift
    source is the EF kernel's x-update compiling separately from the
    in-context jnp graph (different mul+add contraction choices), so it
    applies to every compressor, deterministic ones included: round-2
    deltas quantize the ulp-drifted x.

Plus the launch-count proof behind BENCH_fused.json (exactly
``2 * n_buckets * gossip_steps`` pallas_call equations per exchange: one
fused quantize+pack and one fused dequant+EF-update per bucket per
round) and checkpoint-fingerprint invariance across backends.
Multi-device tests follow the tests/test_distributed.py subprocess
pattern.
"""
import os
import subprocess
import sys
import textwrap

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
# slow tier — 8-device engine parity / launch counts / fingerprint
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("comp", [
    "QSGD(s=16)",
    "QSGD(s=200)",                   # int16 wire format
    "SignNorm()",
    "TopK(k=9)",
    "Identity()",
])
def test_fused_engine_cross_backend_parity(comp):
    """jnp vs pallas backend on the multi-leaf packed engine, 5 rounds.
    Round-1 x_hat is always bit-exact (the wire witness); all later state
    drifts only at FMA rounding level (see module docstring)."""
    run_sub(f"""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.core import QSGD, SignNorm, TopK, Identity

        n = 8
        mesh = make_mesh((8, 1), ("data", "model"))
        tree0 = {{"a": jax.random.normal(jax.random.PRNGKey(1), (n, 384)),
                  "b": jax.random.normal(jax.random.PRNGKey(2), (n, 130)),
                  "c": jax.random.normal(jax.random.PRNGKey(3), (n, 512))}}
        specs = {{k: P("data", None) for k in tree0}}
        outs, r1hat = {{}}, {{}}
        for bk in ("jnp", "pallas"):
            ex = make_gossip_exchange(mode="choco", mesh=mesh,
                                      state_specs=specs, axis="data",
                                      compressor={comp}, gamma=0.07,
                                      kernel_backend=bk)
            x = dict(tree0)
            xh = jax.tree.map(jnp.zeros_like, tree0)
            s = jax.tree.map(jnp.zeros_like, tree0)
            for i in range(5):
                x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
                if i == 0:
                    r1hat[bk] = xh
            outs[bk] = (x, xh, s)
        for k in tree0:
            np.testing.assert_array_equal(
                np.asarray(r1hat["jnp"][k]), np.asarray(r1hat["pallas"][k]))
        for j in range(3):
            for k in tree0:
                np.testing.assert_allclose(np.asarray(outs["jnp"][j][k]),
                                           np.asarray(outs["pallas"][j][k]),
                                           rtol=0, atol=1e-5)
        print("PARITY")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_fused_pallas_engine_matches_matrix_simulator():
    """The pallas-backed engine reproduces the (n, d) matrix simulator with
    the same tolerances the jnp engine is held to (deterministic TopK so
    compressor randomness cannot diverge)."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.core.choco_gossip import (choco_gossip_round_efficient,
                                             init_efficient_state)
        from repro.core import ring, TopK

        n, d = 8, 128
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(k=9)
        gamma = 0.07
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))

        W = jnp.asarray(ring(n).W)
        st = init_efficient_state(x0)
        for _ in range(5):
            st = choco_gossip_round_efficient(st, W, gamma, comp)

        specs = {"w": P("data", None)}
        ex = make_gossip_exchange(mode="choco", mesh=mesh, state_specs=specs,
                                  axis="data", compressor=comp, gamma=gamma,
                                  kernel_backend="pallas")
        x = {"w": x0}
        xh = {"w": jnp.zeros_like(x0)}
        s = {"w": jnp.zeros_like(x0)}
        for i in range(5):
            x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
        np.testing.assert_allclose(np.asarray(x["w"]), np.asarray(st.x),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(xh["w"]), np.asarray(st.x_hat),
                                   rtol=1e-4, atol=1e-5)
        print("MATCH")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_fused_launch_count_per_bucket_per_round():
    """Exactly 2 fused kernel launches per bucket per gossip round — one
    quantize+pack, one dequant+EF-update — and zero on the jnp backend.
    This is the structural claim BENCH_fused.json's stream audit rests
    on: more launches would mean unfused glue re-reading the buckets."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.packing import make_bucket_spec
        from repro.core import QSGD
        from repro.analysis.jaxpr_audit import count_pallas_calls

        n, steps = 8, 3
        mesh = make_mesh((8, 1), ("data", "model"))
        tree0 = {"a": jax.random.normal(jax.random.PRNGKey(1), (n, 384)),
                 "b": jax.random.normal(jax.random.PRNGKey(2), (n, 4, 130))}
        specs = {k: P("data", None) for k in tree0}
        local = [jax.ShapeDtypeStruct((1,) + v.shape[1:], v.dtype)
                 for v in tree0.values()]
        spec = make_bucket_spec(local)
        counts = {}
        for bk in ("jnp", "pallas"):
            ex = make_gossip_exchange(mode="choco", mesh=mesh,
                                      state_specs=specs, axis="data",
                                      compressor=QSGD(s=16), gamma=0.07,
                                      gossip_steps=steps, kernel_backend=bk)
            z = jax.tree.map(jnp.zeros_like, tree0)
            jaxpr = jax.make_jaxpr(ex)(jax.random.PRNGKey(0), tree0, z, z)
            counts[bk] = count_pallas_calls(jaxpr.jaxpr)
        assert counts["jnp"] == 0, counts
        assert counts["pallas"] == 2 * spec.n_buckets * steps, (
            counts, spec.n_buckets)
        print("LAUNCHES", counts)
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_kernel_backend_never_in_fingerprint():
    """Flipping --kernel-backend must not change the checkpoint
    fingerprint or state layout: a run restarted on a host without the
    Pallas toolchain has to restore bit-compatibly."""
    run_sub("""
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import make_optimizer, cosine_schedule

        cfg = get_config("qwen3-1.7b", smoke=True)
        model = build_model(cfg)
        mesh = make_mesh((8, 1), ("data", "model"))
        fps, layouts = [], []
        for bk in ("jnp", "pallas", "auto"):
            tr = DecentralizedTrainer(
                model=model,
                choco=ChocoConfig(compressor="qsgd",
                                  comp_kwargs=(("s", 16),),
                                  gossip_axis="data", kernel_backend=bk),
                mesh=mesh, n_nodes=8,
                optimizer=make_optimizer("momentum"),
                lr_fn=cosine_schedule(0.1, warmup=10, total=100),
                mode="choco")
            fps.append(tr.fingerprint())
            state = tr.init_state(jax.random.PRNGKey(0))
            layouts.append(jax.tree.map(
                lambda l: (l.shape, str(l.dtype)), state.params))
        assert fps[0] == fps[1] == fps[2], fps
        assert layouts[0] == layouts[1] == layouts[2]
        print("FINGERPRINT", fps[0])
    """)
