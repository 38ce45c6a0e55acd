"""Stochastic topology subsystem: randomized matchings, link failures, and
directed push-sum (comm/stochastic.py, comm/pushsum.py).

Fast tier: process construction + expected-W algebra + seed determinism +
matrix-simulator convergence + fail-fast wiring.  The distributed
engine == simulator equivalence tests live at the bottom under the standard
``slow``/``distributed`` markers (subprocess with 8 simulated host devices),
so the fast inner loop (-m "not slow") never compiles shard_map graphs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.topology import (DirectedTopology, beta_norm, directed_ring,
                                 is_directed, make_topology, random_digraph,
                                 ring, spectral_gap)
from repro.core.compression import Identity, TopK
from repro.core.choco_gossip import (init_pushsum_state, pushsum_debias,
                                     pushsum_gossip_round, run_pushsum_gossip)
from repro.comm.schedule import compile_directed_schedule, compile_schedule
from repro.comm.stochastic import (LinkFailureProcess, MatchingProcess,
                                   SAMPLE_SALT, choco_process_round,
                                   init_process_state, make_topology_process,
                                   run_choco_gossip_process)

from repro.launch.mesh import make_mesh
from optional_hypothesis import HAVE_HYPOTHESIS, given, settings, st

TOPOS = ["ring", "hypercube", "star", "chain", "torus", "fully_connected"]


def _sched(name, n=8):
    return compile_schedule(make_topology(name, n))


# ---------------------------------------------------------------------------
# directed topologies + directed schedule compiler
# ---------------------------------------------------------------------------

class TestDirectedTopology:
    @pytest.mark.parametrize("n", [1, 2, 4, 8])
    def test_directed_ring_column_stochastic(self, n):
        topo = directed_ring(n)
        A = topo.A
        np.testing.assert_allclose(A.sum(0), 1.0, atol=1e-12)
        assert np.all(A >= 0)
        if n > 2:
            assert not np.allclose(A, A.T), "directed ring must be asymmetric"

    @pytest.mark.parametrize("seed", [0, 3, 7])
    def test_random_digraph_column_stochastic_connected(self, seed):
        topo = random_digraph(8, 0.3, seed=seed)
        np.testing.assert_allclose(topo.A.sum(0), 1.0, atol=1e-12)
        # ring backbone guarantees strong connectivity -> positive gap
        assert 0.0 < topo.delta <= 1.0

    def test_directed_names_registered(self):
        for name in ("directed_ring", "random_digraph"):
            assert is_directed(name)
            assert isinstance(make_topology(name, 8), DirectedTopology)
        assert not is_directed("ring")

    @pytest.mark.parametrize("topo_fn", [
        lambda: directed_ring(8),
        lambda: random_digraph(8, 0.4, seed=1),
        lambda: random_digraph(6, 0.7, seed=2),
    ])
    def test_directed_schedule_reconstructs_A(self, topo_fn):
        topo = topo_fn()
        sched = compile_directed_schedule(topo)
        np.testing.assert_allclose(sched.mixing_matrix(), topo.A, atol=1e-12)
        # every round is a partial permutation: distinct srcs, distinct dsts
        for rnd in sched.rounds:
            srcs = [s for s, _ in rnd.perm]
            dsts = [d for _, d in rnd.perm]
            assert len(set(srcs)) == len(srcs)
            assert len(set(dsts)) == len(dsts)

    def test_symmetric_compiler_rejects_directed_W(self):
        topo = directed_ring(8)
        fake = make_topology("ring", 8)
        with pytest.raises(ValueError, match="push-sum"):
            compile_schedule(
                type(fake)("directed", topo.A, fake.neighbors))


# ---------------------------------------------------------------------------
# matching process
# ---------------------------------------------------------------------------

class TestMatchingProcess:
    @pytest.mark.parametrize("name", TOPOS)
    @pytest.mark.parametrize("sampler", ["uniform", "weighted"])
    def test_expected_matrix_equals_static_W(self, name, sampler):
        """Tentpole algebra: sum_r p_r W_r == W exactly (the rounds
        partition W's off-diagonal mass and scaling by 1/p_r cancels)."""
        topo = make_topology(name, 8)
        proc = MatchingProcess(compile_schedule(topo), sampler=sampler)
        np.testing.assert_allclose(proc.expected_matrix(), topo.W,
                                   atol=1e-12)

    @pytest.mark.parametrize("name", TOPOS)
    def test_branch_matrices_are_doubly_stochastic(self, name):
        proc = MatchingProcess(_sched(name))
        for M in proc.branch_matrices():
            np.testing.assert_allclose(M.sum(1), 1.0, atol=1e-12)
            np.testing.assert_allclose(M.sum(0), 1.0, atol=1e-12)
            assert M.min() >= -1e-12

    def test_empirical_round_frequencies_match_probs(self):
        proc = MatchingProcess(_sched("star"), sampler="weighted")
        key = jax.random.PRNGKey(0)
        idx = np.asarray([int(proc.round_index(jax.random.fold_in(key, i), 0))
                          for i in range(2000)])
        freq = np.bincount(idx, minlength=proc.n_rounds) / len(idx)
        np.testing.assert_allclose(freq, proc.probs, atol=0.05)

    def test_unknown_sampler_rejected(self):
        with pytest.raises(ValueError, match="sampler"):
            MatchingProcess(_sched("ring"), sampler="zipf")

    def test_single_node_schedule_rejected(self):
        topo = make_topology("ring", 1)
        with pytest.raises(ValueError, match="at least one round"):
            MatchingProcess(compile_schedule(topo))


class TestLinkFailureProcess:
    def test_drop_prob_validation(self):
        with pytest.raises(ValueError, match="drop_prob"):
            LinkFailureProcess(_sched("ring"), drop_prob=1.0)
        with pytest.raises(ValueError, match="drop_prob"):
            LinkFailureProcess(_sched("ring"), drop_prob=-0.1)

    @pytest.mark.parametrize("name", TOPOS)
    def test_sampled_matrix_row_stochastic_symmetric(self, name):
        topo = make_topology(name, 8)
        proc = LinkFailureProcess(compile_schedule(topo), drop_prob=0.4)
        for i in range(5):
            W = np.asarray(proc.sample_matrix(jax.random.PRNGKey(i), 0))
            np.testing.assert_allclose(W.sum(1), 1.0, atol=1e-6)
            np.testing.assert_allclose(W, W.T, atol=1e-6)
            assert W.min() >= -1e-6

    def test_p_zero_is_static_W(self):
        topo = make_topology("hypercube", 8)
        proc = LinkFailureProcess(compile_schedule(topo), drop_prob=0.0)
        W = np.asarray(proc.sample_matrix(jax.random.PRNGKey(0), 0))
        np.testing.assert_allclose(W, topo.W, atol=1e-6)

    def test_expected_matrix_interpolates_to_identity(self):
        topo = make_topology("ring", 8)
        p = 0.3
        proc = LinkFailureProcess(compile_schedule(topo), drop_prob=p)
        np.testing.assert_allclose(
            proc.expected_matrix(), (1 - p) * topo.W + p * np.eye(8),
            atol=1e-12)
        delta, beta = proc.expected_delta_beta()
        assert delta == pytest.approx((1 - p) * spectral_gap(topo.W),
                                      abs=1e-9)
        assert beta == pytest.approx((1 - p) * beta_norm(topo.W), abs=1e-9)

    def test_registry(self):
        sched = _sched("ring")
        assert make_topology_process("matching", sched).kind == "matching"
        assert make_topology_process(
            "linkfail", sched, edge_drop_prob=0.2).drop_prob == 0.2
        with pytest.raises(ValueError, match="unknown topology process"):
            make_topology_process("quantum", sched)


# ---------------------------------------------------------------------------
# seed reproducibility: the no-communication determinism contract
# ---------------------------------------------------------------------------

class TestSeedReproducibility:
    def test_round_index_pure_function_of_key(self):
        """Two independently-built identical processes, eager and jitted,
        sample the same round sequence — this is what lets every node (and
        every engine: packed / per-leaf / plain / simulator) agree on the
        sampled round with zero communication."""
        p1 = MatchingProcess(_sched("hypercube"))
        p2 = MatchingProcess(_sched("hypercube"))
        jit_idx = jax.jit(lambda k, t: p1.round_index(k, t),
                          static_argnums=1)
        key = jax.random.PRNGKey(42)
        for step in range(20):
            ek = jax.random.fold_in(key, step)
            for t in range(3):
                a = int(p1.round_index(ek, t))
                assert a == int(p2.round_index(ek, t))
                assert a == int(jit_idx(ek, t))

    def test_round_sequence_varies_over_steps(self):
        proc = MatchingProcess(_sched("hypercube"))
        key = jax.random.PRNGKey(0)
        idx = {int(proc.round_index(jax.random.fold_in(key, i), 0))
               for i in range(50)}
        assert len(idx) > 1, "sampler is stuck on one round"

    def test_edge_mask_deterministic_and_salted(self):
        proc = LinkFailureProcess(_sched("torus"), drop_prob=0.5)
        key = jax.random.PRNGKey(7)
        m1 = np.asarray(proc.edge_mask(key, 0))
        m2 = np.asarray(proc.edge_mask(key, 0))
        np.testing.assert_array_equal(m1, m2)
        # the in-step round index t enters the fold salt: with 12+ edges at
        # p = 0.5 a colliding draw has probability 2^-12
        masks = np.stack([np.asarray(proc.edge_mask(key, t))
                          for t in range(4)])
        assert (masks != masks[0]).any()

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), t=st.integers(0, 7))
    def test_sampling_reproducible_property(self, seed, t):
        proc = MatchingProcess(_sched("star"))
        lf = LinkFailureProcess(_sched("star"), drop_prob=0.3)
        key = jax.random.PRNGKey(seed)
        assert int(proc.round_index(key, t)) == int(proc.round_index(key, t))
        np.testing.assert_array_equal(np.asarray(lf.edge_mask(key, t)),
                                      np.asarray(lf.edge_mask(key, t)))
        # the sample fold is salted away from the raw key stream
        assert SAMPLE_SALT > 0


# ---------------------------------------------------------------------------
# matrix-simulator convergence (the sound replica algorithm)
# ---------------------------------------------------------------------------

class TestProcessSimulator:
    # 250 sampled rounds x 8 graph/process combos ~= 2.5 min: slow tier
    # (fast-tier mixing signal stays via test_matching_beats_nothing_baseline)
    @pytest.mark.slow
    @pytest.mark.parametrize("name", ["ring", "hypercube", "star", "torus"])
    @pytest.mark.parametrize("kind", ["matching", "linkfail"])
    def test_consensus_converges(self, name, kind, key):
        proc = make_topology_process(kind, _sched(name),
                                     edge_drop_prob=0.3)
        x0 = jax.random.normal(key, (8, 32))
        gamma = 0.4 if kind == "matching" else 0.3
        _, errs = run_choco_gossip_process(x0, proc, gamma, TopK(k=8), 250)
        assert float(errs[-1]) < 1e-4 * float(errs[0]), (
            f"{name}/{kind}: {float(errs[0])} -> {float(errs[-1])}")

    def test_average_preserved_exactly(self, key):
        """Every sampled update moves mass along doubly-stochastic rows:
        the node average is invariant step by step."""
        proc = MatchingProcess(_sched("hypercube"))
        x0 = jax.random.normal(key, (8, 16))
        xbar0 = np.asarray(jnp.mean(x0, 0))
        st = init_process_state(x0, proc)
        for i in range(40):
            st = choco_process_round(st, proc, 0.4, TopK(k=4),
                                     jax.random.PRNGKey(i))
        np.testing.assert_allclose(np.asarray(jnp.mean(st.x, 0)), xbar0,
                                   atol=1e-5)

    def test_matching_beats_nothing_baseline(self, key):
        """Sanity: sampling one round per step still mixes (vs zero rounds)."""
        proc = MatchingProcess(_sched("ring"))
        x0 = jax.random.normal(key, (8, 32))
        _, errs = run_choco_gossip_process(x0, proc, 0.4, Identity(), 150)
        assert float(errs[-1]) < 0.05 * float(errs[0])

    # 150 sampled Algorithm-4 rounds per kind ~= 18s: slow tier
    @pytest.mark.slow
    @pytest.mark.parametrize("kind", ["matching", "linkfail"])
    def test_blackbox_averaging_scheme_contracts(self, kind, key):
        """Algorithm-4 composition point (core/consensus.py): the stochastic
        process plugs in as an AveragingScheme whose auxiliary Y carries the
        reference state; average preserved, consensus contracts."""
        from repro.core import stochastic_choco_averaging
        proc = make_topology_process(kind, _sched("hypercube"),
                                     edge_drop_prob=0.2)
        sch = stochastic_choco_averaging(proc, TopK(k=8), 32, gamma=0.35)
        assert 0.0 < sch.p < 1.0
        x0 = jax.random.normal(key, (8, 32))
        xbar = np.asarray(jnp.mean(x0, 0))
        X, Y = x0, init_process_state(x0, proc).refs
        for i in range(150):
            X, Y = sch.h(X, Y, jax.random.PRNGKey(i))
        np.testing.assert_allclose(np.asarray(jnp.mean(X, 0)), xbar,
                                   atol=1e-5)
        err = float(jnp.mean(jnp.sum((X - xbar) ** 2, -1)))
        assert err < 1e-5


# ---------------------------------------------------------------------------
# push-sum simulator
# ---------------------------------------------------------------------------

class TestPushSum:
    @pytest.mark.parametrize("topo_fn,gamma", [
        (lambda: directed_ring(8), 0.5),
        (lambda: random_digraph(8, 0.4, seed=1), 0.5),
    ])
    def test_compressed_pushsum_converges_to_average(self, topo_fn, gamma,
                                                     key):
        topo = topo_fn()
        x0 = jax.random.normal(key, (8, 32))
        A = jnp.asarray(topo.A)
        final, errs = run_pushsum_gossip(x0, A, gamma, TopK(k=16), 400)
        assert float(errs[-1]) < 1e-6, float(errs[-1])
        # weight mass is conserved: 1^T w = n exactly (column-stochastic A)
        assert float(jnp.sum(final.w)) == pytest.approx(8.0, abs=1e-4)

    def test_identity_compressor_is_lazy_pushsum(self, key):
        """With Q = identity the recursion collapses to
        x' = ((1-g) I + g A) x — verify against the closed form."""
        topo = random_digraph(8, 0.5, seed=3)
        A = jnp.asarray(topo.A)
        g = 0.7
        x0 = jax.random.normal(key, (8, 8))
        st = init_pushsum_state(x0)
        x_ref, w_ref = x0, jnp.ones((8, 1))
        M = (1 - g) * jnp.eye(8) + g * A
        for _ in range(20):
            st = pushsum_gossip_round(st, A, g, Identity())
            x_ref, w_ref = M @ x_ref, M @ w_ref
        np.testing.assert_allclose(np.asarray(st.x), np.asarray(x_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(st.w), np.asarray(w_ref),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(pushsum_debias(st)),
            np.asarray(st.x / st.w), atol=0)

    def test_plain_averaging_never_reaches_consensus_on_digraph(self, key):
        """The fail-fast rationale: feeding a column-stochastic A to the
        symmetric averaging x' = A x converges to the Perron direction
        pi * (1^T x0) — nodes NEVER agree (unless pi is uniform), which is
        exactly the bias the push-sum weight column corrects."""
        topo = random_digraph(8, 0.4, seed=5)
        A = jnp.asarray(topo.A)
        x = jax.random.normal(key, (8, 4))
        for _ in range(300):
            x = A @ x
        spread = float(jnp.max(jnp.abs(x - jnp.mean(x, 0, keepdims=True))))
        assert spread > 1e-2          # stuck on the non-uniform Perron vector
        # push-sum on the SAME graph does reach the true average
        _, errs = run_pushsum_gossip(jax.random.normal(key, (8, 4)),
                                     A, 0.5, Identity(), 300)
        assert float(errs[-1]) < 1e-8


# ---------------------------------------------------------------------------
# trainer / CLI fail-fast
# ---------------------------------------------------------------------------

class TestFailFast:
    def _trainer(self, **kw):
        from repro.configs.base import ChocoConfig, get_config
        from repro.models import build_model
        from repro.optim import constant_schedule, sgd
        from repro.train.trainer import DecentralizedTrainer
        mesh = make_mesh((1, 1), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        mode = kw.pop("mode", "choco")
        return DecentralizedTrainer(
            model=build_model(cfg), choco=ChocoConfig(**kw), mesh=mesh,
            n_nodes=1, optimizer=sgd(), lr_fn=constant_schedule(0.1),
            mode=mode)

    def test_directed_topology_needs_pushsum(self):
        with pytest.raises(ValueError, match="push-sum"):
            self._trainer(topology="directed_ring", mode="choco")
        with pytest.raises(ValueError, match="push-sum"):
            self._trainer(topology="random_digraph", mode="plain")

    def test_process_with_time_varying_sequence_rejected(self):
        with pytest.raises(ValueError, match="ambiguous"):
            self._trainer(topology="ring,hypercube",
                          topology_process="matching", gossip_steps=2)

    def test_process_with_directed_rejected(self):
        with pytest.raises(ValueError, match="push-sum|directed"):
            self._trainer(topology="directed_ring",
                          topology_process="matching", mode="pushsum")

    def test_process_with_allreduce_rejected(self):
        with pytest.raises(ValueError, match="allreduce|gossip graph"):
            self._trainer(topology="ring", topology_process="linkfail",
                          mode="allreduce")

    @pytest.mark.parametrize("argv,msg", [
        (["--topology", "directed_ring"], "pushsum"),
        (["--mode", "pushsum", "--topology", "ring,hypercube",
          "--gossip-steps", "2"], "time-varying"),
        (["--mode", "pushsum", "--topology", "directed_ring",
          "--topology-process", "matching"], "topology-process"),
        (["--mode", "pushsum", "--topology", "directed_ring",
          "--gossip-engine", "per-leaf"], "packed"),
        (["--topology-process", "matching", "--topology", "ring,torus",
          "--gossip-steps", "2"], "ambiguous"),
        (["--edge-drop-prob", "0.3"], "linkfail"),
        (["--topology-process", "linkfail", "--edge-drop-prob", "1.5"],
         "0, 1"),
        (["--matching-sampler", "weighted"], "matching"),
        (["--keep-checkpoints", "0", "--checkpoint-dir", "/tmp/x"], ">= 1"),
        (["--keep-checkpoints", "2"], "checkpoint-dir"),
    ])
    def test_cli_fail_fast(self, argv, msg, capsys):
        """launch/train.py rejects bad combinations before importing jax /
        touching devices (argparse.error -> SystemExit(2))."""
        from repro.launch.train import main
        with pytest.raises(SystemExit) as ei:
            main(["--arch", "qwen3-1.7b", "--smoke"] + argv)
        assert ei.value.code == 2
        assert msg.split("|")[0] in capsys.readouterr().err


# ---------------------------------------------------------------------------
# distributed equivalence (slow tier — 8 simulated host devices)
# ---------------------------------------------------------------------------

from test_distributed import run_sub  # noqa: E402  (shared subprocess runner)


@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("topology", ["ring", "hypercube", "star"])
@pytest.mark.parametrize("kind", ["matching", "linkfail"])
def test_distributed_process_engine_matches_simulator(topology, kind):
    """Acceptance: the replica-based process engine (packed AND per-leaf)
    reproduces the matrix simulator per step given the same seed — the
    sampled round / edge mask is drawn identically on every node from the
    shared exchange key, with zero communication."""
    run_sub(f"""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.comm.stochastic import (make_topology_process,
                                           choco_process_round,
                                           init_process_state)
        from repro.core import make_topology, TopK

        n, d = 8, 96
        topo = make_topology("{topology}", n)
        sched = compile_schedule(topo)
        proc = make_topology_process("{kind}", sched, edge_drop_prob=0.3)
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(k=9)            # deterministic: no RNG divergence
        gamma = 0.3
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        R = sched.n_rounds

        st = init_process_state(x0, proc)
        for i in range(6):
            st = choco_process_round(st, proc, gamma, comp,
                                     jax.random.PRNGKey(i))

        for packed in (True, False):
            ex = jax.jit(make_gossip_exchange(
                mode="choco", mesh=mesh, state_specs={{"w": P("data", None)}},
                axis="data", compressor=comp, gamma=gamma, packed=packed,
                process=proc))
            x = {{"w": x0}}
            if proc.kind == "matching":
                xh = [{{"w": jnp.zeros_like(x0)}} for _ in range(R)]
            else:
                xh = {{"w": jnp.zeros_like(x0)}}
            s = [{{"w": jnp.zeros_like(x0)}} for _ in range(R)]
            for i in range(6):
                x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
            np.testing.assert_allclose(np.asarray(x["w"]), np.asarray(st.x),
                                       rtol=1e-4, atol=1e-5)
        print("PROCESS ENGINE == SIMULATOR")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_distributed_plain_matching_is_exact_sampled_gossip():
    """Plain engine + matching process: x' = W_t x with the sampled branch
    matrix, bit-for-bit the same branch on every node."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.comm.stochastic import MatchingProcess
        from repro.core import make_topology

        n, d = 8, 32
        topo = make_topology("hypercube", n)
        proc = MatchingProcess(compile_schedule(topo))
        mesh = make_mesh((8, 1), ("data", "model"))
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        Ms = jnp.asarray(proc.branch_matrices())

        ex = make_gossip_exchange(mode="plain", mesh=mesh,
                                  state_specs=P("data", None), axis="data",
                                  process=proc)
        x, ref = x0, x0
        for i in range(8):
            k = jax.random.PRNGKey(i)
            x, _, _ = ex(k, x, x * 0, x * 0)
            ref = Ms[proc.round_index(k, 0)] @ ref
        np.testing.assert_allclose(np.asarray(x), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        print("PLAIN MATCHING OK")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_distributed_matching_single_permute_launch():
    """Flagship perf claim: a sampled-matching gossip round executes ONE
    round's permutes regardless of the schedule's round count.  In the
    compiled HLO every collective-permute lives inside a conditional branch
    computation (lax.switch — one branch executes per step) and the ENTRY
    computation carries zero unconditional permutes, so there is no fan-out
    of the full 7-round fully-connected schedule."""
    run_sub("""
        import re
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.comm.stochastic import MatchingProcess
        from repro.core import make_topology, TopK

        n = 8
        topo = make_topology("fully_connected", n)   # 7 static rounds
        sched = compile_schedule(topo)
        proc = MatchingProcess(sched)
        mesh = make_mesh((8, 1), ("data", "model"))
        ex = make_gossip_exchange(
            mode="choco", mesh=mesh, state_specs=P("data", None),
            axis="data", compressor=TopK(k=16), gamma=0.3, process=proc)
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, 256))
        xh = [jnp.zeros_like(x0) for _ in range(sched.n_rounds)]
        s = [jnp.zeros_like(x0) for _ in range(sched.n_rounds)]
        lowered = jax.jit(ex).lower(jax.random.PRNGKey(0), x0, xh, s)
        hlo = lowered.compile().as_text()

        # split the HLO module into computations; permutes must live ONLY
        # in (conditional branch) sub-computations, never in ENTRY
        comps, cur = {}, None
        for line in hlo.splitlines():
            m = re.match(r"^(ENTRY )?%?([\\w.\\-]+)\\s*\\(", line)
            if m and line.rstrip().endswith("{"):
                cur = (("ENTRY " if m.group(1) else "") + m.group(2))
                comps[cur] = []
            elif cur is not None:
                comps[cur].append(line)
        is_permute = lambda l: ("collective-permute" in l
                                and "-done" not in l)
        entry = next(k for k in comps if k.startswith("ENTRY"))
        entry_permutes = sum(is_permute(l) for l in comps[entry])
        entry_conds = sum("conditional" in l for l in comps[entry])
        branch_counts = [sum(is_permute(l) for l in v)
                         for k, v in comps.items()
                         if k != entry and sum(is_permute(l) for l in v)]
        assert entry_permutes == 0, entry_permutes
        assert entry_conds >= 1, "matching must lower to lax.switch"
        assert len(branch_counts) == sched.n_rounds, branch_counts
        # one round's payload per branch: a small constant (vals + idx
        # permutes, possibly split by SPMD), NOT the whole schedule
        assert max(branch_counts) <= 4, branch_counts
        print("SINGLE-LAUNCH OK entry=0 branches:", branch_counts)
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_distributed_pushsum_directed_ring_e2e():
    """Acceptance: compressed push-sum on a directed ring over an 8-device
    simulated mesh converges to the TRUE average (de-biased x/w) and matches
    the matrix simulator."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.pushsum import debias
        from repro.comm.schedule import compile_directed_schedule
        from repro.core import directed_ring, TopK
        from repro.core.choco_gossip import (init_pushsum_state,
                                             pushsum_gossip_round)

        n, d = 8, 96
        topo = directed_ring(n)
        sched = compile_directed_schedule(topo)
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(k=24)
        gamma = 0.5
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        xbar = np.asarray(jnp.mean(x0, 0))

        st = init_pushsum_state(x0)
        A = jnp.asarray(topo.A)
        for i in range(6):
            st = pushsum_gossip_round(st, A, gamma, comp)

        ex = jax.jit(make_gossip_exchange(
            mode="pushsum", mesh=mesh, state_specs={"p": P("data", None)},
            axis="data", compressor=comp, gamma=gamma,
            schedules=(sched,), weight_specs=P("data", None)))
        x = {"p": x0}
        xh = {"p": jnp.zeros_like(x0)}
        s = {"p": jnp.zeros_like(x0)}
        w = jnp.ones((n, 1))
        for i in range(6):
            x, xh, s, w = ex(jax.random.PRNGKey(i), x, xh, s, w)
        np.testing.assert_allclose(np.asarray(x["p"]), np.asarray(st.x),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(w), np.asarray(st.w),
                                   rtol=1e-4, atol=1e-5)

        # run to convergence: de-biased estimate hits the true average
        # (directed ring delta = 0.076 — the slow-mixing worst case; the
        # initial consensus error is ~d = 96, so 1e-4 is a 6-decade drop)
        for i in range(6, 300):
            x, xh, s, w = ex(jax.random.PRNGKey(i), x, xh, s, w)
        z = np.asarray(debias(x, w)["p"])
        err = np.mean(np.sum((z - xbar) ** 2, axis=-1))
        assert err < 1e-4, err
        # mass conservation on the wire: 1^T w == n
        np.testing.assert_allclose(float(jnp.sum(w)), n, atol=1e-3)
        print("PUSHSUM E2E OK", err)
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_trainer_process_and_pushsum_e2e():
    """Trainer end-to-end: matching + linkfail processes and push-sum mode
    all train with finite decreasing loss on an 8-device mesh."""
    run_sub("""
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((8, 1), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        nb = make_lm_batch_fn(cfg, 32, 2, 8)

        cases = [
            ("choco", ChocoConfig(compressor="top_k",
                                  comp_kwargs=(("fraction", 0.05),),
                                  topology="hypercube",
                                  topology_process="matching")),
            ("choco", ChocoConfig(compressor="top_k",
                                  comp_kwargs=(("fraction", 0.05),),
                                  topology="ring",
                                  topology_process="linkfail",
                                  edge_drop_prob=0.25)),
            ("pushsum", ChocoConfig(compressor="top_k",
                                    comp_kwargs=(("fraction", 0.05),),
                                    topology="directed_ring",
                                    consensus_gamma=0.4)),
            # plain + process: no replicas — x_hat/s stay single trees
            ("plain", ChocoConfig(topology="hypercube",
                                  topology_process="matching")),
        ]
        for mode, choco in cases:
            tr = DecentralizedTrainer(model=m, choco=choco, mesh=mesh,
                                      n_nodes=8, optimizer=sgd(),
                                      lr_fn=constant_schedule(0.05), mode=mode)
            state = tr.init_state(jax.random.PRNGKey(0))
            b = jax.tree.map(jnp.asarray, nb())
            step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                        jax.eval_shape(lambda: b))
            losses = []
            for i in range(10):
                state, mets = step(state, jax.tree.map(jnp.asarray, nb()))
                losses.append(float(mets["loss"]))
            assert all(np.isfinite(losses)), (mode, losses)
            assert losses[-1] < losses[0], (mode, losses)
            print(mode, choco.topology_process or choco.topology,
                  "LOSS", losses[0], "->", losses[-1])
        print("TRAINER PROCESS/PUSHSUM OK")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_static_paths_unchanged_regression():
    """PR 2 bit-match guarantee: building an exchange WITHOUT a process (the
    static path) takes the exact pre-existing code path — verified by the
    engine==legacy tests in test_distributed.py; here we additionally pin
    that a process=None exchange and a drop_prob=0 linkfail exchange agree
    on the final consensus point (same algorithm family, same fixed W)."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.comm.stochastic import LinkFailureProcess
        from repro.core import make_topology, TopK

        n, d = 8, 64
        topo = make_topology("hypercube", n)
        sched = compile_schedule(topo)
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(k=16)
        gamma = 0.3
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        xbar = np.asarray(jnp.mean(x0, 0))

        # static engine
        ex0 = jax.jit(make_gossip_exchange(mode="choco", mesh=mesh,
                                           state_specs=P("data", None),
                                           axis="data", compressor=comp,
                                           gamma=gamma, schedules=(sched,)))
        x, xh, s = x0, jnp.zeros_like(x0), jnp.zeros_like(x0)
        for i in range(120):
            x, xh, s = ex0(jax.random.PRNGKey(i), x, xh, s)
        err_static = np.mean(np.sum((np.asarray(x) - xbar) ** 2, -1))

        # p=0 linkfail: every round always live, same fixed W
        proc = LinkFailureProcess(sched, drop_prob=0.0)
        ex1 = jax.jit(make_gossip_exchange(mode="choco", mesh=mesh,
                                           state_specs=P("data", None),
                                           axis="data", compressor=comp,
                                           gamma=gamma, process=proc))
        x = x0
        xh = jnp.zeros_like(x0)
        s = [jnp.zeros_like(x0) for _ in range(sched.n_rounds)]
        for i in range(120):
            x, xh, s = ex1(jax.random.PRNGKey(i), x, xh, s)
        err_p0 = np.mean(np.sum((np.asarray(x) - xbar) ** 2, -1))

        assert err_static < 1e-6 and err_p0 < 1e-6, (err_static, err_p0)
        print("STATIC/P0 OK", err_static, err_p0)
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_checkpoint_restore_across_process_change():
    """A checkpoint saved WITHOUT a topology process restores into a
    matching-process trainer via the elastic re-mix path: params/opt are
    read back exactly, the re-shaped x_hat/s reference lists are zero-filled
    (structural drift under reset prefixes is not a mismatch), and the
    consensus warmup re-seeds them under the process engine."""
    run_sub("""
        import tempfile, os
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((8, 1), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        nb = make_lm_batch_fn(cfg, 32, 2, 8)

        def trainer(proc):
            return DecentralizedTrainer(
                model=m, choco=ChocoConfig(
                    compressor="top_k", comp_kwargs=(("fraction", 0.05),),
                    topology="hypercube", topology_process=proc),
                mesh=mesh, n_nodes=8, optimizer=sgd(),
                lr_fn=constant_schedule(0.05))

        t0 = trainer(None)
        state = t0.init_state(jax.random.PRNGKey(0))
        b = jax.tree.map(jnp.asarray, nb())
        step = t0.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: b))
        for i in range(3):
            state, _ = step(state, jax.tree.map(jnp.asarray, nb()))
        d = os.path.join(tempfile.mkdtemp(), "step3")
        t0.save_checkpoint(d, state)

        t1 = trainer("matching")
        restored, man, warmup = t1.restore_checkpoint(d)
        assert warmup > 0, "process change must take the re-mix path"
        # params read back exactly
        p_old = jax.tree.leaves(state.params)[0]
        p_new = jax.tree.leaves(restored.params)[0]
        np.testing.assert_array_equal(np.asarray(p_old), np.asarray(p_new))
        # re-shaped reference lists start zeroed
        assert isinstance(restored.x_hat, list)
        for tree in restored.x_hat:
            for leaf in jax.tree.leaves(tree):
                assert float(jnp.sum(jnp.abs(leaf))) == 0.0
        restored = t1.consensus_warmup(restored, warmup)
        # warmup engaged the process engine: refs are no longer all-zero
        total = sum(float(jnp.sum(jnp.abs(l)))
                    for tree in restored.x_hat
                    for l in jax.tree.leaves(tree))
        assert total > 0
        # and training continues
        step1 = t1.jitted_train_step(jax.eval_shape(lambda: restored),
                                     jax.eval_shape(lambda: b))
        for i in range(2):
            restored, mets = step1(restored, jax.tree.map(jnp.asarray, nb()))
        assert np.isfinite(float(mets["loss"]))
        print("PROCESS-CHANGE RESTORE OK")
    """)
