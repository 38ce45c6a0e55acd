"""Bounded-staleness async gossip (comm/async_gossip.py + the
core/choco_gossip.py delay-expanded simulator).

Fast tier: StalenessProcess construction + expected-mixing algebra + seed
determinism + simulator convergence/average-preservation + fail-fast wiring.
The distributed engine == simulator equivalence, the HLO permute-launch
audit against the link-failure baseline, and the trainer/CLI e2e live at the
bottom under the standard ``slow``/``distributed`` markers (subprocess with
8 simulated host devices), so the fast inner loop (-m "not slow") never
compiles shard_map graphs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.topology import make_topology, spectral_gap
from repro.core.compression import Identity, TopK
from repro.core.choco_gossip import (choco_gossip_round_efficient,
                                     choco_stale_round, init_efficient_state,
                                     init_stale_state, run_choco_stale_gossip)
from repro.comm.schedule import compile_schedule
from repro.comm.async_gossip import StalenessProcess
from repro.comm.stochastic import (LinkFailureProcess, choco_process_round,
                                   init_process_state, make_topology_process)
from repro.launch.mesh import make_mesh

from optional_hypothesis import HAVE_HYPOTHESIS, given, settings, st

TOPOS = ["ring", "hypercube", "star", "chain", "torus", "fully_connected"]


def _sched(name, n=8):
    return compile_schedule(make_topology(name, n))


def _proc(name="ring", tau=2, n=8, **kw):
    return StalenessProcess(_sched(name, n), max_staleness=tau, **kw)


# ---------------------------------------------------------------------------
# construction + validation
# ---------------------------------------------------------------------------

class TestStalenessProcess:
    def test_registry(self):
        sched = _sched("ring")
        p = make_topology_process("staleness", sched, max_staleness=3)
        assert p.kind == "staleness" and p.max_staleness == 3

    def test_negative_tau_rejected(self):
        with pytest.raises(ValueError, match="max_staleness"):
            _proc(tau=-1)

    def test_single_node_schedule_rejected(self):
        with pytest.raises(ValueError, match="at least one round"):
            StalenessProcess(compile_schedule(make_topology("ring", 1)))

    def test_delay_probs_validation(self):
        with pytest.raises(ValueError, match="entries"):
            _proc(tau=2, delay_probs=(0.5, 0.5))          # needs tau+1 = 3
        with pytest.raises(ValueError, match="nonnegative"):
            _proc(tau=1, delay_probs=(1.5, -0.5))
        # unnormalized mass is normalized, not rejected
        p = _proc(tau=1, delay_probs=(3.0, 1.0))
        np.testing.assert_allclose(p.delay_probs, (0.75, 0.25))

    def test_delay_statistics(self):
        p = _proc(tau=2)                      # uniform over {0, 1, 2}
        assert p.mean_delay == pytest.approx(1.0)
        assert p.freshness == pytest.approx((1 + 1 / 2 + 1 / 3) / 3)
        p0 = _proc(tau=0)
        assert p0.mean_delay == 0.0 and p0.freshness == 1.0


# ---------------------------------------------------------------------------
# per-edge straggler links
# ---------------------------------------------------------------------------

class TestStragglerEdges:
    def test_unknown_edge_rejected(self):
        """Naming an edge outside the schedule's support is a ValueError
        (ring(8) has no chord 0-5)."""
        with pytest.raises(ValueError, match="unknown straggler edge 0-5"):
            _proc(straggler_edges=((0, 5),))

    def test_registry_passthrough(self):
        p = make_topology_process(
            "staleness", _sched("ring"), max_staleness=2,
            straggler_edges=((1, 0),),
            straggler_delay_probs=(0.0, 0.5, 0.5))
        # edge canonicalized to (min, max)
        assert p.straggler_edges == ((0, 1),)
        assert p.straggler_delay_probs == (0.0, 0.5, 0.5)

    def test_default_straggler_is_point_mass_at_tau(self):
        p = _proc(tau=2, straggler_edges=((0, 1),))
        assert p.straggler_delay_probs == (0.0, 0.0, 1.0)
        e = p._edges.index((0, 1))
        assert p.edge_freshness[e] == pytest.approx(1.0 / 3)

    def test_probs_without_edges_rejected(self):
        with pytest.raises(ValueError, match="without"):
            _proc(tau=1, straggler_delay_probs=(0.5, 0.5))

    def test_nonstraggler_draws_bit_identical_to_global(self):
        """The per-edge cumulative table shares one uniform draw per edge,
        so adding a straggler edge must not perturb any OTHER edge's delay
        sequence (and the straggler itself obeys its point mass)."""
        base = _proc("torus", tau=2)
        strag = _proc("torus", tau=2, straggler_edges=((0, 1),))
        e = strag._edges.index((0, 1))
        key = jax.random.PRNGKey(11)
        for t in range(6):
            d0 = np.asarray(base.edge_delays(key, t))
            d1 = np.asarray(strag.edge_delays(key, t))
            other = np.arange(len(d0)) != e
            np.testing.assert_array_equal(d0[other], d1[other])
            assert d1[e] == 2

    def test_expected_matrix_per_edge_algebra(self):
        """Straggler edges carry their own phi_e: the expected matrix keeps
        phi * w on every healthy edge, phi_s * w on the straggler, and
        stays symmetric row-stochastic (the undelivered remainder folds
        into BOTH endpoints' diagonals equally)."""
        topo = make_topology("ring", 8)
        p = StalenessProcess(compile_schedule(topo), max_staleness=2,
                             straggler_edges=((2, 3),))
        E = p.expected_matrix()
        phi = p.freshness
        phi_s = 1.0 / 3                      # point mass at tau = 2
        W = topo.W
        np.testing.assert_allclose(E.sum(axis=1), np.ones(8), atol=1e-12)
        np.testing.assert_allclose(E, E.T, atol=1e-12)
        assert E[2, 3] == pytest.approx(phi_s * W[2, 3])
        assert E[0, 1] == pytest.approx(phi * W[0, 1])
        # straggler slows consensus: eigengap strictly below the uniform one
        delta_s, _ = p.expected_delta_beta()
        delta_u, _ = _proc(tau=2).expected_delta_beta()
        assert delta_s < delta_u

    def test_straggler_average_preserved_in_simulator(self):
        """Both directions of the straggler link share its delay, so the
        pairwise-cancellation argument still holds: 1^T x is invariant
        under the extended simulator, step by step."""
        p = _proc("ring", tau=2, straggler_edges=((0, 1), (4, 5)),
                  straggler_delay_probs=(0.1, 0.1, 0.8))
        x0 = jnp.asarray(np.random.default_rng(0).standard_normal((8, 6)),
                         jnp.float32)
        state = init_stale_state(x0, p.max_staleness)
        key = jax.random.PRNGKey(3)
        for t in range(12):
            state = choco_stale_round(state, p, 0.4, TopK(k=2),
                                      jax.random.fold_in(key, t))
            np.testing.assert_allclose(np.asarray(state.x.mean(axis=0)),
                                       np.asarray(x0.mean(axis=0)),
                                       atol=1e-5)

    @pytest.mark.parametrize("stragglers", [None, ((0, 1),)])
    def test_theorem2_contraction_band_holds(self, stragglers):
        """Theorem-2 band under the distribution-aware constants: with
        gamma = theorem2_stepsize(delta_eff, beta_eff, omega_eff) the stale
        simulator's consensus error stays inside
        e_T <= e_0 * rate^T, rate = theorem2_rate(delta_eff, omega_eff) —
        with and without a straggler edge (the straggler's smaller
        delta/omega widen the band; the iterates must still respect it)."""
        from repro.core.choco_gossip import theorem2_rate, theorem2_stepsize
        p = _proc("hypercube", tau=2, straggler_edges=stragglers)
        comp = TopK(k=3)
        omega = p.effective_omega(comp.omega(6))
        delta, beta = p.expected_delta_beta()
        gamma = theorem2_stepsize(delta, beta, omega)
        rate = theorem2_rate(delta, omega)
        x0 = jnp.asarray(np.random.default_rng(1).standard_normal((8, 6)),
                         jnp.float32)
        _, errs = run_choco_stale_gossip(x0, p, gamma, comp, steps=300,
                                         key=jax.random.PRNGKey(5))
        errs = np.asarray(errs)
        bound = float(errs[0]) * rate ** np.arange(len(errs))
        assert (errs <= bound * 1.05).all(), (
            f"consensus error left the Theorem-2 band: "
            f"worst ratio {float((errs / bound).max())}")
        assert errs[-1] < errs[0]


# ---------------------------------------------------------------------------
# expected-mixing algebra (the Theorem-2 surrogate)
# ---------------------------------------------------------------------------

class TestExpectedMixing:
    @pytest.mark.parametrize("name", TOPOS)
    def test_expected_matrix_is_freshness_interpolation(self, name):
        """E_eff = phi W + (1 - phi) I with phi = E[1/(1+d)] — the same
        shape as linkfail's (1-p) W + p I, with phi standing in for the
        keep probability."""
        topo = make_topology(name, 8)
        p = StalenessProcess(compile_schedule(topo), max_staleness=2)
        phi = p.freshness
        np.testing.assert_allclose(
            p.expected_matrix(), phi * topo.W + (1 - phi) * np.eye(8),
            atol=1e-12)
        delta, _ = p.expected_delta_beta()
        assert delta == pytest.approx(phi * spectral_gap(topo.W), abs=1e-9)

    def test_tau_zero_is_static_W(self):
        topo = make_topology("hypercube", 8)
        p = StalenessProcess(compile_schedule(topo), max_staleness=0)
        np.testing.assert_allclose(p.expected_matrix(), topo.W, atol=1e-12)
        assert p.effective_omega(0.25) == 0.25

    def test_drop_is_the_staleness_limit(self):
        """Subsumption: a link that is ALWAYS maximally stale approaches
        the linkfail expected matrix as tau grows (phi -> 0 ~ p -> 1)."""
        sched = _sched("ring")
        delayed = StalenessProcess(
            sched, max_staleness=9,
            delay_probs=(0.0,) * 9 + (1.0,))          # d = 9 always
        lf = LinkFailureProcess(sched, drop_prob=0.9)  # keep prob 0.1
        np.testing.assert_allclose(delayed.expected_matrix(),
                                   lf.expected_matrix(), atol=1e-12)

    def test_effective_omega_is_distribution_aware(self):
        """omega_eff = omega * phi with phi = E[1/(1+d)]: the uniform
        tau=3 distribution keeps more of omega than the worst case, and a
        point mass at tau reproduces the historical omega / (1 + tau)."""
        phi = (1 + 1 / 2 + 1 / 3 + 1 / 4) / 4
        assert _proc(tau=3).effective_omega(0.4) == pytest.approx(0.4 * phi)
        point = StalenessProcess(_sched("ring"), max_staleness=3,
                                 delay_probs=(0.0, 0.0, 0.0, 1.0))
        assert point.effective_omega(0.4) == pytest.approx(0.4 / 4)

    def test_effective_omega_monotone_in_delay_mass(self):
        """Shifting probability mass toward larger delays can only shrink
        the Lyapunov constant: omega_eff is monotone decreasing as the
        delay distribution moves mass from d=0 to d=tau."""
        sched = _sched("ring")
        omegas = []
        for mass in (0.0, 0.25, 0.5, 0.75, 1.0):
            p = StalenessProcess(sched, max_staleness=2,
                                 delay_probs=(1.0 - mass, 0.0, mass))
            omegas.append(p.effective_omega(0.4))
        assert omegas == sorted(omegas, reverse=True)
        assert omegas[0] == pytest.approx(0.4)          # all-fresh
        assert omegas[-1] == pytest.approx(0.4 / 3)     # all at tau=2

    def test_straggler_edge_governs_effective_omega(self):
        """The slowest edge's phi_e bounds the accumulated-error path, so
        one straggler edge drags omega_eff to ITS freshness even when the
        global distribution is all-fresh."""
        sched = _sched("ring")
        p = StalenessProcess(sched, max_staleness=2,
                             delay_probs=(1.0, 0.0, 0.0),
                             straggler_edges=((0, 1),))
        assert p.freshness == pytest.approx(1.0)
        assert p.effective_omega(0.4) == pytest.approx(0.4 / 3)

    def test_sample_matrix_not_a_per_step_matrix(self):
        with pytest.raises(NotImplementedError, match="choco_stale_round"):
            _proc().sample_matrix(jax.random.PRNGKey(0), 0)


# ---------------------------------------------------------------------------
# seed reproducibility: the no-communication determinism contract
# ---------------------------------------------------------------------------

class TestSeedReproducibility:
    def test_edge_delays_pure_function_of_key(self):
        p1, p2 = _proc("hypercube"), _proc("hypercube")
        jit_d = jax.jit(lambda k, t: p1.edge_delays(k, t), static_argnums=1)
        key = jax.random.PRNGKey(42)
        for step in range(10):
            ek = jax.random.fold_in(key, step)
            a = np.asarray(p1.edge_delays(ek, 0))
            np.testing.assert_array_equal(a, np.asarray(p2.edge_delays(ek, 0)))
            np.testing.assert_array_equal(a, np.asarray(jit_d(ek, 0)))

    def test_delays_bounded_and_varying(self):
        p = _proc("torus", tau=3)
        key = jax.random.PRNGKey(7)
        draws = np.stack([np.asarray(p.edge_delays(key, t))
                          for t in range(8)])
        assert draws.min() >= 0 and draws.max() <= 3
        assert (draws != draws[0]).any(), "delay sampler is stuck"

    def test_both_directions_share_the_edge_delay(self):
        """Average preservation needs d_ij == d_ji: the per-round delay a
        destination sees must agree with what the reverse direction's
        destination sees, via the canonical undirected edge id."""
        p = _proc("ring", tau=4, n=8)
        dvecs = [np.asarray(v) for v in
                 p.round_delay_vecs(jax.random.PRNGKey(3), 0)]
        for r, ids in enumerate(p.round_edge_ids):
            for dst, e in enumerate(ids):
                if e < 0:
                    continue
                for r2, ids2 in enumerate(p.round_edge_ids):
                    for dst2, e2 in enumerate(ids2):
                        if e2 == e:
                            assert dvecs[r][dst] == dvecs[r2][dst2]

    def test_empirical_delay_frequencies_match_probs(self):
        probs = (0.5, 0.3, 0.2)
        p = _proc("ring", tau=2, delay_probs=probs)
        key = jax.random.PRNGKey(0)
        draws = np.concatenate([np.asarray(p.edge_delays(key, t))
                                for t in range(400)])
        freq = np.bincount(draws, minlength=3) / len(draws)
        np.testing.assert_allclose(freq, probs, atol=0.05)

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), t=st.integers(0, 7))
    def test_sampling_reproducible_property(self, seed, t):
        p = _proc("star", tau=2)
        key = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(np.asarray(p.edge_delays(key, t)),
                                      np.asarray(p.edge_delays(key, t)))


# ---------------------------------------------------------------------------
# matrix simulator (core/choco_gossip.py)
# ---------------------------------------------------------------------------

class TestStaleSimulator:
    # 250 delay-expanded rounds x 8 graph/tau combos ~= 50s: slow tier
    # (fast-tier stale signal stays via test_average_preserved_exactly)
    @pytest.mark.slow
    @pytest.mark.parametrize("name", ["ring", "hypercube", "star", "torus"])
    @pytest.mark.parametrize("tau", [1, 2])
    def test_consensus_converges(self, name, tau, key):
        proc = _proc(name, tau=tau)
        x0 = jax.random.normal(key, (8, 32))
        _, errs = run_choco_stale_gossip(x0, proc, 0.25, TopK(k=8), 250)
        assert float(errs[-1]) < 1e-4 * float(errs[0]), (
            f"{name}/tau={tau}: {float(errs[0])} -> {float(errs[-1])}")

    def test_average_preserved_exactly(self, key):
        """The pairwise stale exchange moves mass symmetrically at a SHARED
        per-edge lag, so the node average is invariant step by step."""
        proc = _proc("hypercube", tau=3)
        x0 = jax.random.normal(key, (8, 16))
        xbar0 = np.asarray(jnp.mean(x0, 0))
        st = init_stale_state(x0, 3)
        for i in range(40):
            st = choco_stale_round(st, proc, 0.3, TopK(k=4),
                                   jax.random.PRNGKey(i))
        np.testing.assert_allclose(np.asarray(jnp.mean(st.x, 0)), xbar0,
                                   atol=1e-5)

    def test_tau_zero_equals_linkfail_p0(self, key):
        """tau = 0 forces every edge fresh: the stale round must reproduce
        the link-failure replica round at p = 0 (the same always-fresh
        Algorithm-2 form) step for step."""
        sched = _sched("ring")
        sp = StalenessProcess(sched, max_staleness=0)
        lf = LinkFailureProcess(sched, drop_prob=0.0)
        x0 = jax.random.normal(key, (8, 24))
        a = init_stale_state(x0, 0)
        b = init_process_state(x0, lf)
        comp = TopK(k=6)
        for i in range(6):
            k = jax.random.PRNGKey(i)
            a = choco_stale_round(a, sp, 0.3, comp, k)
            b = choco_process_round(b, lf, 0.3, comp, k)
            np.testing.assert_allclose(np.asarray(a.x), np.asarray(b.x),
                                       rtol=1e-6, atol=1e-7)

    def test_tau_zero_equals_static_efficient(self, key):
        """...and therefore also Algorithm 5 on the static W: with every
        copy fresh, sum_r v_r (x_hat_src - x_hat_i) == ((W - I) x_hat)_i."""
        topo = make_topology("hypercube", 8)
        sp = StalenessProcess(compile_schedule(topo), max_staleness=0)
        x0 = jax.random.normal(key, (8, 24))
        W = jnp.asarray(topo.W)
        a = init_stale_state(x0, 0)
        b = init_efficient_state(x0)
        comp = TopK(k=6)
        for i in range(5):
            a = choco_stale_round(a, sp, 0.3, comp, jax.random.PRNGKey(i))
            b = choco_gossip_round_efficient(b, W, 0.3, comp)
            np.testing.assert_allclose(np.asarray(a.x), np.asarray(b.x),
                                       rtol=1e-5, atol=1e-6)

    def test_exact_compressor_still_converges_under_staleness(self, key):
        proc = _proc("ring", tau=4)
        x0 = jax.random.normal(key, (8, 32))
        _, errs = run_choco_stale_gossip(x0, proc, 0.3, Identity(), 200)
        assert float(errs[-1]) < 1e-6 * float(errs[0])


# ---------------------------------------------------------------------------
# trainer / CLI fail-fast + gamma folding
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

    def test_staleness_with_plain_rejected(self):
        with pytest.raises(ValueError, match="choco engine"):
            self._trainer(topology="ring", topology_process="staleness",
                          mode="plain")

    def test_exchange_level_rejection(self):
        """make_gossip_exchange itself guards the plain engine (library
        users bypassing the trainer hit the same wall)."""
        from repro.comm.gossip import make_gossip_exchange
        mesh = make_mesh((1, 1), ("data", "model"))
        with pytest.raises(ValueError, match="choco engine"):
            make_gossip_exchange(mode="plain", mesh=mesh, state_specs=None,
                                 axis="data", process=_proc("ring", n=8))

    def test_gamma_shrinks_with_staleness_bound(self):
        """Theorem-2 gamma must fold both the delay-averaged eigengap and
        the omega/(1+tau) staleness bound: larger tau -> smaller gamma
        (exactly the composition the trainer runs — the trainer-level twin
        is asserted in the distributed e2e below)."""
        from repro.core.choco_gossip import theorem2_stepsize
        omega = 0.25

        def gamma(tau):
            p = _proc("ring", tau=tau)
            delta, beta = p.expected_delta_beta()
            return theorem2_stepsize(delta, beta, p.effective_omega(omega))

        gammas = [gamma(tau) for tau in (0, 1, 3)]
        assert gammas[0] > gammas[1] > gammas[2] > 0.0

    @pytest.mark.parametrize("argv,msg", [
        (["--topology-process", "staleness", "--mode", "plain"], "choco"),
        (["--topology-process", "staleness", "--mode", "allreduce"],
         "allreduce"),
        (["--mode", "pushsum", "--topology", "directed_ring",
          "--topology-process", "staleness"], "topology-process"),
        (["--max-staleness", "2"], "staleness"),
        (["--topology-process", "staleness", "--max-staleness", "-1"],
         ">= 0"),
        (["--topology-process", "staleness", "--topology", "ring,torus",
          "--gossip-steps", "2"], "ambiguous"),
    ])
    def test_cli_fail_fast(self, argv, msg, capsys):
        """launch/train.py rejects bad async combinations before importing
        jax / touching devices (argparse.error -> SystemExit(2))."""
        from repro.launch.train import main
        with pytest.raises(SystemExit) as ei:
            main(["--arch", "qwen3-1.7b", "--smoke"] + argv)
        assert ei.value.code == 2
        assert msg in capsys.readouterr().err


# ---------------------------------------------------------------------------
# distributed equivalence + HLO audit (slow tier — 8 simulated host devices)
# ---------------------------------------------------------------------------

from test_distributed import run_sub  # noqa: E402  (shared subprocess runner)


@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("topology", ["ring", "star"])
@pytest.mark.parametrize("tau", [1, 2])
def test_distributed_async_engine_matches_simulator(topology, tau):
    """Acceptance: the bounded-staleness engine (packed AND per-leaf)
    reproduces the delay-expanded matrix simulator per step given the same
    seed — per-edge delays are drawn identically on every node from the
    shared exchange key, with zero coordination bytes."""
    run_sub(f"""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.comm.async_gossip import StalenessProcess
        from repro.core import make_topology, TopK
        from repro.core.choco_gossip import (choco_stale_round,
                                             init_stale_state)

        n, d, tau = 8, 96, {tau}
        topo = make_topology("{topology}", n)
        sched = compile_schedule(topo)
        proc = StalenessProcess(sched, max_staleness=tau)
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(k=9)            # deterministic: no RNG divergence
        gamma = 0.3
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        R = sched.n_rounds

        st = init_stale_state(x0, tau)
        for i in range(6):
            st = choco_stale_round(st, proc, gamma, comp,
                                   jax.random.PRNGKey(i))

        for packed in (True, False):
            ex = jax.jit(make_gossip_exchange(
                mode="choco", mesh=mesh, state_specs={{"w": P("data", None)}},
                axis="data", compressor=comp, gamma=gamma, packed=packed,
                process=proc))
            x = {{"w": x0}}
            xh = [{{"w": jnp.zeros_like(x0)}} for _ in range(1 + tau)]
            s = [{{"w": jnp.zeros_like(x0)}} for _ in range(R * (1 + tau))]
            for i in range(6):
                x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
            np.testing.assert_allclose(np.asarray(x["w"]), np.asarray(st.x),
                                       rtol=1e-4, atol=1e-5)
        print("ASYNC ENGINE == SIMULATOR")
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_async_permute_count_equals_linkfail():
    """Acceptance: staleness adds ZERO permute launches over the linkfail
    baseline — every compiled round ships every step either way, and the
    arrived-vs-stale selection is pure where-mask arithmetic over the ring
    slots (no control flow, no extra collectives)."""
    run_sub("""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.comm.async_gossip import StalenessProcess
        from repro.comm.stochastic import LinkFailureProcess
        from repro.core import make_topology, TopK
        from repro.analysis.hlo_audit import count_permute_launches

        def permutes(ex, *args):
            hlo = jax.jit(ex).lower(*args).compile().as_text()
            return count_permute_launches(hlo)

        n, d = 8, 256
        sched = compile_schedule(make_topology("ring", n))
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(k=16)
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        R = sched.n_rounds
        k = jax.random.PRNGKey(0)

        lf = LinkFailureProcess(sched, drop_prob=0.1)
        ex_lf = make_gossip_exchange(
            mode="choco", mesh=mesh, state_specs=P("data", None),
            axis="data", compressor=comp, gamma=0.3, process=lf)
        n_lf = permutes(ex_lf, k, x0, jnp.zeros_like(x0),
                        [jnp.zeros_like(x0) for _ in range(R)])

        tau = 2
        sp = StalenessProcess(sched, max_staleness=tau)
        ex_as = make_gossip_exchange(
            mode="choco", mesh=mesh, state_specs=P("data", None),
            axis="data", compressor=comp, gamma=0.3, process=sp)
        n_as = permutes(ex_as, k, x0,
                        [jnp.zeros_like(x0) for _ in range(1 + tau)],
                        [jnp.zeros_like(x0) for _ in range(R * (1 + tau))])
        assert n_as == n_lf, (n_as, n_lf)
        print("ASYNC PERMUTES ==", n_as, "== LINKFAIL", n_lf)
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_trainer_async_e2e_and_staleness_change_restore():
    """Trainer end-to-end under bounded staleness on an 8-device mesh:
    finite decreasing loss, replica/ring state layout, and a staleness-bound
    change restoring via the elastic re-mix path (ring subtrees live under
    the reset prefixes, so the re-shaped lists restore clean + re-warm)."""
    run_sub("""
        import os, tempfile
        from repro.configs.base import get_config, ChocoConfig
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.data.synthetic import make_lm_batch_fn

        mesh = make_mesh((8, 1), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        nb = make_lm_batch_fn(cfg, 32, 2, 8)

        def trainer(tau):
            return DecentralizedTrainer(
                model=m, choco=ChocoConfig(
                    compressor="top_k", comp_kwargs=(("fraction", 0.05),),
                    topology="ring", topology_process="staleness",
                    max_staleness=tau),
                mesh=mesh, n_nodes=8, optimizer=sgd(),
                lr_fn=constant_schedule(0.05))

        # gamma folds the staleness bound (trainer-level twin of the
        # fast-tier formula test)
        assert trainer(0).gamma > trainer(2).gamma > 0.0

        tr = trainer(2)
        state = tr.init_state(jax.random.PRNGKey(0))
        R = tr.schedules[0].n_rounds
        assert len(state.x_hat) == 3 and len(state.s) == R * 3
        b = jax.tree.map(jnp.asarray, nb())
        step = tr.jitted_train_step(jax.eval_shape(lambda: state),
                                    jax.eval_shape(lambda: b))
        losses = []
        for i in range(8):
            state, mets = step(state, jax.tree.map(jnp.asarray, nb()))
            losses.append(float(mets["loss"]))
        assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses

        d = os.path.join(tempfile.mkdtemp(), "step8")
        tr.save_checkpoint(d, state)
        same, man, warm = tr.restore_checkpoint(d)
        assert warm == 0, "same staleness bound must be resume-exact"
        assert man.fingerprint["max_staleness"] == 2

        t1 = trainer(1)
        restored, man, warm = t1.restore_checkpoint(d)
        assert warm > 0, "staleness-bound change must take the re-mix path"
        assert len(restored.x_hat) == 2 and len(restored.s) == R * 2
        p_old = jax.tree.leaves(state.params)[0]
        p_new = jax.tree.leaves(restored.params)[0]
        np.testing.assert_array_equal(np.asarray(p_old), np.asarray(p_new))
        restored = t1.consensus_warmup(restored, warm)
        total = sum(float(jnp.sum(jnp.abs(l)))
                    for tree in restored.x_hat
                    for l in jax.tree.leaves(tree))
        assert total > 0, "warmup must engage the async engine"
        print("TRAINER ASYNC OK", losses[0], "->", losses[-1])
    """)


@pytest.mark.slow
@pytest.mark.distributed
def test_launcher_max_staleness_e2e():
    """Full CLI path: --topology-process staleness --max-staleness trains
    through launch/train.py on a simulated 8-device mesh."""
    run_sub("""
        from repro.launch.train import main
        assert main(["--arch", "qwen3-1.7b", "--smoke", "--mesh", "8x1",
                     "--simulate-devices", "8", "--seq-len", "32",
                     "--batch-per-node", "2", "--compressor", "top_k",
                     "--fraction", "0.05", "--optimizer", "sgd",
                     "--lr", "0.05", "--steps", "4",
                     "--topology-process", "staleness",
                     "--max-staleness", "2"]) == 0
        print("CLI MAX-STALENESS OK")
    """)
