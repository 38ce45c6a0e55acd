"""Convergence-contract tests over the declarative scenario matrix.

Tiering (see pytest.ini):
  * fast (unmarked): matrix-shape invariants + ONE representative contract
    triple (skewed CHOCO vs its no-gossip and IID controls) — seconds;
  * ``slow``: the full >= 12-scenario sweep with every contract;
  * ``slow + distributed``: per-edge straggler engine == simulator parity
    on the 8-device mesh (iterate for iterate).
"""
import numpy as np
import pytest

from scenarios import (BATCH, N_NODES, SCENARIOS, Scenario, get_scenario,
                       iid_control, no_gossip_control, run_scenario)
from test_distributed import run_sub  # noqa: E402  (shared subprocess runner)

# contract tolerances, calibrated against the observed noise floor of the
# reduced problem (~1e-4 in final loss between reseeded gossip runs; the
# no-gossip gap is ~2e-2 — two orders of magnitude of headroom)
IID_BAND = 0.01         # |loss(skewed CHOCO) - loss(IID CHOCO)| stays inside
NOGOSSIP_MARGIN = 5e-3  # loss(no-gossip) - loss(CHOCO) must exceed


# ---------------------------------------------------------------------------
# fast tier: the declarative matrix itself
# ---------------------------------------------------------------------------


class TestMatrixShape:
    def test_core_matrix_floor(self):
        """Acceptance floor: alpha in {0.1, 1, 100} x {ring, hypercube} x
        {topk, qsgd} — at least 12 core scenarios, all distinct."""
        names = [sc.name for sc in SCENARIOS]
        assert len(names) == len(set(names))
        assert len(SCENARIOS) >= 12
        for alpha in (0.1, 1.0, 100.0):
            for topo in ("ring", "hypercube"):
                for comp in ("topk", "qsgd"):
                    assert f"a{alpha:g}-{topo}-{comp}" in names

    def test_matrix_has_controls_k3_and_stragglers(self):
        names = [sc.name for sc in SCENARIOS]
        assert any(n.startswith("iid-") for n in names)
        assert any(n.endswith("-k3") for n in names)
        straggler = [sc for sc in SCENARIOS if sc.straggler_edges]
        assert straggler and all(sc.process == "staleness"
                                 for sc in straggler)

    def test_get_scenario_roundtrip_and_unknown(self):
        for sc in SCENARIOS:
            assert get_scenario(sc.name) is sc
        with pytest.raises(KeyError, match="unknown scenario"):
            get_scenario("nope")

    def test_controls_are_derived_not_listed(self):
        sc = get_scenario("a0.1-ring-topk")
        ng = no_gossip_control(sc)
        assert ng.gamma == 0.0 and ng.process is None and ng.alpha == sc.alpha
        iid = iid_control(sc)
        assert iid.alpha is None and iid.gamma == sc.gamma
        # derived controls never shadow a declared scenario
        names = {s.name for s in SCENARIOS}
        assert ng.name not in names and iid.name not in names


class TestRepresentativeContract:
    """One contract triple in the fast tier so a broken runner or a broken
    partitioner fails within seconds, not only in the slow sweep."""

    @pytest.fixture(scope="class")
    def triple(self):
        sc = get_scenario("a0.1-ring-topk")
        return {"choco": run_scenario(sc),
                "nogossip": run_scenario(no_gossip_control(sc)),
                "iid": run_scenario(iid_control(sc))}

    def test_skewed_choco_beats_no_gossip(self, triple):
        assert (triple["nogossip"]["final_loss"]
                > triple["choco"]["final_loss"] + NOGOSSIP_MARGIN), triple

    def test_skewed_choco_inside_iid_band(self, triple):
        gap = abs(triple["choco"]["final_loss"]
                  - triple["iid"]["final_loss"])
        assert gap < IID_BAND, triple

    def test_no_gossip_diverges_in_consensus(self, triple):
        assert (triple["nogossip"]["consensus_dist"]
                > 100 * triple["choco"]["consensus_dist"]), triple


# ---------------------------------------------------------------------------
# slow tier: the full sweep, every contract
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestFullSweep:
    @pytest.fixture(scope="class")
    def results(self):
        out = {sc.name: run_scenario(sc) for sc in SCENARIOS}
        # controls for the contract comparisons (skew alpha=0.1 cells)
        for name in ("a0.1-ring-topk", "a0.1-ring-qsgd",
                     "a0.1-hypercube-topk", "a0.1-hypercube-qsgd"):
            sc = get_scenario(name)
            out[name + "-nogossip"] = run_scenario(no_gossip_control(sc))
        return out

    def test_all_scenarios_converge(self, results):
        for name, r in results.items():
            assert np.isfinite(r["final_loss"]), (name, r)
            assert r["final_loss"] < 0.55, (name, r)     # well below ln 2

    def test_skewed_beats_no_gossip_everywhere(self, results):
        for name in ("a0.1-ring-topk", "a0.1-ring-qsgd",
                     "a0.1-hypercube-topk", "a0.1-hypercube-qsgd"):
            choco, ng = results[name], results[name + "-nogossip"]
            assert (ng["final_loss"]
                    > choco["final_loss"] + NOGOSSIP_MARGIN), (name, choco, ng)

    def test_skew_within_iid_band(self, results):
        """Final consensus-loss band vs the IID control, per cell."""
        for topo in ("ring", "hypercube"):
            for comp in ("topk", "qsgd"):
                iid = results[f"iid-{topo}-{comp}"]["final_loss"]
                for alpha in (0.1, 1.0, 100.0):
                    got = results[f"a{alpha:g}-{topo}-{comp}"]["final_loss"]
                    assert abs(got - iid) < IID_BAND, (topo, comp, alpha,
                                                       got, iid)

    def test_gossip_steps_3_narrows_skew_gap(self, results):
        """k=3 consensus rounds per step vs k=1 on the hardest skew: the
        consensus gap must shrink decisively, and the final loss must not
        regress beyond noise."""
        for comp in ("topk", "qsgd"):
            k1 = results[f"a0.1-ring-{comp}"]
            k3 = results[f"a0.1-ring-{comp}-k3"]
            assert (k3["consensus_dist"]
                    < 0.5 * k1["consensus_dist"]), (comp, k1, k3)
            assert (k3["final_loss"]
                    < k1["final_loss"] + 1e-3), (comp, k1, k3)

    def test_straggler_still_converges(self, results):
        """A maximally slow ring link under alpha=0.1 skew slows consensus
        but does not break the contract vs no communication at all."""
        straggler = results["a0.1-ring-topk-straggler"]
        uniform = results["a0.1-ring-topk-stale-uniform"]
        ng = results["a0.1-ring-topk-nogossip"]
        for r in (straggler, uniform):
            assert ng["final_loss"] > r["final_loss"] + NOGOSSIP_MARGIN, r
            assert ng["consensus_dist"] > 100 * r["consensus_dist"], r


# ---------------------------------------------------------------------------
# distributed tier: straggler engine == simulator, iterate for iterate
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("probs", ["default", "custom"])
def test_distributed_straggler_engine_matches_simulator(probs):
    """Acceptance: with a single straggler edge the 8-device engine
    reproduces the extended matrix simulator iterate for iterate — the
    per-edge delay table is drawn identically on every node from the shared
    exchange key, exactly like the global-distribution case."""
    sprobs = ("None" if probs == "default" else "(0.1, 0.2, 0.7)")
    run_sub(f"""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.comm.async_gossip import StalenessProcess
        from repro.core import make_topology, TopK
        from repro.core.choco_gossip import (choco_stale_round,
                                             init_stale_state)

        n, d, tau = 8, 96, 2
        sched = compile_schedule(make_topology("ring", n))
        proc = StalenessProcess(sched, max_staleness=tau,
                                straggler_edges=((0, 1), (4, 5)),
                                straggler_delay_probs={sprobs})
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
        print("STRAGGLER ENGINE == SIMULATOR")
    """)
