"""Schedule-driven gossip engine vs the Algorithm-5 matrix simulator on
every compiled topology, in an 8-device subprocess.

Kept apart from tests/test_distributed.py so that pytest-xdist's
``--dist loadfile`` can run the two files on different workers: each holds
about half of the distributed suite's compile time."""
import pytest

from test_distributed import run_sub  # shared subprocess runner

pytestmark = [pytest.mark.slow, pytest.mark.distributed]


@pytest.mark.parametrize("topology", ["hypercube", "star", "chain",
                                      "fully_connected", "torus"])
def test_distributed_schedule_matches_simulator(topology):
    """Tentpole acceptance: the schedule-driven engine (packed AND per-leaf)
    reproduces the Algorithm-5 matrix simulator on every compiled topology —
    graphs the pre-schedule runtime could not run at all (hypercube, star,
    chain, fully-connected) now go through the same packed ppermute path."""
    run_sub(f"""
        from jax.sharding import PartitionSpec as P
        from repro.comm.gossip import make_gossip_exchange
        from repro.comm.schedule import compile_schedule
        from repro.core import make_topology, TopK
        from repro.core.choco_gossip import (choco_gossip_round_efficient,
                                             init_efficient_state)

        n, d = 8, 96
        topo = make_topology("{topology}", n)
        sched = compile_schedule(topo)
        mesh = make_mesh((8, 1), ("data", "model"))
        comp = TopK(k=9)            # deterministic: no RNG divergence
        gamma = 0.07
        x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        W = jnp.asarray(topo.W)

        st = init_efficient_state(x0)
        for _ in range(5):
            st = choco_gossip_round_efficient(st, W, gamma, comp)

        for packed in (True, False):
            ex = make_gossip_exchange(
                mode="choco", mesh=mesh, state_specs={{"w": P("data", None)}},
                axis="data", compressor=comp, gamma=gamma, packed=packed,
                schedules=(sched,))
            x = {{"w": x0}}
            xh = {{"w": jnp.zeros_like(x0)}}
            s = {{"w": jnp.zeros_like(x0)}}
            for i in range(5):
                x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
            np.testing.assert_allclose(np.asarray(x["w"]), np.asarray(st.x),
                                       rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(np.asarray(xh["w"]),
                                       np.asarray(st.x_hat),
                                       rtol=1e-4, atol=1e-5)
        print("SCHEDULE MATCHES SIMULATOR")
    """)
