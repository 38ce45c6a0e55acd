"""Smoke run of the decentralized trainer on TPU chips.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips of one host (v5e:2x2)

One chip: the launcher's main path (``repro.launch.train.main``, called in
this process) trains qwen3-1.7b at its published widths, cut to
``N_LAYERS`` layers, as one CHOCO node with ring top-1% gossip and the
fused Pallas kernels.  The script then checks the run log: backend
``pallas``, the depth cut recorded, finite and falling loss; and compares
the fused EF update (``ef_bucket_update``) with its jnp twin on one real
bucket, on the chip.

Four chips: the same launcher call as a 4-node ring (one node per chip),
a check that each node's parameters sit on a device of their own, and the
CHOCO-GOSSIP engine against the (n, d) matrix simulator on the 4 chips.

Depth and batch come from a compile rehearsal for a described v5e
(``memory_analysis`` of the jitted step): 4 layers at batch 1 x 4096
tokens hold 8.2 GB of trainer state (x, x_hat, s, momentum in f32) plus
6.2 GB of temporaries, 14.4 GB of the chip's 16 GB.

Any failed phase exits nonzero.  The last line of standard output is one
JSON object naming the device.  Without a TPU, or without the rest of the
repository beside it, the script fails and prints no result.  It starts
no processes.
"""
import argparse
import json
import math
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen3-1.7b"
N_LAYERS = 4
BATCH_PER_NODE = 1
SEQ_LEN = 4096
STEPS = 8
#: momentum SGD at the launcher's default lr 0.1 diverges at d_model 2048
#: (loss 12.4 -> 36.9 in 8 steps on a v5e chip); 0.01 descends
LR = 0.01
#: bound on |pallas - jnp| for the EF update, the one tests/test_fused.py
#: holds the two backends to (FMA-contraction rounding only)
EF_ATOL = 1e-5


def _say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def _fail(msg):
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _train(n_nodes):
    """The launcher's main path on ``n_nodes`` chips; returns the run log."""
    from repro.launch.train import main as launch
    mdir = os.path.join(ROOT, "chiprun_out", "chip_smoke", f"{n_nodes}chip")
    log = os.path.join(mdir, "metrics.jsonl")
    if os.path.exists(log):
        os.remove(log)
    rc = launch(["--arch", ARCH, "--n-layers", str(N_LAYERS),
                 "--mesh", f"{n_nodes}x1", "--mode", "choco",
                 "--compressor", "top_k", "--fraction", "0.01",
                 "--seq-len", str(SEQ_LEN),
                 "--batch-per-node", str(BATCH_PER_NODE),
                 "--steps", str(STEPS), "--lr", str(LR),
                 "--state-dtype", "float32",
                 "--metrics-dir", mdir])
    if rc != 0:
        _fail(f"launcher exited {rc}")
    with open(log) as f:
        recs = [json.loads(line) for line in f]
    header = [r for r in recs if r["kind"] == "header"][0]
    steps = [r for r in recs if r["kind"] == "metrics" and "train/loss" in r]
    return header, steps


def _check_run(header, steps, n_nodes):
    import jax
    backend = header["kernel_backend"]
    _say(f"kernel_backend {backend}")
    if backend != "pallas":
        _fail(f"kernel backend resolved to {backend!r}, not 'pallas'")
    want = f"n_layers 28->{N_LAYERS}"
    _say(f"arch {header['arch']} reduced: {header['reduced']} "
         f"(every width as published)")
    if header["reduced"] != want:
        _fail(f"run header says reduced={header['reduced']!r}, not {want!r}")
    nodes = header["node_devices"]
    _say(f"node_devices {nodes}")
    ids = [d for node in nodes for d in node]
    if len(nodes) != n_nodes or any(len(n) != 1 for n in nodes) \
            or len(set(ids)) != n_nodes:
        _fail(f"expected each of {n_nodes} nodes on a device of its own, "
              f"got {nodes}")
    compile_s = [r["train/compile_s"] for r in steps if "train/compile_s" in r]
    sps = [r["train/s_per_step"] for r in steps if "train/s_per_step" in r]
    _say(f"compile_s {compile_s[0]} steady_s_per_step {sps[-1]} "
         f"tokens_per_step {n_nodes * BATCH_PER_NODE * SEQ_LEN}")
    losses = [(r["step"], r["train/loss"]) for r in steps]
    _say("losses " + " ".join(f"step{s}={l}" for s, l in losses))
    if not all(math.isfinite(l) for _, l in losses):
        _fail(f"non-finite loss: {losses}")
    if not losses[-1][1] < losses[0][1]:
        _fail(f"loss did not fall: {losses}")
    for d in jax.devices():
        if d.id in ids:
            stats = d.memory_stats()
            _say(f"device {d.id} peak_bytes_in_use "
                 f"{stats['peak_bytes_in_use']} bytes_limit "
                 f"{stats.get('bytes_limit')} peak_bytes_reserved "
                 f"{stats.get('peak_bytes_reserved')}")


def _ef_parity():
    """Fused EF update vs its jnp twin on the largest per-layer bucket of
    the cut model, on the chip."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.comm.packing import make_bucket_spec
    from repro.configs.base import get_config
    from repro.kernels import dispatch as kd
    from repro.models import build_model
    cfg = dataclasses.replace(get_config(ARCH), n_layers=N_LAYERS)
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    sizes = sorted(b.size for b in make_bucket_spec(shapes).buckets)
    d = sizes[-2] if len(sizes) > 1 else sizes[-1]   # [-1]: the embedding
    args = [jax.random.normal(k, (d,), jnp.float32)
            for k in jax.random.split(jax.random.PRNGKey(7), 5)]
    outs = {}
    for bk in ("pallas", "jnp"):
        fn = jax.jit(lambda *a, bk=bk: kd.ef_bucket_update(
            *a, 1 / 3, 1 / 3, 0.046, backend=bk))
        outs[bk] = [np.asarray(o) for o in fn(*args)]
    worst = max(float(np.max(np.abs(a - b)))
                for a, b in zip(outs["pallas"], outs["jnp"]))
    _say(f"ef_bucket_update pallas vs jnp on a {d}-element bucket: "
         f"max_abs_diff {worst} (bound {EF_ATOL})")
    if not worst <= EF_ATOL:
        _fail(f"fused EF update differs from jnp by {worst} > {EF_ATOL}")


def _engine_vs_simulator(n):
    """CHOCO-GOSSIP on ``n`` chips (shard_map + ppermute) against the
    (n, d) matrix simulator, for the backend the platform picks and jnp."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.comm.gossip import make_gossip_exchange
    from repro.core import TopK, ring
    from repro.core.choco_gossip import (choco_gossip_round_efficient,
                                         init_efficient_state)
    from repro.launch.mesh import make_mesh
    d, gamma, rounds = 96, 0.07, 5
    comp = TopK(k=9)                   # deterministic: no RNG divergence
    x0 = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    with jax.default_matmul_precision("highest"):
        W = jnp.asarray(ring(n).W)
        st = init_efficient_state(x0)
        for _ in range(rounds):
            st = choco_gossip_round_efficient(st, W, gamma, comp)
    mesh = make_mesh((n, 1), ("data", "model"))
    specs = {"w": P("data", None)}
    for backend in ("auto", "jnp"):
        ex = make_gossip_exchange(mode="choco", mesh=mesh, state_specs=specs,
                                  axis="data", compressor=comp, gamma=gamma,
                                  kernel_backend=backend)
        x, xh, s = {"w": x0}, {"w": jnp.zeros_like(x0)}, \
            {"w": jnp.zeros_like(x0)}
        for i in range(rounds):
            x, xh, s = ex(jax.random.PRNGKey(i), x, xh, s)
        err = max(float(np.max(np.abs(np.asarray(x["w"]) - np.asarray(st.x)))),
                  float(np.max(np.abs(np.asarray(xh["w"])
                                      - np.asarray(st.x_hat)))))
        _say(f"choco engine ({backend}) vs matrix simulator, n={n} ring, "
             f"{rounds} rounds: max_abs_diff {err}")
        np.testing.assert_allclose(np.asarray(x["w"]), np.asarray(st.x),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(xh["w"]), np.asarray(st.x_hat),
                                   rtol=1e-4, atol=1e-5)


def main(argv=None):
    """Run the phases for ``--chips``; exit nonzero if any fails."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): the one-chip training phases; 4: "
                         "only the four-chip gossip phases")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.env import place_compile_cache
    except ImportError as e:
        _fail(f"the repository's src/ is not beside this script: {e}")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} chips, found "
              f"{len(devices)}")
    _say(f"devices {len(devices)} x {devices[0].device_kind}; compile cache "
         f"{place_compile_cache()}")
    try:
        if args.chips == 1:
            _check_run(*_train(1), 1)
            _ef_parity()
        else:
            _check_run(*_train(4), 4)
            _engine_vs_simulator(4)
    except Exception as e:     # report any failed phase, then exit nonzero
        traceback.print_exc()
        _fail(f"{type(e).__name__}: {e}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
