"""Framework-level communication benchmark: bytes on the wire per training
step for CHOCO vs plain gossip vs centralized all-reduce, plus the packed
(bucketed flat-buffer) vs per-leaf gossip engine comparison.

Three views:
  * analytic — from the compressors' wire formats (exact, any size);
  * packing audit — per-leaf vs packed payload wire bits + payload-array
    counts for a real multi-leaf param tree (no compilation needed);
  * compiled — collective-launch counts and wire bytes parsed from the SPMD
    HLO of the real train step on a small simulated mesh (subprocess with 8
    host devices, since benches themselves must see 1 device).

Methodology notes live in EXPERIMENTS.md §Wire audit.
"""
import json
import textwrap

import jax

from repro.core import TopK, RandK, QSGD, Identity
from .common import emit, run_cpu_child



def analytic():
    d = 1_720_000_000          # qwen3-1.7b-scale parameter vector (tied)
    for name, comp in (("exact", Identity()),
                       ("qsgd16", QSGD(16)),
                       ("rand1pct", RandK(fraction=0.01)),
                       ("top1pct", TopK(fraction=0.01))):
        gb = comp.wire_bits(d) / 8 / 1e9 * 2        # 2 ring neighbours
        emit(f"collectives/analytic_{name}", 0.0,
             f"GB_per_node_per_step={gb:.3f};reduction={Identity().wire_bits(d)/comp.wire_bits(d):.0f}x")


def packing_audit(arch: str = "qwen3-1.7b"):
    """Packed-engine wire accounting vs the summed per-leaf payloads, from
    static shapes only.  The acceptance bar for the packing engine is packed
    wire bits within 10% of the per-leaf sum (padding + per-bucket ceil(k)
    are the only differences) with ~#leaves/#buckets fewer payload arrays."""
    from repro.configs.base import get_config
    from repro.models import build_model
    from repro.comm.packing import make_bucket_spec, packed_wire_bits
    from repro.launch.sharding import param_pspecs
    from repro.comm.gossip import _leaf_routes

    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    n_nodes = 4
    # the real trainer state: (n_nodes, ...) leaves; routes from the same
    # param_pspecs call the exchange uses (model-sharded vs replicated)
    shapes_n = jax.eval_shape(
        lambda k: jax.vmap(m.init)(jax.random.split(k, n_nodes)),
        jax.random.PRNGKey(0))
    specs = param_pspecs(shapes_n, cfg, node_axis="data", model_size=0)
    routes = _leaf_routes(specs, "data")
    # per-node view (what one gossip node packs and ships)
    shapes = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), shapes_n)
    leaves = jax.tree.leaves(shapes)
    comp = TopK(fraction=0.01)
    per_leaf_bits = sum(comp.wire_bits(l.size) for l in leaves)
    spec = make_bucket_spec(shapes, routes=routes)
    packed_bits = packed_wire_bits(spec, comp)
    # payload arrays ppermuted per neighbour: 2 per sparse payload
    per_leaf_arrays = 2 * len(leaves)
    packed_arrays = 2 * spec.n_buckets
    emit(f"collectives/packing_audit_{arch}", 0.0,
         f"leaves={len(leaves)};buckets={spec.n_buckets};"
         f"per_leaf_bits={per_leaf_bits};packed_bits={packed_bits};"
         f"packed_over_per_leaf={packed_bits / per_leaf_bits:.4f};"
         f"payload_arrays_{per_leaf_arrays}->{packed_arrays}")


def compiled():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        from repro.configs.base import get_config, ChocoConfig, InputShape
        from repro.models import build_model
        from repro.train.trainer import DecentralizedTrainer
        from repro.optim import sgd, constant_schedule
        from repro.launch.specs import train_batch_specs
        from repro.analysis.roofline import parse_collectives

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = get_config("qwen3-1.7b", smoke=True)
        m = build_model(cfg)
        out = {}
        runs = [("choco_packed", "choco", True), ("choco_per_leaf", "choco", False),
                ("plain", "plain", True), ("allreduce", "allreduce", True)]
        for name, mode, packed in runs:
            tr = DecentralizedTrainer(model=m, choco=ChocoConfig(
                    compressor="top_k", comp_kwargs=(("fraction", 0.01),),
                    packed_gossip=packed),
                mesh=mesh, n_nodes=4, optimizer=sgd(),
                lr_fn=constant_schedule(0.01), mode=mode)
            ss = tr.state_shape()
            bs = train_batch_specs(cfg, InputShape("b", 128, 16, "train"), 4)
            comp = tr.jitted_train_step(ss, bs).lower(ss, bs).compile()
            st = parse_collectives(comp.as_text(), 8)
            out[name] = {"wire_bytes": st.total_wire_bytes,
                         "permute_bytes": st.wire_bytes["collective-permute"],
                         "allreduce_bytes": st.wire_bytes["all-reduce"],
                         "permute_count": st.counts["collective-permute"],
                         "collective_count": sum(st.counts.values())}
        print(json.dumps(out))
    """)
    stdout = run_cpu_child("collectives/compiled", script)
    out = json.loads(stdout.strip().splitlines()[-1])
    base = out["plain"]["permute_bytes"] or 1.0
    for mode, v in out.items():
        emit(f"collectives/compiled_{mode}", 0.0,
             f"wire_bytes={v['wire_bytes']:.3e};permute={v['permute_bytes']:.3e};"
             f"permute_count={v['permute_count']};collectives={v['collective_count']};"
             f"vs_plain_permute={v['permute_bytes']/base:.4f}")
    pk, pl = out["choco_packed"], out["choco_per_leaf"]
    emit("collectives/packed_vs_per_leaf", 0.0,
         f"permute_launches_{pl['permute_count']}->{pk['permute_count']};"
         f"launch_reduction={pl['permute_count']/max(pk['permute_count'],1):.1f}x;"
         f"permute_bytes_ratio={pk['permute_bytes']/max(pl['permute_bytes'],1.0):.4f}")


def run():
    analytic()
    packing_audit()
    compiled()


if __name__ == "__main__":
    run()
