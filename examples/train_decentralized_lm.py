"""End-to-end driver: decentralized CHOCO-SGD training of a transformer LM.

Simulates a gossip ring of data-parallel nodes on CPU host devices (the same
code path lowers to the TPU production mesh via launch/train.py).  Default is
a fast CPU-sized run; --model-scale 100m trains a ~100M-parameter qwen3-family
model for --steps steps.

Run:
    python examples/train_decentralized_lm.py                      # 2-min demo
    python examples/train_decentralized_lm.py --model-scale 100m --steps 300
    python examples/train_decentralized_lm.py --mode allreduce     # baseline
"""
import argparse
import dataclasses
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
N_DEVICES = 8
os.environ.setdefault("XLA_FLAGS",
                      f"--xla_force_host_platform_device_count={N_DEVICES}")

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, ChocoConfig
from repro.models import build_model
from repro.models.transformer import count_params
from repro.train.trainer import DecentralizedTrainer
from repro.optim import momentum_sgd, cosine_schedule
from repro.data.synthetic import make_lm_batch_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--model-scale", default="tiny", choices=["tiny", "20m", "100m"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-per-node", type=int, default=4)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--mode", default="choco", choices=["choco", "plain", "allreduce"])
    ap.add_argument("--compressor", default="top_k")
    ap.add_argument("--fraction", type=float, default=0.01)
    ap.add_argument("--heterogeneity", type=float, default=1.0,
                    help="1.0 = paper's hardest 'sorted' data assignment")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    if args.model_scale == "20m":
        cfg = dataclasses.replace(cfg, n_layers=4, d_model=512, n_heads=8,
                                  n_kv_heads=4, head_dim=64, d_ff=1536,
                                  vocab_size=8192)
    elif args.model_scale == "100m":
        cfg = dataclasses.replace(cfg, n_layers=8, d_model=768, n_heads=12,
                                  n_kv_heads=4, head_dim=64, d_ff=3072,
                                  vocab_size=32768)
    model = build_model(cfg)
    print(f"arch={cfg.name} params={count_params(cfg) / 1e6:.1f}M "
          f"nodes={args.nodes} mode={args.mode} "
          f"compressor={args.compressor}@{args.fraction}")

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((args.nodes, N_DEVICES // args.nodes),
                     ("data", "model"))
    trainer = DecentralizedTrainer(
        model=model,
        choco=ChocoConfig(compressor=args.compressor,
                          comp_kwargs=(("fraction", args.fraction),)),
        mesh=mesh, n_nodes=args.nodes,
        optimizer=momentum_sgd(beta=0.9),
        lr_fn=cosine_schedule(0.2, warmup=10, total=args.steps),
        mode=args.mode)
    print(f"consensus stepsize gamma = {trainer.gamma:.4f}")

    state = trainer.init_state(jax.random.PRNGKey(0))
    next_batch = make_lm_batch_fn(cfg, args.seq_len, args.batch_per_node,
                                  args.nodes, args.heterogeneity)
    batch0 = jax.tree.map(jnp.asarray, next_batch())
    step = trainer.jitted_train_step(jax.eval_shape(lambda: state),
                                     jax.eval_shape(lambda: batch0))

    t0 = time.time()
    for i in range(args.steps):
        batch = jax.tree.map(jnp.asarray, next_batch())
        state, mets = step(state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(mets['loss']):.4f}  "
                  f"lr {float(mets['lr']):.4f}  "
                  f"grad_norm {float(mets['grad_norm']):.2f}  "
                  f"({(time.time() - t0) / (i + 1):.2f}s/step)")

    if args.checkpoint:
        trainer.save_checkpoint(args.checkpoint, state,
                                metadata={"arch": cfg.name})
        print(f"saved sharded checkpoint to {args.checkpoint}/ "
              f"(manifest.json + per-host shards; resume via "
              f"launch/train.py --resume)")


if __name__ == "__main__":
    main()
