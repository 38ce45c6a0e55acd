"""Checkpoint-layer benchmark: sharded manifest-driven format vs legacy
flat-npz, on the qwen3-1.7b smoke TrainState.

Two sections:
  * save_restore — median wall time (us) for save and restore in both
    formats, with the checkpoint's on-disk bytes-per-host as the derived
    column.  Run for state_dtype float32 and bfloat16: the legacy flat
    format widens bf16 error-feedback state to f32 (npz cannot store
    ml_dtypes), while the sharded manifest bit-casts it to uint16 — half
    the bytes for the x_hat/s payload, recorded lossless.
  * restore_modes — sharded restore into target shardings (the production
    resume path: no host-gather, no donor state) vs host-numpy assembly.

Methodology notes live in EXPERIMENTS.md §Checkpointing.
"""
import os
import shutil
import tempfile

import jax

from repro.checkpoint.checkpointing import (restore_pytree, restore_sharded,
                                            save_pytree, save_sharded)
from .common import time_fn, emit


def _dir_bytes(path: str) -> int:
    if os.path.isfile(path):
        return os.path.getsize(path)
    return sum(os.path.getsize(os.path.join(r, f))
               for r, _, fs in os.walk(path) for f in fs)


def _make_trainer(state_dtype: str):
    from repro.configs.base import get_config, ChocoConfig
    from repro.models import build_model
    from repro.train.trainer import DecentralizedTrainer
    from repro.optim import momentum_sgd, constant_schedule
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("qwen3-1.7b", smoke=True)
    tr = DecentralizedTrainer(
        model=build_model(cfg),
        choco=ChocoConfig(compressor="top_k",
                          comp_kwargs=(("fraction", 0.01),),
                          state_dtype=state_dtype),
        mesh=mesh, n_nodes=1, optimizer=momentum_sgd(),
        lr_fn=constant_schedule(0.1))
    return tr, tr.init_state(jax.random.PRNGKey(0))


def save_restore():
    for sdt, tag in (("float32", "f32"), ("bfloat16", "bf16")):
        tr, state = _make_trainer(sdt)
        shape = jax.eval_shape(lambda: state)
        shardings = tr.state_shardings(shape)
        work = tempfile.mkdtemp(prefix="bench_ckpt_")
        flat_path = os.path.join(work, "flat.npz")
        shard_dir = os.path.join(work, "sharded")
        host = jax.device_get(state)

        us = time_fn(lambda: save_pytree(flat_path, host), iters=3)
        emit(f"checkpoint/legacy_save_{tag}", us,
             f"MB_per_host={_dir_bytes(flat_path) / 1e6:.1f}")
        us = time_fn(lambda: restore_pytree(flat_path, shape), iters=3)
        emit(f"checkpoint/legacy_restore_{tag}", us, "host_gathered=1")

        us = time_fn(lambda: save_sharded(
            shard_dir, state, step=0,
            fingerprint=tr.fingerprint()), iters=3)
        emit(f"checkpoint/sharded_save_{tag}", us,
             f"MB_per_host={_dir_bytes(shard_dir) / 1e6:.1f}")
        us = time_fn(lambda: restore_sharded(shard_dir, shape, shardings),
                     iters=3)
        emit(f"checkpoint/sharded_restore_{tag}", us,
             "into_target_shardings=1")
        shutil.rmtree(work, ignore_errors=True)


def restore_modes():
    tr, state = _make_trainer("bfloat16")
    shape = jax.eval_shape(lambda: state)
    shardings = tr.state_shardings(shape)
    work = tempfile.mkdtemp(prefix="bench_ckpt_")
    shard_dir = os.path.join(work, "sharded")
    save_sharded(shard_dir, state, step=0, fingerprint=tr.fingerprint())
    us = time_fn(lambda: restore_sharded(shard_dir, shape, shardings), iters=3)
    emit("checkpoint/restore_into_shardings", us, "mode=device")
    us = time_fn(lambda: restore_sharded(shard_dir, shape), iters=3)
    emit("checkpoint/restore_host_numpy", us, "mode=host")
    shutil.rmtree(work, ignore_errors=True)


def run():
    save_restore()
    restore_modes()


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
