"""The comparison that decides ``correct`` fails what it has to fail.

At a size a test run holds (the qwen3-1.7b smoke widths, tied, seq 64 x
2 rows, on the CPU): the program's run is correct; the control (the
reference with float8 matmuls in the program's place) is not; and a run
whose timed path is broken underneath is not, for every fault a cell can
have: a step that returns its state unchanged, half of the batch left out
of the loss, the exchange between nodes left out, the compressed message
altered where it is produced.  The four-node faults run in a child
process with four host devices.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

#: limits for this size, set from its readings as the cells' are from
#: theirs: the program reads at most loss 1.1e-4, grad 1.4e-3, step
#: 2.0e-3, hat 2.3e-3, s 1.8e-3, grad_proj 5.9e-2, step_proj 5.2e-2 on
#: one and four nodes; the control reads grad 2.3e-2, step 1.3e-2,
#: grad_proj 0.45 and step_proj 0.48 at the least
LIMITS = {"loss_gap": 1e-3, "grad_gap": 6e-3, "step_gap": 8e-3,
          "hat_gap": 1e-2, "s_gap": 1e-2, "grad_proj_gap": 0.2,
          "step_proj_gap": 0.2}
FAULTS = ("unchanged", "half_batch", "altered", "no_exchange")


def tiny_cell(nodes: int):
    from repro.configs.base import get_config
    from bench.cell import Cell
    from bench.program import json_fields
    cfg = dataclasses.replace(get_config("qwen3-1.7b", smoke=True),
                              tie_embeddings=True)
    config = {"name": "tiny", "lr": 0.01, "model": json_fields(cfg),
              "derived_from": {"repo_config": "qwen3-1.7b", "smoke": True,
                               "overrides": {"tie_embeddings": True}}}
    traffic = {"nodes": nodes, "mode": "choco", "topology": "ring",
               "compressor": "top_k", "fraction": 0.01,
               "gossip_engine": "packed", "kernel_backend": "auto",
               "exact_small_leaves": False, "state_dtype": "float32",
               "optimizer": "momentum", "seq_len": 64, "batch_per_node": 2,
               "heterogeneity": 1.0}
    limits = {k: v for k, v in LIMITS.items() if nodes > 1 or k != "s_gap"}
    return Cell(name="tiny", chips=nodes, config_name="tiny", config=config,
                traffic_name="tiny", traffic=traffic, limits=limits,
                end_to_end=[{"name": "setup_s"}, {"name": "tokens_per_s"}],
                per_layer=[])


def plant(fault, monkeypatch):
    """Break the program's timed path underneath the harness."""
    from repro.comm import gossip, packing
    from repro.models.transformer import Model
    from repro.train.trainer import DecentralizedTrainer
    if fault == "unchanged":
        make = DecentralizedTrainer.make_train_step

        def broken(self, phase_scopes=False):
            step = make(self, phase_scopes)
            return lambda state, batch: (state, step(state, batch)[1])
        monkeypatch.setattr(DecentralizedTrainer, "make_train_step", broken)
    elif fault == "half_batch":
        loss = Model.loss

        def broken(self, params, batch):
            S = batch["tokens"].shape[-1]
            import jax.numpy as jnp
            valid = jnp.broadcast_to(jnp.arange(S) < S // 2,
                                     batch["tokens"].shape)
            return loss(self, params, dict(batch, valid=valid))
        monkeypatch.setattr(Model, "loss", broken)
    elif fault == "altered":
        compress = packing.compress_bucket

        def broken(*args, **kwargs):
            p = compress(*args, **kwargs)
            return dataclasses.replace(p, values=-p.values)
        monkeypatch.setattr(packing, "compress_bucket", broken)
    elif fault == "no_exchange":
        def broken(payloads, groups, axis_arg, dense_fn, flat_idx_fn):
            return [d * 0.0 for d in dense_fn(payloads)], 0.0
        monkeypatch.setattr(gossip, "_neighbor_sum", broken)
    elif fault is not None:
        raise ValueError(fault)


def run_tiny(nodes: int, fault=None, monkeypatch=None):
    from bench import run as brun
    if fault is not None:
        plant(fault, monkeypatch)
    return brun.run(tiny_cell(nodes), 12345678901, 0.5, False,
                    time.perf_counter())


def test_program_correct_control_not():
    import jax
    from bench import compare
    from bench.calibrate import calibrate
    summary = calibrate(tiny_cell(1), [3000000001], 1, jax.devices()[:1])
    assert compare.judge(summary["lower"], tiny_cell(1).limits)
    assert not compare.judge(summary["upper"]["control"], tiny_cell(1).limits)


@pytest.mark.parametrize("fault", [f for f in FAULTS if f != "no_exchange"])
def test_one_node_fault_is_not_correct(fault, monkeypatch):
    assert run_tiny(1, fault, monkeypatch)["correct"] is False


@pytest.fixture(scope="module")
def four_nodes():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, __file__], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_four_node_ring(fault, four_nodes):
    assert four_nodes[str(fault)] is (fault is None)


if __name__ == "__main__":
    out = {}
    for fault in (None,) + FAULTS:
        with pytest.MonkeyPatch.context() as mp:
            out[str(fault)] = run_tiny(4, fault, mp)["correct"]
    print(json.dumps(out))


def test_limit_rule():
    """Limits come from readings by one rule: a control under 3x the lower
    reading and a fault under 10x it set no upper reading, the unchanged
    state reads 1 on the change's norm, and the limit lies between."""
    from bench.calibrate import limits
    lower = {"loss_gap": 1e-4, "grad_gap": 1e-3, "step_gap": 4e-4,
             "step_proj_gap": 2e-2, "hat_gap": 2e-6}
    upper = {"control": {"loss_gap": 2e-4, "grad_gap": 2e-3,
                         "step_gap": 1.6e-3, "step_proj_gap": 0.3,
                         "hat_gap": 1e-7},
             "half_batch": {"loss_gap": 1e-2, "grad_gap": 5e-3,
                            "step_gap": 6e-2, "step_proj_gap": 0.1,
                            "hat_gap": 1e-6},
             "altered": {"loss_gap": 0.0, "grad_gap": 0.0, "step_gap": 0.0,
                         "step_proj_gap": 0.0, "hat_gap": 3.6}}
    lim, ups, fails = limits(lower, upper)
    assert ups == {"loss_gap": 1e-2, "step_gap": 1.6e-3,
                   "step_proj_gap": 0.3, "hat_gap": 1.0}
    assert "grad_gap" not in lim      # no reading 3x / 10x above the lower
    assert lim == {"loss_gap": 2e-3, "step_gap": 1e-3,
                   "step_proj_gap": 0.1, "hat_gap": 1e-2}
    assert all(lower[k] < v < ups[k] for k, v in lim.items())
    assert fails                      # the control fails step and step_proj
    upper["control"] = {k: v / 10 for k, v in upper["control"].items()}
    assert not limits(lower, upper)[2]
