"""The system under test, built as the training launcher builds it.

``launch/train.py:main`` turns its flags into a ``ModelConfig``, a mesh of
one node per chip and a ``DecentralizedTrainer``; :func:`build` does the
same from a cell's files.  The one departure is the learning-rate
schedule: a constant rate, since a window of a fixed number of seconds has
no total step count for the launcher's cosine schedule to end at.

The harness gives the trainer its weights (``reference.init_nodes`` from
the seed, one jitted call on the device) in place of the trainer's own, so
the reference can make the same weights without taking them from the
program.  The trainer's x_hat, s and momentum start at zero either way.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp

from bench import compare, reference


def model_config(config: Dict):
    """The program's ``ModelConfig`` for a configuration file; raises when
    it differs from the ``model`` section the file states."""
    from repro.configs.base import get_config
    src = config["derived_from"]
    cfg = get_config(src["repo_config"], smoke=src.get("smoke", False))
    cfg = dataclasses.replace(cfg, **src["overrides"])
    built = json_fields(cfg)
    if built != config["model"]:
        diff = {k: (built.get(k), config["model"].get(k))
                for k in set(built) | set(config["model"])
                if built.get(k) != config["model"].get(k)}
        raise ValueError(f"configuration {config['name']}: the program builds "
                         f"{diff} (program, file)")
    return cfg


def json_fields(cfg) -> Dict:
    """``dataclasses.asdict`` with tuples as lists, as JSON holds them."""
    def plain(v):
        if isinstance(v, dict):
            return {k: plain(x) for k, x in v.items()}
        if isinstance(v, (list, tuple)):
            return [plain(x) for x in v]
        return v
    return plain(dataclasses.asdict(cfg))


@dataclasses.dataclass
class Program:
    trainer: object
    step: object           # the jitted train step (launcher's build)
    mesh: object


def build(config: Dict, traffic: Dict, phase_scopes: bool) -> Program:
    from repro.configs.base import ChocoConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.optim import constant_schedule, make_optimizer
    from repro.train.trainer import DecentralizedTrainer
    cfg = model_config(config)
    n = traffic["nodes"]
    mesh = make_mesh((n, 1), ("data", "model"))
    trainer = DecentralizedTrainer(
        model=build_model(cfg),
        choco=ChocoConfig(
            compressor=traffic["compressor"],
            comp_kwargs=(("fraction", traffic["fraction"]),),
            gossip_axis="data", state_dtype=traffic["state_dtype"],
            topology=traffic["topology"], gossip_steps=1,
            packed_gossip=traffic["gossip_engine"] == "packed",
            exact_small_leaves=traffic["exact_small_leaves"],
            kernel_backend=traffic["kernel_backend"]),
        mesh=mesh, n_nodes=n,
        optimizer=make_optimizer(traffic["optimizer"]),
        lr_fn=constant_schedule(config["lr"]), mode=traffic["mode"])
    shape = trainer.state_shape()
    batch = {k: jax.ShapeDtypeStruct(
        (n, traffic["batch_per_node"], traffic["seq_len"]), jnp.int32)
        for k in ("tokens", "labels")}
    step = trainer.jitted_train_step(shape, batch, phase_scopes=phase_scopes)
    return Program(trainer=trainer, step=step, mesh=mesh)


def seeded_state(prog: Program, model: Dict, key):
    """The trainer's initial state with the seed's weights in place of its
    own, written over the trainer's in place (no second copy on the chip)."""
    trainer = prog.trainer
    shape = trainer.state_shape()
    ours = jax.eval_shape(lambda k: reference.init_nodes(
        model, k, trainer.n_nodes), key)
    if (jax.tree.structure(ours) != jax.tree.structure(shape.params)
            or [a.shape for a in jax.tree.leaves(ours)]
            != [a.shape for a in jax.tree.leaves(shape.params)]):
        raise ValueError("the program's parameters are not the dense "
                         "decoder's that the reference describes: "
                         f"{jax.tree.map(lambda a: a.shape, shape.params)}")
    fill = jax.jit(lambda st, k: st._replace(
        params=reference.init_nodes(model, k, trainer.n_nodes)),
        donate_argnums=0, out_shardings=trainer.state_shardings(shape))
    return fill(trainer.init_state(key), key)


def _node_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(a.reshape(a.shape[0], -1)
                                        .astype(jnp.float32)), axis=1))
            for a in jax.tree.leaves(tree)]


def _node_projections(tree):
    return [jax.vmap(compare.projections)(a.reshape(a.shape[0], -1)
                                          .astype(jnp.float32))
            for a in jax.tree.leaves(tree)]


@jax.jit
def momentum_norms(state):
    """Per leaf, per-node norms and projections of the optimizer's first
    moment."""
    return _node_norms(state.opt.mu), _node_projections(state.opt.mu)


def end_norms(prog: Program, model: Dict):
    """Jitted (state, key) -> per leaf, per-node norms of the parameters'
    change from the seed's weights (made again inside, not kept), its
    projections, and the norms of x_hat and of s."""
    n = prog.trainer.n_nodes

    def norms(state, key):
        x0 = reference.init_nodes(model, key, n)
        dx = jax.tree.map(lambda a, b: a - b, state.params, x0)
        return (_node_norms(dx), _node_projections(dx),
                _node_norms(state.x_hat), _node_norms(state.s))
    return jax.jit(norms)
