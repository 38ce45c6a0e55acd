"""Stage scopes inside the gossip exchange (comm/stages.py).

With ``phase_scopes=True`` the train step's HLO carries, below
``obs:exchange``, the scope of every stage the engine has (``pack``,
``compress``, ``mix``, ``ef_update``, ``unpack``); with it off no ``obs:``
string appears, and the compiled step differs from the scoped one only in
op metadata.  One-node engines build in process on the one CPU device;
the engines that need neighbours (pipelined, replica, bounded staleness)
lower on 4 host devices in a subprocess.
"""
import contextlib
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.comm import stages
from repro.configs.base import ChocoConfig, get_config
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import constant_schedule, make_optimizer
from repro.train.trainer import DecentralizedTrainer

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
ALL = {"pack", "compress", "mix", "ef_update", "unpack"}

#: engine -> (ChocoConfig overrides, the stages it has)
ENGINES = {
    "packed-jnp": (dict(kernel_backend="jnp"), ALL),
    "fused-pallas": (dict(kernel_backend="pallas"), ALL),
    "per-leaf": (dict(packed_gossip=False, kernel_backend="jnp"),
                 {"compress", "mix", "ef_update"}),
}


def _trainer(**choco):
    cfg = get_config("qwen3-1.7b", smoke=True)
    return DecentralizedTrainer(
        model=build_model(cfg),
        choco=ChocoConfig(compressor="top_k",
                          comp_kwargs=(("fraction", 0.01),),
                          gossip_axis="data", state_dtype="float32", **choco),
        mesh=make_mesh((1, 1), ("data", "model")), n_nodes=1,
        optimizer=make_optimizer("sgd"), lr_fn=constant_schedule(0.01),
        mode="choco")


def _lowered(engine, phase_scopes):
    tr = _trainer(**ENGINES[engine][0])
    shape = tr.state_shape()
    batch = {k: jax.ShapeDtypeStruct((1, 1, 16), jnp.int32)
             for k in ("tokens", "labels")}
    return tr.jitted_train_step(shape, batch,
                                phase_scopes=phase_scopes).lower(shape, batch)


def scope_names(lowered):
    """Name-stack paths of the lowered step's ops (their MLIR locations)."""
    return re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))


def stages_in(names):
    """Stages named as a path component below obs:exchange."""
    found = set()
    for name in names:
        parts = name.split("/")
        if "obs:exchange" in parts:
            found |= ALL & set(parts[parts.index("obs:exchange") + 1:])
    return found


@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_stage_scopes_in_the_step(engine):
    names = scope_names(_lowered(engine, True))
    assert stages_in(names) == ENGINES[engine][1]
    # stages never nest in one another
    for name in names:
        assert len(ALL & set(name.split("/"))) <= 1, name


@pytest.mark.parametrize("engine", ["packed-jnp", "fused-pallas"])
def test_scopes_off_leave_no_obs_name(engine):
    assert "obs:" not in _lowered(engine, False).compile().as_text()


@pytest.mark.parametrize("engine", ["packed-jnp", "fused-pallas"])
def test_scopes_change_only_metadata(engine):
    strip = lambda s: re.sub(r", metadata=\{[^}]*\}", "", s)
    # built from one line, so that the source locations match too
    on, off = (_lowered(engine, scoped).compile().as_text()
               for scoped in (True, False))
    assert "obs:exchange/" in on
    assert strip(on) == strip(off)


def test_stage_is_a_null_context_unless_scoped():
    off = lambda: isinstance(stages.stage("mix"), contextlib.nullcontext)
    assert off()
    with stages.scoped(True):
        assert not off()
        with stages.scoped(False):
            assert off()
        assert not off()
    assert off()
    with pytest.raises(AssertionError):
        stages.stage("exchange")


#: engines that need neighbours -> (ChocoConfig overrides, their stages,
#: whether the stages sit inside lax.switch branches)
MESH_ENGINES = {
    "pipelined": (dict(pipeline_gossip=True), ALL, False),
    "matching": (dict(topology_process="matching"), ALL, True),
    "linkfail-per-leaf": (dict(topology_process="linkfail",
                               packed_gossip=False),
                          {"compress", "mix", "ef_update"}, False),
    "staleness": (dict(topology_process="staleness"), ALL, False),
}


@pytest.mark.slow
@pytest.mark.distributed
@pytest.mark.parametrize("engine", sorted(MESH_ENGINES))
def test_stage_scopes_in_mesh_engines(engine):
    overrides, want, in_branch = MESH_ENGINES[engine]
    script = textwrap.dedent(f"""
        import os, re
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, {SRC!r})
        import jax, jax.numpy as jnp
        from repro.configs.base import ChocoConfig, get_config
        from repro.launch.mesh import make_mesh
        from repro.models import build_model
        from repro.optim import constant_schedule, make_optimizer
        from repro.train.trainer import DecentralizedTrainer
        tr = DecentralizedTrainer(
            model=build_model(get_config("qwen3-1.7b", smoke=True)),
            choco=ChocoConfig(compressor="top_k",
                              comp_kwargs=(("fraction", 0.05),),
                              gossip_axis="data", **{overrides!r}),
            mesh=make_mesh((4, 1), ("data", "model")), n_nodes=4,
            optimizer=make_optimizer("sgd"),
            lr_fn=constant_schedule(0.01), mode="choco")
        shape = tr.state_shape()
        batch = {{k: jax.ShapeDtypeStruct((4, 1, 16), jnp.int32)
                 for k in ("tokens", "labels")}}
        step = lambda on: tr.jitted_train_step(shape, batch,
                                               phase_scopes=on)
        # a shard_map body lowers to a function of its own, whose MLIR
        # locations are relative: the compiled op_names hold full paths
        print(re.findall(r'op_name="([^"]*)"',
                         step(True).lower(shape, batch).compile().as_text()))
        print(re.findall(r'loc\\("([^"]*)"', step(False).lower(
            shape, batch).as_text(debug_info=True)))
    """)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, r.stderr
    on, off = (eval(line) for line in r.stdout.splitlines())
    assert stages_in(on) == want
    for name in on:
        assert len(ALL & set(name.split("/"))) <= 1, name
    # a stage inside a branch: a branch component above the stage's (a
    # lax.cond inside a stage, as top-k's tie branch, does not count)
    branch = any("branch" in part and ALL & set(name.split("/")[i + 1:])
                 for name in on
                 for i, part in enumerate(name.split("/")))
    assert branch == in_branch
    assert not any("obs:" in name for name in off)
