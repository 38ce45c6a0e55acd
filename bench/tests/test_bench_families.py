"""The model's yardstick is found by its configuration's ``family``
(``bench/families``): counts, weights, the reference's loss and the
program's state all go through the family's module, so a family is added
by a new file alone."""
import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import counts, families, program, reference
from bench.families import dense

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)["model"]


@pytest.mark.parametrize("name", ["qwen3-1.7b", "mistral-7b"])
def test_dense_is_what_counts_and_reference_use(name, monkeypatch):
    m = model(name)
    assert families.load("dense") is dense
    assert counts.param_shapes(m) == dense.param_shapes(m)
    assert counts.flops_per_token(m, 4096) == dense.flops_per_token(m, 4096)
    seen = []
    monkeypatch.setattr(dense, "init_leaf",
                        lambda path, shape, key: seen.append(path)
                        or jnp.zeros(shape))
    monkeypatch.setattr(dense, "param_shapes", lambda m: {"w": (2, 3)})
    assert counts.leaf_shapes(m) == [("w", (2, 3))]
    weights = reference.init_node(m, jax.random.PRNGKey(0), 0)
    assert jax.tree.map(jnp.shape, weights) == {"w": (2, 3)}
    assert seen == ["w"]


def test_unknown_family_names_its_file():
    with pytest.raises(ValueError, match=r"bench/families/nosuch\.py"):
        families.load("nosuch")
    m = dict(model("qwen3-1.7b"), family="nosuch")
    with pytest.raises(ValueError, match=r"bench/families/nosuch\.py"):
        counts.n_params(m)
    with pytest.raises(ValueError, match=r"bench/families/nosuch\.py"):
        reference.Reference(m, {"nodes": 1, "fraction": 0.1}, 0.1,
                            jax.devices()[:1])


def test_family_check_refuses_what_it_does_not_describe():
    with pytest.raises(ValueError, match="dense decoder only"):
        dense.check(dict(model("qwen3-1.7b"), sliding_window=4096))
    dense.check(model("mistral-7b"))


def _toy():
    """A family of one embedding and a head: logits = emb[tokens] @ head."""
    toy = types.ModuleType("bench.families.toy")
    toy.checked = []
    toy.check = toy.checked.append
    toy.param_shapes = lambda m: {"emb": (m["vocab_size"], m["d_model"]),
                                  "head": {"w": (m["d_model"],
                                                 m["vocab_size"])}}
    toy.init_leaf = lambda path, shape, key: \
        jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[0])
    toy.flops_per_token = lambda m, s: 6.0 * m["d_model"] * m["vocab_size"]

    def loss(params, tokens, labels, m, mm, valid=None):
        logits = mm("bsd,dv->bsv", params["emb"][tokens], params["head"]["w"])
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]
        valid = jnp.ones(tokens.shape) if valid is None else valid
        return jnp.sum(nll * valid) / jnp.sum(valid)
    toy.loss = loss
    return toy


def test_a_new_family_needs_only_its_module(monkeypatch):
    toy = _toy()
    monkeypatch.setitem(sys.modules, "bench.families.toy", toy)
    m = {"family": "toy", "vocab_size": 300, "d_model": 8}
    assert counts.leaf_shapes(m) == [("emb", (300, 8)), ("head/w", (8, 300))]
    assert counts.n_params(m) == 4800
    assert counts.flops_per_token(m, 16) == 6.0 * 2400
    sizes = [math.prod(s) for _, s in counts.leaf_shapes(m)]
    assert [b["slots"] for b in counts.bucket_plan(sizes, 0.1)] == \
        [[(0, 0, 2400), (1, 2432, 2400)]]
    assert counts.kept_per_node(m, 0.1) == 480
    w = reference.init_nodes(m, jax.random.PRNGKey(1), 2)
    assert w["emb"].shape == (2, 300, 8)
    assert w["head"]["w"].shape == (2, 8, 300)
    assert not np.array_equal(w["emb"][0], w["emb"][1])

    # the reference runs the family's loss and check, at HIGHEST and as
    # the control, through the same code as every other family
    ref = reference.Reference(m, {"nodes": 1, "fraction": 0.1}, 0.1,
                              jax.devices()[:1])
    assert toy.checked == [m]
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 300, (1, 2, 17), dtype=np.int32)
    batch = [{"tokens": toks[..., :-1], "labels": toks[..., 1:]}]
    plain = ref.run(jax.random.PRNGKey(1), batch)
    assert np.isfinite(plain["loss"][0])
    assert plain["grad"].shape == (1, 2) and np.all(plain["grad"] > 0)
    low = ref.run(jax.random.PRNGKey(1), batch, control=True)
    assert low["grad"][0, 1] != plain["grad"][0, 1]


def test_program_state_is_checked_against_the_family(monkeypatch):
    """The harness refuses a program whose parameters are not the tree
    the configuration's family describes, and names that family."""
    monkeypatch.setitem(sys.modules, "bench.families.toy", _toy())
    m = {"family": "toy", "vocab_size": 300, "d_model": 8}
    shape = types.SimpleNamespace(
        params={"emb": jax.ShapeDtypeStruct((1, 300, 8), jnp.float32)})
    trainer = types.SimpleNamespace(state_shape=lambda: shape, n_nodes=1)
    prog = program.Program(trainer=trainer, step=None, mesh=None)
    with pytest.raises(ValueError, match=r"'toy'.*bench/families/toy\.py"):
        program.seeded_state(prog, m, jax.random.PRNGKey(0))
