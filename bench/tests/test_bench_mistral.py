"""The Mistral-7B decoder the benchmark derives from the repo's LLaVA-NeXT
configuration: its file matches what the program builds, and it trains
through the trainer at a CPU size."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench import program

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config():
    with open(os.path.join(BENCH, "configs", "mistral-7b.json")) as f:
        return json.load(f)


def test_file_matches_the_repo_config_it_derives_from():
    from repro.configs.base import get_config
    c = config()
    repo = get_config(c["derived_from"]["repo_config"])
    built = program.model_config(c)          # raises on any other field
    assert c["reduced"] == {"num_hidden_layers": {"published": repo.n_layers,
                                                  "run": built.n_layers}}
    assert repo.n_layers == 32 and built.n_layers == 1
    for width in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                  "vocab_size", "rope_theta", "mlp_type", "tie_embeddings"):
        assert getattr(built, width) == getattr(repo, width), width
    assert built.family == "dense" and built.frontend is None
    assert "Mistral-7B" in c["source"]
    assert repo.source.endswith("llava-v1.6-mistral-7b-hf")


def test_trains_through_the_trainer_at_smoke_size():
    c = config()
    smoke = dict(c, derived_from=dict(c["derived_from"], smoke=True,
                                      overrides={**c["derived_from"]
                                                 ["overrides"],
                                                 "n_layers": 2}))
    from repro.configs.base import get_config
    cfg = dataclasses.replace(get_config("llava-next-mistral-7b", smoke=True),
                              **smoke["derived_from"]["overrides"])
    smoke["model"] = program.json_fields(cfg)
    traffic = {"nodes": 1, "mode": "choco", "topology": "ring",
               "compressor": "top_k", "fraction": 0.05,
               "gossip_engine": "packed", "kernel_backend": "auto",
               "exact_small_leaves": False, "state_dtype": "float32",
               "optimizer": "momentum", "seq_len": 32, "batch_per_node": 2}
    prog = program.build(smoke, traffic, phase_scopes=False)
    key = jax.random.PRNGKey(0)
    state = program.seeded_state(prog, smoke["model"], key)
    before = [np.asarray(a) for a in jax.tree.leaves(state.params)]
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(3):
        toks = rng.integers(0, cfg.vocab_size, (1, 2, 33), dtype=np.int32)
        state, mets = prog.step(state, {"tokens": jnp.asarray(toks[..., :-1]),
                                        "labels": jnp.asarray(toks[..., 1:])})
        losses.append(float(mets["loss"]))
    assert all(np.isfinite(losses)), losses
    after = [np.asarray(a) for a in jax.tree.leaves(state.params)]
    assert all(not np.array_equal(a, b) for a, b in zip(before, after))
