"""Checkpoint round-trips, including CHOCO error-feedback state.

Fast tier: everything here runs on the single real CPU device (the sharded
format degenerates to one shard file, exercising the same manifest /
validation / bit-cast code paths).  Multi-device resume-exactness and
elastic restore live in test_checkpoint_distributed.py (slow/distributed).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointing import (restore_pytree, restore_sharded,
                                            save_pytree, save_sharded,
                                            load_metadata)
from repro.checkpoint.elastic import (consensus_warmup_rounds, elastic_ratio,
                                      remap_rows, source_rows)
from repro.launch.mesh import make_mesh
from repro.checkpoint.manifest import (ElasticRestoreError, ManifestError,
                                       ShardCoverageError, TreeMismatchError,
                                       is_sharded_checkpoint, read_manifest)


def _tree():
    return {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16),
                       "c": jnp.zeros((), jnp.int32)}}


# ---------------------------------------------------------------------------
# legacy flat npz
# ---------------------------------------------------------------------------

def test_roundtrip(tmp_path):
    tree = _tree()
    p = str(tmp_path / "ckpt")
    save_pytree(p, tree, metadata={"step": 7})
    got = restore_pytree(p, jax.eval_shape(lambda: tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert load_metadata(p)["step"] == 7


def test_trainstate_roundtrip(tmp_path):
    from repro.train.trainer import TrainState
    from repro.optim import sgd
    params = {"w": jnp.ones((3, 4))}
    st = TrainState(params=params,
                    x_hat=jax.tree.map(lambda x: x * 0.5, params),
                    s=jax.tree.map(lambda x: x * 0.1, params),
                    opt=sgd().init(params),
                    step=jnp.int32(42), key=jax.random.PRNGKey(1))
    p = str(tmp_path / "state")
    save_pytree(p, st, metadata={"step": 42})
    got = restore_pytree(p, jax.eval_shape(lambda: st))
    assert int(got.step) == 42
    np.testing.assert_allclose(np.asarray(got.x_hat["w"]), 0.5)
    np.testing.assert_allclose(np.asarray(got.s["w"]), 0.1)


def test_restore_pytree_typed_validation(tmp_path):
    """The bare `assert` (stripped under python -O) is gone: missing, extra,
    and shape-mismatched keys raise one TreeMismatchError enumerating all."""
    tree = _tree()
    p = str(tmp_path / "ckpt")
    save_pytree(p, tree)
    like = {"a": jax.ShapeDtypeStruct((3, 3), jnp.float32),       # wrong shape
            "nested": {"c": jax.ShapeDtypeStruct((), jnp.int32),  # b missing
                       "d": jax.ShapeDtypeStruct((2,), jnp.float32)}}  # new
    with pytest.raises(TreeMismatchError) as ei:
        restore_pytree(p, like)
    err = ei.value
    assert not isinstance(err, AssertionError) and not isinstance(err, KeyError)
    assert err.missing == ("nested__d",)
    assert err.extra == ("nested__b",)
    assert [m[0] for m in err.mismatched] == ["a"]
    for frag in ("nested__d", "nested__b", "(3, 3)", "(2, 3)"):
        assert frag in str(err), (frag, str(err))


# ---------------------------------------------------------------------------
# sharded manifest-driven format
# ---------------------------------------------------------------------------

def test_sharded_roundtrip_and_manifest(tmp_path):
    tree = _tree()
    d = str(tmp_path / "ck")
    save_sharded(d, tree, step=11,
                 fingerprint={"n_nodes": 2, "topology": "ring"},
                 metadata={"arch": "t"})
    assert is_sharded_checkpoint(d)
    man = read_manifest(d)
    assert man.step == 11 and man.n_nodes == 2
    assert man.fingerprint["topology"] == "ring"
    # true dtype recorded, bf16 bit-cast to uint16 on disk (not widened f32)
    assert man.leaves["nested__b"].dtype == "bfloat16"
    assert man.leaves["nested__b"].storage == "uint16"
    assert man.leaves["a"].shape == (2, 3)

    got = restore_sharded(d, jax.eval_shape(lambda: tree))
    assert got["nested"]["b"].dtype == jnp.bfloat16
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(got)):
        np.testing.assert_array_equal(
            np.asarray(a).reshape(-1).view(np.uint8),
            np.asarray(b).reshape(-1).view(np.uint8))


def test_sharded_restore_under_shardings(tmp_path):
    """Restore builds leaves directly under the target NamedShardings —
    degenerate 1-device mesh here; real 8-device placement is covered by the
    distributed suite."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = {"w": jnp.arange(12, dtype=jnp.float32).reshape(1, 12)}
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    d = str(tmp_path / "ck")
    save_sharded(d, jax.device_put(tree, shardings), step=0)
    got = restore_sharded(d, jax.eval_shape(lambda: tree), shardings)
    assert got["w"].sharding == shardings["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(tree["w"]))


def test_sharded_state_dtype_mismatch_regression(tmp_path):
    """bf16 manifest round-trip: restoring a bfloat16-state checkpoint into
    a float32 target (state_dtype drift) is a typed dtype error naming the
    leaf — never a silent cast of bit-cast uint16 payloads."""
    d = str(tmp_path / "ck")
    save_sharded(d, {"x_hat": jnp.ones((4,), jnp.bfloat16)}, step=0)
    like = {"x_hat": jax.ShapeDtypeStruct((4,), jnp.float32)}
    with pytest.raises(TreeMismatchError) as ei:
        restore_sharded(d, like)
    assert ("x_hat", "dtype", "bfloat16", "float32") in ei.value.mismatched


def test_sharded_missing_extra_shape_enumerated(tmp_path):
    d = str(tmp_path / "ck")
    save_sharded(d, _tree(), step=0)
    like = {"a": jax.ShapeDtypeStruct((9, 9), jnp.float32),
            "nested": {"b": jax.ShapeDtypeStruct((4,), jnp.bfloat16)},
            "zzz": jax.ShapeDtypeStruct((1,), jnp.int32)}
    with pytest.raises(TreeMismatchError) as ei:
        restore_sharded(d, like)
    err = ei.value
    assert err.missing == ("zzz",)
    assert err.extra == ("nested__c",)
    assert ("a", "shape", "(2, 3)", "(9, 9)") in err.mismatched


def test_sharded_incomplete_checkpoint(tmp_path):
    d = str(tmp_path / "nope")
    os.makedirs(d)
    assert not is_sharded_checkpoint(d)
    with pytest.raises(ManifestError):
        read_manifest(d)


def test_sharded_coverage_error(tmp_path):
    """A deleted shard file is a ShardCoverageError naming the leaf, not a
    zero-filled array."""
    d = str(tmp_path / "ck")
    tree = {"w": jnp.ones((4, 4), jnp.float32)}
    save_sharded(d, tree, step=0)
    for f in os.listdir(d):
        if f.endswith(".index.json"):
            os.remove(os.path.join(d, f))
    with pytest.raises(ShardCoverageError, match="w"):
        restore_sharded(d, jax.eval_shape(lambda: tree))


# ---------------------------------------------------------------------------
# elastic restore policy
# ---------------------------------------------------------------------------

def test_elastic_remap_policy():
    old = np.arange(4 * 3, dtype=np.float32).reshape(4, 3)
    # grow: cyclic tile, new[j] = old[j % n_old]
    grown = remap_rows(old, 8)
    np.testing.assert_array_equal(grown, old[np.arange(8) % 4])
    # shrink: strided mean, new[j] = mean(old[j::n_new])
    shrunk = remap_rows(old, 2)
    np.testing.assert_allclose(shrunk, np.stack([old[[0, 2]].mean(0),
                                                 old[[1, 3]].mean(0)]))
    # tile then shrink round-trips
    np.testing.assert_allclose(remap_rows(remap_rows(old, 8), 4), old)
    # source_rows agrees with remap_rows
    for j in range(8):
        assert source_rows(j, 4, 8) == (j % 4,)
    assert source_rows(1, 4, 2) == (1, 3)
    with pytest.raises(ElasticRestoreError):
        elastic_ratio(4, 6)


def test_elastic_restore_remap_and_reset(tmp_path):
    """Full elastic restore through the sharded reader: params re-mapped
    across the node dim, x_hat/s re-zeroed (old public copies are invalid
    under the new W)."""
    old = {"params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3)},
           "x_hat": {"w": jnp.full((2, 3), 7.0)},
           "s": {"w": jnp.full((2, 3), 3.0)},
           "step": jnp.int32(5)}
    d = str(tmp_path / "ck")
    save_sharded(d, old, step=5, fingerprint={"n_nodes": 2})
    like = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((4,) + tuple(l.shape[1:]), l.dtype)
        if l.ndim else jax.ShapeDtypeStruct((), l.dtype), old)
    got = restore_sharded(d, like, node_remap=(2, 4),
                          reset_prefixes=("x_hat", "s"))
    np.testing.assert_array_equal(
        got["params"]["w"], np.asarray(old["params"]["w"])[np.arange(4) % 2])
    assert not np.any(got["x_hat"]["w"]) and not np.any(got["s"]["w"])
    assert int(got["step"]) == 5


def test_elastic_reset_keys_exempt_from_dtype_check(tmp_path):
    """state_dtype change + elastic restore: x_hat/s are zero-filled in the
    TARGET dtype without reading saved bytes, so their saved dtype must not
    fail validation (params still validate strictly)."""
    old = {"params": {"w": jnp.ones((2, 3), jnp.float32)},
           "x_hat": {"w": jnp.ones((2, 3), jnp.bfloat16)}}
    d = str(tmp_path / "ck")
    save_sharded(d, old, step=0)
    like = {"params": {"w": jax.ShapeDtypeStruct((4, 3), jnp.float32)},
            "x_hat": {"w": jax.ShapeDtypeStruct((4, 3), jnp.float32)}}
    got = restore_sharded(d, like, node_remap=(2, 4),
                          reset_prefixes=("x_hat",))
    assert got["x_hat"]["w"].dtype == np.float32
    assert not np.any(got["x_hat"]["w"])


def test_consensus_warmup_rounds():
    # fully-connected mixes in one round; harder graphs need more, capped
    assert consensus_warmup_rounds(1.0) == 1
    assert consensus_warmup_rounds(0.5) < consensus_warmup_rounds(0.1)
    assert consensus_warmup_rounds(1e-6) == 64
    with pytest.raises(ElasticRestoreError):
        consensus_warmup_rounds(0.0)


# ---------------------------------------------------------------------------
# retention / GC (save_sharded keep_last)
# ---------------------------------------------------------------------------

def test_keep_last_gc_deletes_oldest(tmp_path):
    from repro.checkpoint.checkpointing import gc_checkpoints
    base = tmp_path / "ckpts"
    for step in (10, 20, 30):
        save_sharded(str(base / f"step{step}"), _tree(), step=step)
    # keep_last applied on the 4th save: only the newest 2 survive
    save_sharded(str(base / "step40"), _tree(), step=40, keep_last=2)
    kept = sorted(p.name for p in base.iterdir())
    assert kept == ["step30", "step40"], kept
    # every survivor is still a complete, restorable checkpoint
    for name in kept:
        restore_sharded(str(base / name), _tree())
    # idempotent: nothing more to delete
    assert gc_checkpoints(str(base), 2) == []


def test_keep_last_never_deletes_step_being_written(tmp_path):
    base = tmp_path / "ckpts"
    save_sharded(str(base / "step5"), _tree(), step=5)
    # keep_last=1 with the new save protected: the NEW dir survives even
    # though an adversarial ordering might sort it for deletion
    save_sharded(str(base / "step9"), _tree(), step=9, keep_last=1)
    assert sorted(p.name for p in base.iterdir()) == ["step9"]
    restore_sharded(str(base / "step9"), _tree())


def test_keep_last_ignores_torn_dirs_and_foreign_files(tmp_path):
    from repro.checkpoint.checkpointing import gc_checkpoints
    base = tmp_path / "ckpts"
    base.mkdir()
    (base / "torn").mkdir()                      # no manifest: never touched
    (base / "torn" / "shards-p00000.npz").write_bytes(b"x")
    (base / "notes.txt").write_text("keep me")
    save_sharded(str(base / "step1"), _tree(), step=1)
    save_sharded(str(base / "step2"), _tree(), step=2, keep_last=1)
    names = sorted(p.name for p in base.iterdir())
    assert names == ["notes.txt", "step2", "torn"], names
    with pytest.raises(ValueError, match="keep_last"):
        gc_checkpoints(str(base), 0)
