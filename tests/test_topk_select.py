"""Exact top-k by threshold select (kernels/ops.topk_threshold_select) and
the packed TopK path that runs it (comm/packing.compress_bucket).

The reference is ``lax.top_k`` on the magnitudes: the same index set,
equal magnitudes resolved to the lower index, and so a bitwise-equal
dense q.  The threshold select lists its indices in ascending order."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import packing
from repro.comm.packing import (bucket_selection, bucket_wire_bits,
                                compress_bucket, make_bucket_spec)
from repro.core.compression import (BlockTopK, PackedSparsePayload, QSGD,
                                    SparsePayload, TopK)
from repro.kernels import ops
from repro.kernels.ops import block_topk_select, topk_threshold_select


def _gauss(shape, seed=0):
    return np.random.default_rng(seed).standard_normal(shape).astype(np.float32)


def _few_nonzeros():
    x = _gauss((2, 1000))
    x[:, 5:] = 0.0
    x[1, 500] = -0.0
    return x, 40


def _quantised():
    return np.round(_gauss((3, 1024), 1) * 2) / 2, 100


def _signed_zeros():
    x = np.zeros((2, 256), np.float32)
    x[0, ::3] = -0.0
    x[1, 7] = -0.0
    x[1, 9] = 1.0
    x[1, 200] = -2.0
    return x, 10


def _some_rows_tied():
    x = _gauss((4, 512), 2)
    x[1, :100] = 3.0              # 100 equal magnitudes, 20 kept
    x[3, 50:60] = -3.0            # 10 equal, 10 kept ...
    x[3, 300:310] = 3.0           # ... of 20
    return x, 20


CASES = {
    "gaussian": lambda: (_gauss((3, 1024)), 17),
    "fewer_than_k_nonzeros": _few_nonzeros,
    "quantised_ties": _quantised,
    "signed_zeros": _signed_zeros,
    "k_1": lambda: (_gauss((2, 384), 3), 1),
    "k_N": lambda: (_gauss((2, 384), 4), 384),
    "some_rows_tied": _some_rows_tied,
    "width_not_lane_multiple": lambda: (_gauss((1, 300), 5), 7),
    # 8 mantissa bits: ties at the threshold come with the format
    "bfloat16": lambda: (jnp.asarray(_gauss((2, 512), 8), jnp.bfloat16), 30),
}


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int16 if a.dtype.itemsize == 2 else np.int32)


def _dense(shape, values, indices):
    R = shape[0]
    return jnp.zeros(shape, values.dtype).at[
        jnp.arange(R)[:, None], indices].set(values)


def _ref(x, k):
    _, idx = jax.lax.top_k(jnp.abs(x), k)
    return jnp.take_along_axis(x, idx, axis=1), idx


@pytest.mark.parametrize("case", list(CASES))
def test_threshold_select_matches_lax_top_k(case):
    x, k = CASES[case]()
    x = jnp.asarray(x)
    values, indices = topk_threshold_select(x, k)
    ref_values, ref_indices = _ref(x, k)
    assert values.shape == ref_values.shape and values.dtype == x.dtype
    assert indices.shape == ref_indices.shape and indices.dtype == jnp.int32
    for got, want in zip(np.asarray(indices), np.asarray(ref_indices)):
        assert set(got.tolist()) == set(want.tolist())
    assert np.all(np.diff(np.asarray(indices), axis=1) > 0)
    np.testing.assert_array_equal(
        _bits(_dense(x.shape, values, indices)),
        _bits(_dense(x.shape, ref_values, ref_indices)))


# -- the tie branch ------------------------------------------------------------
# One shape and k for every case, so that the eager runs share their ops.

def _tie_case(case):
    x = _gauss((4, 512), 9)
    if case == "exact_fill":
        # t = 3: two magnitudes above it and exactly the two more it needs
        x = np.zeros((4, 512), np.float32)
        x[:, 100:] = 1.0
        x[:, [4, 9, 20, 70]] = [5.0, -5.0, 3.0, -3.0]
        return x, 4
    if case == "fewer_than_k_nonzeros":
        x[:, 10:] = 0.0
        x[2, 300] = -0.0
    elif case == "quantised_ties":
        x = np.round(x * 2) / 2
    elif case == "signed_zeros":
        x[:, 15:] = np.where(np.arange(497) % 3, 0.0, -0.0)
    elif case == "some_rows_tied":
        x[1, :100] = 3.0
        x[3, 50:60] = -3.0
        x[3, 300:310] = 3.0
    return x, 20


@pytest.mark.parametrize("case, surplus", [
    ("gaussian", False), ("exact_fill", False),
    ("fewer_than_k_nonzeros", True), ("quantised_ties", True),
    ("signed_zeros", True), ("some_rows_tied", True)])
def test_tie_branch_runs_exactly_on_surplus(monkeypatch, case, surplus):
    x, k = _tie_case(case)
    calls = []
    tie_cut = ops._tie_cut
    monkeypatch.setattr(ops, "_tie_cut",
                        lambda *a: calls.append(1) or tie_cut(*a))
    with jax.disable_jit():          # lax.cond then runs only its branch
        values, indices = topk_threshold_select(jnp.asarray(x), k)
        ref_values, ref_indices = _ref(jnp.asarray(x), k)
    assert bool(calls) == surplus
    np.testing.assert_array_equal(
        _bits(_dense(x.shape, values, indices)),
        _bits(_dense(x.shape, ref_values, ref_indices)))


@pytest.mark.parametrize("k", [1, 20, 512])
def test_tie_branch_agrees_on_tie_free_rows(monkeypatch, k):
    x = jnp.asarray(_gauss((4, 512), 9))
    with jax.disable_jit():
        plain = topk_threshold_select(x, k)
        monkeypatch.setattr(jax.lax, "cond",
                            lambda pred, tie, no_tie, *args: tie(*args))
        forced = topk_threshold_select(x, k)
    for a, b in zip(plain, forced):
        np.testing.assert_array_equal(_bits(a), _bits(b))


# -- the packed TopK path ------------------------------------------------------

def _bucket(data):
    leaves = [jnp.asarray(_gauss((3000,), 6)), jnp.asarray(_gauss((700,), 7))]
    if data == "quantised":
        leaves = [jnp.round(v * 2) / 2 for v in leaves]
    spec = make_bucket_spec(leaves)
    assert spec.n_buckets == 1
    buf = packing.pack_leaves(spec, leaves)[0]
    return spec, buf


@pytest.mark.parametrize("data", ["gaussian", "quantised"])
@pytest.mark.parametrize("branch", ["single_row", "oversized"])
def test_compress_bucket_topk_matches_sort(monkeypatch, branch, data):
    """Both TopK branches of compress_bucket keep lax.top_k's coordinates
    and ship payloads of the sort path's shapes, so wire bits are equal."""
    spec, buf = _bucket(data)
    bucket, slots = spec.buckets[0], spec.bucket_slots(0)
    comp = TopK(fraction=0.05)
    k = packing._slot_budget(comp, slots, bucket)
    if branch == "oversized":
        monkeypatch.setattr(packing, "MAX_BUCKET_ELEMS", 1024)
        rows = -(-buf.size // 1024)
        kb = -(-k // rows)
        ref = PackedSparsePayload(*block_topk_select(buf, kb, block=1024),
                                  buf.size, 1024)
    else:
        _, idx = jax.lax.top_k(jnp.abs(buf), k)
        ref = SparsePayload(buf[idx], idx.astype(jnp.int32), buf.size)
    got = compress_bucket(comp, None, buf, bucket, slots)
    assert type(got) is type(ref)
    assert got.values.shape == ref.values.shape
    assert got.indices.shape == ref.indices.shape
    assert got.indices.dtype == ref.indices.dtype == jnp.int32
    assert got.wire_bits() == ref.wire_bits()
    idx = np.asarray(got.indices).reshape(-1, got.indices.shape[-1])
    assert np.all(np.diff(idx, axis=1) > 0)
    np.testing.assert_array_equal(_bits(got.dense()), _bits(ref.dense()))


def test_bucket_wire_bits_unchanged():
    """Wire accounting is the per-slot budget: k values and k int32
    indices per slot (ceil(5% of 3000) + ceil(5% of 700) = 150 + 35)."""
    spec, _ = _bucket("gaussian")
    assert bucket_wire_bits(spec, TopK(fraction=0.05)) == [(150 + 35) * 64]


def test_bucket_selection_names_the_path(monkeypatch):
    spec, _ = _bucket("gaussian")
    assert bucket_selection(spec, TopK(fraction=0.05)) == [
        {"selection": "threshold", "rows": 1, "k": 185}]
    monkeypatch.setattr(packing, "MAX_BUCKET_ELEMS", 1024)
    assert bucket_selection(spec, TopK(fraction=0.05)) == [
        {"selection": "threshold", "rows": 4, "k": 47}]
    assert bucket_selection(spec, BlockTopK(k_per_block=2)) == [
        {"selection": "sort", "rows": 30, "k": 2}]
    assert bucket_selection(spec, QSGD(16)) == [None]
