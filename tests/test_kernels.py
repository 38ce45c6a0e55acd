"""Per-kernel shape/dtype sweeps, assert_allclose against ref.py oracles
(interpret mode on CPU; kernels TARGET TPU tiles)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from optional_hypothesis import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.qsgd import qsgd_quantize, qsgd_dequantize
from repro.kernels.topk import block_topk_mask
from repro.kernels.ef_update import ef_gossip_update
from repro.kernels.flash_attention import flash_attention
from repro.launch.mesh import make_mesh

KEY = jax.random.PRNGKey(0)


def _tiles(seed, R, C=128, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), (R, C)) * scale


# -- qsgd ----------------------------------------------------------------------

@pytest.mark.parametrize("R", [8, 24, 64])
@pytest.mark.parametrize("s", [4, 16, 127])
def test_qsgd_kernel_matches_ref(R, s):
    x = _tiles(R + s, R)
    xi = jax.random.uniform(jax.random.PRNGKey(1), (R, 128))
    ck, sk = qsgd_quantize(x, xi, s)
    cr, sr = ref.qsgd_quantize_ref(x, xi, s)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_allclose(float(sk), float(sr), rtol=1e-6)
    yk = qsgd_dequantize(ck, sk)
    np.testing.assert_allclose(np.asarray(yk),
                               np.asarray(ref.qsgd_dequantize_ref(cr, sr)),
                               rtol=1e-6)


def test_qsgd_kernel_contraction():
    """Kernel output satisfies Assumption 1 with omega = 1/tau."""
    import math
    d = 64 * 128
    x = _tiles(7, 64, scale=2.0)
    errs = []
    for i in range(20):
        xi = jax.random.uniform(jax.random.PRNGKey(i), (64, 128))
        c, s = qsgd_quantize(x, xi, 16)
        q = qsgd_dequantize(c, s)
        errs.append(float(jnp.sum((q - x) ** 2)))
    tau = 1.0 + min(d / 256, math.sqrt(d) / 16)
    assert np.mean(errs) <= (1 - 1 / tau) * float(jnp.sum(x * x)) * 1.1


def test_qsgd_zero_vector():
    x = jnp.zeros((8, 128))
    xi = jnp.zeros((8, 128))
    c, s = qsgd_quantize(x, xi, 16)
    assert float(jnp.sum(jnp.abs(qsgd_dequantize(c, s)))) == 0.0


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 12), st.integers(2, 127), st.integers(0, 10 ** 6))
def test_qsgd_vector_roundtrip_hypothesis(blocks, s, seed):
    d = blocks * 997                     # deliberately unaligned
    x = jax.random.normal(jax.random.PRNGKey(seed), (d,))
    xi = jax.random.uniform(jax.random.PRNGKey(seed + 1), (d,))
    codes, scale = ops.qsgd_compress_vector(x, xi, s)
    y = ops.qsgd_decompress_vector(codes, scale)
    assert y.shape == x.shape
    # contraction (deterministic given xi: compare directly)
    assert float(jnp.sum((y - x) ** 2)) <= float(jnp.sum(x * x)) * 1.0 + 1e-6


# -- block top-k -----------------------------------------------------------------

@pytest.mark.parametrize("R", [8, 32])
@pytest.mark.parametrize("k", [1, 5, 64, 128])
def test_block_topk_matches_ref(R, k):
    x = _tiles(R * k, R)
    mk, tk = block_topk_mask(x, k)
    mr, tr = ref.block_topk_mask_ref(x, k)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    np.testing.assert_allclose(np.asarray(tk), np.asarray(tr), rtol=1e-6)


def test_block_topk_counts():
    x = _tiles(3, 8)
    mask, _ = block_topk_mask(x, 10)
    counts = np.asarray(jnp.sum(mask, axis=1))
    assert (counts >= 10).all() and (counts <= 12).all()


def test_block_topk_selects_largest():
    x = _tiles(4, 8)
    mask, _ = block_topk_mask(x, 4)
    mag = np.abs(np.asarray(x))
    for r in range(8):
        sel = mag[r][np.asarray(mask[r]) > 0]
        unsel = mag[r][np.asarray(mask[r]) == 0]
        assert sel.min() >= unsel.max() - 1e-6


def test_block_topk_contraction():
    """Blockwise top-k satisfies Assumption 1 with omega ~= k/C."""
    x = _tiles(11, 16, scale=3.0)
    q = x * block_topk_mask(x, 13)[0]
    lhs = float(jnp.sum((q - x) ** 2))
    assert lhs <= (1 - 13 / 128) * float(jnp.sum(x * x)) + 1e-5


# -- ef update -------------------------------------------------------------------

@pytest.mark.parametrize("R", [256, 1024])
def test_ef_update_matches_ref(R):
    args = [_tiles(i, R) for i in range(5)]
    k1 = ef_gossip_update(*args, 1 / 3, 1 / 3, 0.046)
    r1 = ref.ef_gossip_update_ref(*args, 1 / 3, 1 / 3, 0.046)
    for a, b in zip(k1, r1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10 ** 6),
       st.floats(0.0, 1.0), st.floats(0.0, 1.0), st.floats(0.0, 1.0))
def test_ef_update_vector_hypothesis(seed, ws, wn, g):
    d = 3000
    args = [jax.random.normal(jax.random.PRNGKey(seed + i), (d,))
            for i in range(5)]
    out_k = ops.ef_gossip_update_vector(*args, ws, wn, g)
    out_r = ref.ef_gossip_update_ref(*args, ws, wn, g)
    for a, b in zip(out_k, out_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# -- flash attention ---------------------------------------------------------------

@pytest.mark.parametrize("S,Dh", [(128, 64), (256, 128), (512, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(S, Dh, causal):
    B, H, KV = 1, 2, 1
    q = jax.random.normal(KEY, (B, S, H, Dh), jnp.float32) * 0.5
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, Dh)) * 0.5
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, Dh))
    o = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    kk = jnp.repeat(k, H // KV, 2)
    vv = jnp.repeat(v, H // KV, 2)
    o_ref = ref.flash_attention_ref(q, kk, vv, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_softcap_and_bf16():
    B, S, H, Dh = 1, 256, 2, 64
    q = (jax.random.normal(KEY, (B, S, H, Dh)) * 0.5).astype(jnp.bfloat16)
    k = (jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, Dh)) * 0.5).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, Dh)).astype(jnp.bfloat16)
    o = flash_attention(q, k, v, causal=True, softcap=30.0, block_q=64, block_k=64)
    o_ref = ref.flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                    v.astype(jnp.float32), causal=True, softcap=30.0)
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(o_ref),
                               rtol=0.1, atol=0.02)

# -- wire-format parity: int8/int16 codes vs the packing wire format -----------

@pytest.mark.parametrize("s", [1, 127, 128, 255])
def test_qsgd_codes_wire_dtype(s):
    """compress_bucket's wire rule: int8 codes up to s=127, int16 above
    (int8 would silently clamp large coordinates)."""
    from repro.kernels.qsgd import code_dtype, qsgd_quantize_codes
    x = _tiles(3, 8, scale=2.0)
    xi = jax.random.uniform(jax.random.PRNGKey(4), (8, 128))
    want = jnp.int8 if s <= 127 else jnp.int16
    assert code_dtype(s) == want
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    codes = qsgd_quantize_codes(x, xi, 1.0 / norm, s)
    assert codes.dtype == want
    ref_codes, _ = jax.jit(ref.qsgd_quantize_ref,
                           static_argnames="s")(x, xi, s)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(ref_codes))
    # extreme levels actually reached: one coordinate carries the whole norm
    spike = jnp.zeros((8, 128)).at[0, 0].set(3.0)
    codes = qsgd_quantize_codes(spike, jnp.zeros((8, 128)), 1.0 / 3.0, s)
    assert int(codes[0, 0]) == s


@settings(max_examples=12, deadline=None)
@given(st.integers(1, 3000), st.sampled_from([1, 16, 127, 255]),
       st.integers(0, 10 ** 6))
def test_qsgd_codes_pallas_matches_jitted_ref_hypothesis(d, s, seed):
    """Odd sizes + padding tails: pallas(interpret) codes over the padded
    tiles slice back to exactly the JITTED ref codes of the flat vector.
    Bit-exact comparisons are always against the jitted ref: the engine
    runs compiled, and eager jnp rounds FMA differently."""
    from repro.kernels import dispatch as kd
    x = jax.random.normal(jax.random.PRNGKey(seed), (d,))
    xi = jax.random.uniform(jax.random.PRNGKey(seed + 1), (d,))
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    inv_norm = jnp.where(norm == 0, 0.0, 1.0 / norm)
    got = kd.qsgd_codes(x, xi, inv_norm, s, backend="pallas")
    want = jax.jit(
        lambda x, xi: (jnp.sign(x)
                       * jnp.floor(jnp.abs(x) * inv_norm * s + xi)
                       ).astype(jnp.int8 if s <= 127 else jnp.int16))(x, xi)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qsgd_codes_zero_norm_bucket():
    """All-zero bucket: inv_norm = 0 must code to all-zero (both backends)."""
    from repro.kernels import dispatch as kd
    x = jnp.zeros((901,))
    xi = jax.random.uniform(jax.random.PRNGKey(2), (901,))
    for backend in ("jnp", "pallas"):
        codes = kd.qsgd_codes(x, xi, jnp.float32(0.0), 16, backend=backend)
        assert int(jnp.sum(jnp.abs(codes))) == 0


def test_sign_codes_parity():
    from repro.kernels import dispatch as kd
    x = jax.random.normal(jax.random.PRNGKey(5), (777,))
    want = jax.jit(ref.signnorm_codes_ref)(x)
    for backend in ("jnp", "pallas"):
        got = kd.sign_codes(x, backend=backend)
        assert got.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_qsgd_dequantize_bit_exact():
    codes = jnp.asarray(
        jax.random.randint(KEY, (8, 128), -127, 128), jnp.int8)
    scale = jnp.float32(0.037)
    got = qsgd_dequantize(codes, scale)
    want = jax.jit(ref.qsgd_dequantize_ref)(codes, scale)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_block_topk_mask_bit_exact_vs_jitted_ref():
    x = _tiles(9, 16, scale=2.0)
    mk, tk = block_topk_mask(x, 13)
    mr, tr = jax.jit(ref.block_topk_mask_ref, static_argnames="k")(x, k=13)
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(tk), np.asarray(tr))


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4000), st.integers(0, 10 ** 6))
def test_ef_update_pallas_bit_exact_vs_jitted_ref(d, seed):
    """Fused EF kernel == JITTED oracle, bitwise, on odd flat sizes (the
    padded tail stays exactly zero and is sliced off)."""
    args = [jax.random.normal(jax.random.PRNGKey(seed + i), (d,))
            for i in range(5)]
    got = ops.ef_gossip_update_vector(*args, 1 / 3, 1 / 3, 0.046)
    want = jax.jit(ref.ef_gossip_update_ref)(*args, 1 / 3, 1 / 3, 0.046)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ef_bucket_update_backends_match():
    from repro.kernels import dispatch as kd
    d = 1536
    args = [jax.random.normal(jax.random.PRNGKey(10 + i), (d,))
            for i in range(5)]
    outs = {bk: jax.jit(lambda *a, bk=bk: kd.ef_bucket_update(
                *a, 1 / 3, 1 / 3, 0.046, backend=bk))(*args)
            for bk in ("jnp", "pallas")}
    for a, b in zip(outs["jnp"], outs["pallas"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- dispatch ------------------------------------------------------------------

def test_resolve_backend_rules():
    from repro.kernels import dispatch as kd
    assert kd.resolve_backend("auto") in ("pallas", "jnp")
    assert kd.resolve_backend("jnp") == "jnp"
    with pytest.raises(ValueError):
        kd.resolve_backend("vulkan")
    with pytest.raises(ValueError):
        kd.resolve_backend("pallas", engine_eligible=False)
    # auto on an ineligible engine silently stays jnp (never raises)
    assert kd.resolve_backend("auto", engine_eligible=False) == "jnp"
    assert kd.resolve_backend("pallas") == "pallas"


def test_auto_never_picks_interpret_pallas():
    """'auto' selects pallas only where the kernels run compiled; on the
    CPU test toolchain (interpret-only) it must resolve to jnp."""
    from repro.kernels import dispatch as kd
    from repro.kernels import resolve_interpret
    if jax.default_backend() != "tpu":
        assert resolve_interpret(None) is True
        assert kd.resolve_backend("auto") == "jnp"
    else:
        assert resolve_interpret(None) is False
        assert kd.resolve_backend("auto") == "pallas"


def test_auto_on_tpu_raises_when_pallas_is_missing(monkeypatch):
    """On a TPU, 'auto' never drops silently to jnp: a Pallas that does
    not import is an error."""
    import builtins
    from repro.kernels import dispatch as kd
    real_import = builtins.__import__

    def no_pallas(name, globals=None, locals=None, fromlist=(), level=0):
        if name == "jax.experimental" and fromlist and "pallas" in fromlist:
            raise ImportError("pallas removed for this test")
        return real_import(name, globals, locals, fromlist, level)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(builtins, "__import__", no_pallas)
    with pytest.raises(RuntimeError, match="does not import"):
        kd.resolve_backend("auto")
    with pytest.raises(RuntimeError, match="does not import"):
        kd.resolve_backend("pallas")
    # an ineligible engine never needs the kernels
    assert kd.resolve_backend("auto", engine_eligible=False) == "jnp"


def test_dispatch_single_node_exchange_backends_agree():
    """Forced jnp vs forced pallas on a 2-bucket spec (in-process, 1-node
    mesh): identical round-1 wire state x_hat (bitwise) and ulp-close x/s
    through the fused bucket-space path.  One round only — later rounds
    quantize the ulp-drifted x, so x_hat stays bit-exact only for the
    round whose input state is shared (the wire witness)."""
    from jax.sharding import PartitionSpec as P
    from repro.comm.gossip import make_gossip_exchange
    from repro.core.compression import QSGD

    mesh = make_mesh((1, 1), ("data", "model"))
    params = {"a": jax.random.normal(jax.random.PRNGKey(1), (1, 300)),
              "b": jax.random.normal(jax.random.PRNGKey(2), (1, 4, 128))}
    specs = {"a": P("data", None), "b": P("data", None, "model")}
    outs = {}
    for bk in ("jnp", "pallas"):
        with mesh:
            ex = jax.jit(make_gossip_exchange(
                mode="choco", mesh=mesh, state_specs=specs, axis="data",
                compressor=QSGD(s=16), gamma=0.3, gossip_steps=1,
                kernel_backend=bk))
        outs[bk] = ex(jax.random.PRNGKey(3), params,
                      jax.tree.map(jnp.zeros_like, params),
                      jax.tree.map(jnp.zeros_like, params))
    for j, tol in ((0, 1e-6), (1, 0.0), (2, 1e-6)):   # x, x_hat, s
        for k in params:
            a = np.asarray(outs["jnp"][j][k])
            b = np.asarray(outs["pallas"][j][k])
            if tol == 0.0:
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, rtol=0, atol=tol)
