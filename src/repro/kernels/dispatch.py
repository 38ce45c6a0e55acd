"""Kernel-backend dispatch for the packed gossip hot path.

The packed CHOCO exchange has two memory-bound stages per bucket per
round: quantize the error-feedback delta into the wire codes (send
half) and integrate the dequantized self/neighbour payloads into the
``(x, x_hat, s)`` state (recv half — Algorithm 6's five full-size
reads and three writes).  This module picks, per exchange build, which
implementation runs them:

* ``"jnp"`` — the inline jnp expressions (the historical path; XLA's
  fusion decides how many HBM passes the EF update costs).
* ``"pallas"`` — the fused kernels in ``kernels/qsgd.py`` /
  ``kernels/ef_update.py``: one launch per bucket per direction.
* ``"auto"`` — ``pallas`` on a TPU for the eligible engine, ``jnp``
  everywhere else.  Interpret-mode pallas on CPU is a correctness tier,
  not a perf tier, so ``auto`` never selects it — tests force
  ``"pallas"`` explicitly to exercise it.  On a TPU a pallas that does
  not import is an error, never a silent drop to ``jnp``.

The kernels evaluate the same elementwise expressions, in the same
association order, as the jnp path: wire payloads are bit-identical
across backends, and the EF iterates agree to FMA-contraction rounding
(``tests/test_kernels.py`` + the distributed parity suite in
``tests/test_fused.py``).  The backend is therefore a pure execution
detail — it never enters the checkpoint fingerprint and resume across
backends is exact.

Module level stays jax-free; jax is imported only where a backend is
resolved or a kernel runs.
"""
from __future__ import annotations

#: Recognised values for ``ChocoConfig.kernel_backend`` / ``--kernel-backend``.
BACKENDS = ("auto", "pallas", "jnp")


def _require_pallas(why: str) -> None:
    try:
        from jax.experimental import pallas  # noqa: F401
    except ImportError as e:
        raise RuntimeError(
            f"{why}, but jax.experimental.pallas does not import: {e}") from e


def resolve_backend(requested: str, *, engine_eligible: bool = True) -> str:
    """Resolve a requested backend to the concrete one the engine runs.

    ``engine_eligible`` says whether the exchange being built is the
    packed choco engine the fused kernels are wired into (packed
    buckets, no topology process).  Forcing ``"pallas"`` on an
    ineligible engine raises; ``"auto"`` on an ineligible engine or off
    a TPU is ``"jnp"`` (pallas would run interpreted there — a debug
    tier, not a perf win).
    """
    if requested not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {requested!r}; expected one of {BACKENDS}")
    if requested == "jnp":
        return "jnp"
    if requested == "pallas":
        if not engine_eligible:
            raise ValueError(
                "kernel_backend='pallas' is wired into the packed static "
                "choco engine only (mode=choco, packed buckets, no topology "
                "process); use 'auto' or 'jnp' here")
        _require_pallas("kernel_backend='pallas' requested")
        return "pallas"
    import jax
    if not engine_eligible or jax.default_backend() != "tpu":
        return "jnp"
    _require_pallas("kernel_backend='auto' on a TPU resolves to pallas")
    return "pallas"


# ---------------------------------------------------------------------------
# fused ops — one entry point per hot-path stage, dispatched on backend
# ---------------------------------------------------------------------------

def qsgd_codes(buf32, xi, inv_norm, s: int, *, backend: str):
    """QSGD wire codes for one packed bucket buffer (send half).

    ``buf32`` is the flat f32 delta, ``xi`` the uniform dither drawn on
    the same shape, ``inv_norm`` the precomputed ``1/||buf||`` (0 for a
    zero bucket — computed once on the unpadded buffer so both backends
    share the exact reduction).  Returns int8 codes for ``s <= 127``,
    int16 above, matching ``packing.compress_bucket``'s wire format.
    The pallas path pads to (rows, 128) tiles, runs the fused
    quantize kernel, and slices the tail; padded lanes quantize to
    code 0 (x == xi == 0 there), so the slice is exact.
    """
    if backend == "pallas":
        from repro.kernels.ops import _to_tiles
        from repro.kernels.qsgd import qsgd_quantize_codes
        xt, d = _to_tiles(buf32)
        xit, _ = _to_tiles(xi)
        codes = qsgd_quantize_codes(xt, xit, inv_norm, s)
        return codes.reshape(-1)[:d]
    import jax.numpy as jnp
    level = jnp.floor(jnp.abs(buf32) * inv_norm * s + xi)
    ctype = jnp.int8 if s <= 127 else jnp.int16
    return (jnp.sign(buf32) * level).astype(ctype)


def sign_codes(buf32, *, backend: str):
    """SignNorm int8 wire codes for one packed bucket buffer."""
    if backend == "pallas":
        from repro.kernels.ops import _to_tiles
        from repro.kernels.qsgd import signnorm_codes
        xt, d = _to_tiles(buf32)
        codes = signnorm_codes(xt)
        return codes.reshape(-1)[:d]
    import jax.numpy as jnp
    return jnp.sign(buf32).astype(jnp.int8)


def ef_bucket_update(x_half, x_hat, s, q_self, q_nbr, w_self, w_nbr, gamma,
                     *, backend: str):
    """Fused CHOCO EF integrate for one flat f32 bucket (recv half).

    One sweep producing the Algorithm 5/6 update::

        x_hat' = x_hat + q_self
        s'     = s + (w_self * q_self + w_nbr * q_nbr)
        x'     = x_half + gamma * (s' - x_hat')

    Returns ``(x', x_hat', s')``.  The pallas path is a single kernel
    launch (5 reads, 3 writes); the jnp path spells out the identical
    expressions — same association, so XLA cannot reorder them apart
    and the backends stay bit-exact.
    """
    if backend == "pallas":
        from repro.kernels.ops import ef_gossip_update_vector
        return ef_gossip_update_vector(
            x_half, x_hat, s, q_self, q_nbr, w_self, w_nbr, gamma)
    x_hat_n = x_hat + q_self
    s_n = s + (w_self * q_self + w_nbr * q_nbr)
    x_n = x_half + gamma * (s_n - x_hat_n)
    return x_n, x_hat_n, s_n
