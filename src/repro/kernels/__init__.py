"""Pallas TPU kernels for the gossip hot path, with pure-jnp oracles.

Layout (the xformers-style kernel/reference discipline):

* ``qsgd.py`` / ``topk.py`` / ``ef_update.py`` / ``flash_attention.py``
  — tiled Pallas kernels for the bandwidth-bound stages (quantize to
  wire codes, block top-k mask, fused CHOCO error-feedback update,
  attention).
* ``ops.py`` — jit'd shape-polymorphic wrappers over flat vectors
  (pad + reshape to (rows, 128) tiles internally).
* ``ref.py`` — bit-exact pure-jnp oracles; every kernel is held to
  parity with its oracle in ``tests/test_kernels.py``.
* ``dispatch.py`` — backend resolution (``auto``/``pallas``/``jnp``)
  and the fused entry points the packed gossip engine calls.

OPTIONAL layer by repo convention: add kernels only for compute
hot-spots the reproduction actually optimizes.
"""


def resolve_interpret(interpret):
    """Interpret mode for a kernel call: compiled on a TPU, interpreted on
    any other backend, unless the caller says otherwise.  Every kernel
    entry point defaults to ``interpret=None`` and resolves it here, so on
    a TPU no caller gets the interpreter by omission."""
    if interpret is None:
        import jax
        return jax.default_backend() != "tpu"
    return bool(interpret)
