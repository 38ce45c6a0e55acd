"""Named stages of the gossip exchange, for the profiler.

Every engine opens these scopes around its stages, below the trainer's
``obs:exchange``:

* ``pack``      state trees (or deltas) into bucket buffers
* ``compress``  delta, selection, payload, own dense q
* ``mix``       neighbour sum: permutes, received payloads made dense,
                weights (the n = 1 placeholder too)
* ``ef_update`` the error-feedback update of x_hat, s and x
* ``unpack``    bucket buffers back to leaves

An engine without a stage does not open it; stages never nest.  The scopes
are ``jax.named_scope`` and land only in HLO op metadata, so they are off
unless the trainer traces its step under :func:`scoped` ``(True)``
(``make_train_step(phase_scopes=True)``): off, :func:`stage` is a null
context and the compiled step is byte-identical to an unscoped build.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Iterator

import jax

STAGES = ("pack", "compress", "mix", "ef_update", "unpack")

_ON = contextvars.ContextVar("exchange_stage_scopes", default=False)


@contextlib.contextmanager
def scoped(on: bool) -> Iterator[None]:
    """Open (``on``) or keep closed the stage scopes of every exchange
    traced inside the block."""
    token = _ON.set(bool(on))
    try:
        yield
    finally:
        _ON.reset(token)


def stage(name: str):
    """The named scope of one exchange stage, or a null context when the
    stage scopes are off."""
    assert name in STAGES, name
    return jax.named_scope(name) if _ON.get() else contextlib.nullcontext()
