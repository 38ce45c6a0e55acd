"""The one place that builds a device ``Mesh``.

Every mesh in the program, its tests, benchmarks and examples comes from
:func:`make_mesh`.  Axes are typed ``AxisType.Auto``: since jax 0.9
``jax.make_mesh`` defaults to Explicit axes, under which the trainer's
per-node ``jax.vmap`` over the sharded node dimension rejects an unsharded
batch ("Mapped away dimension ... data vs None") and jit asks for a
``jax.set_mesh`` context.  Auto axes keep sharding a compiler decision
driven by the in/out shardings, which is what the trainer and the gossip
``shard_map`` are written for.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...],
              devices: Optional[Sequence] = None):
    """Mesh of ``shape`` over ``axes`` with Auto axis types.

    ``devices`` defaults to ``jax.devices()``; pass an explicit list (e.g.
    the ``devices`` of a described TPU topology) to build a mesh over
    devices this process is not attached to."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_default_mesh():
    """What the launcher runs on without ``--mesh``: every visible device
    is one gossip node, ``(data=len(jax.devices()), model=1)``."""
    return make_mesh((len(jax.devices()), 1), ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips.  Used by the dry
    run (``launch/dryrun.py``) on simulated host devices only."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def gossip_axis_for(mesh) -> str:
    """Default gossip placement: 'pod' when present, else 'data'."""
    return "pod" if "pod" in mesh.axis_names else "data"
