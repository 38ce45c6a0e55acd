"""The numbers that decide ``correct``: program against reference.

Both sides follow the same three steps from the same weights and rows.
Each side gives the loss of every step (mean over nodes) and, per node and
parameter leaf, the norm of the first gradient as the optimizer holds it
(its momentum after one step, which started at zero), and after the last
step the norms of the parameters' change, of x_hat and of s.

* ``loss_gap``: the largest relative gap of a step's loss.
* ``grad_proj_gap``, ``step_proj_gap``: the first gradient and the
  parameters' change projected, leaf by leaf, on four fixed pseudo-random
  sign vectors (:func:`projections`); the largest gap of a projection over
  the reference's norm of that leaf or of the median leaf.  A norm moves
  only to second order under rounding noise, a projection to first
  order, so these are the numbers a computation in a lower precision
  fails.
* ``grad_gap``, ``step_gap``, ``hat_gap``, ``s_gap``: by the worst leaf,
  the gap between the program's norm and the reference's, over the
  reference's norm of that leaf or of the median leaf, whichever is
  larger.  ``step_gap``, ``hat_gap`` and ``s_gap`` leave out leaves whose
  reference gradient is under a thousandth of the median leaf's (none
  are, in the dense decoder).  ``s_gap`` exists with neighbours only: on
  one node s equals x_hat.
"""
from __future__ import annotations

from typing import Dict

import jax.numpy as jnp
import numpy as np

#: a leaf whose reference gradient is under this share of the median
#: leaf's moves by round-off alone and is left out of the change
NEGLIGIBLE = 1e-3


#: sign vectors each leaf is projected on
N_SIGNS = 4


def _signs(size: int, k: int):
    """A fixed pseudo-random +-1 vector of ``size`` (an integer hash of the
    position, so that it fuses into the reduction and is never stored)."""
    h = jnp.arange(size, dtype=jnp.uint32) * jnp.uint32(0x9E3779B1) \
        + jnp.uint32(0x7F4A7C15 * (k + 1) & 0xFFFFFFFF)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return jnp.where((h & 1) == 1, 1.0, -1.0).astype(jnp.float32)


def projections(flat):
    """(N_SIGNS,) projections of a flat f32 vector on the sign vectors."""
    return jnp.stack([jnp.sum(flat * _signs(flat.size, k))
                      for k in range(N_SIGNS)])


def _leaf_gap(prog, ref, keep) -> float:
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    denom = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / denom
    return float(np.max(gap[keep]))


def gaps(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The compared numbers, by name."""
    loss_p, loss_r = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    out = {"loss_gap": float(np.max(np.abs(loss_p - loss_r)
                                    / np.abs(loss_r)))}
    grad = np.asarray(ref["grad"], float)
    everything = np.ones(grad.shape, bool)
    moved = grad >= NEGLIGIBLE * np.median(grad)
    out["grad_gap"] = _leaf_gap(prog["grad"], grad, everything)
    names = ["step", "hat"] + (["s"] if grad.shape[0] > 1 else [])
    for name in names:
        out[name + "_gap"] = _leaf_gap(prog[name], ref[name], moved)
    for name, keep in (("grad", everything), ("step", moved)):
        norm = np.asarray(ref[name], float)
        denom = np.maximum(norm, np.median(norm))[..., None]
        gap = np.abs(np.asarray(prog[name + "_proj"], float)
                     - np.asarray(ref[name + "_proj"], float)) / denom
        out[name + "_proj_gap"] = float(np.max(gap[keep]))
    return out


def judge(values: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every number that has a limit is finite and within it.
    A number a cell's limits leave out is read and printed, not compared;
    a cell with no limits is never correct."""
    return bool(limits) and all(np.isfinite(values[k]) and values[k] <= v
                                for k, v in limits.items())
