"""Pallas TPU kernel: fused CHOCO error-feedback gossip update.

The per-step state update (Algorithm 6 lines 8-10) touches FIVE full-size
streams (x_half, x_hat, s, q_self, q_nbr) and writes THREE (x, x_hat, s) —
at 3 x N parameters of state this is the memory-bound hot loop of CHOCO-SGD.
Unfused, XLA may issue it as several passes; this kernel does one
HBM->VMEM->HBM sweep per tile:

    x_hat' = x_hat + q_self
    s'     = s + (w_self q_self + w_nbr q_nbr)
    x'     = x_half + gamma (s' - x_hat')

The s' parenthesization is load-bearing: it matches the association the
engine's jnp leaf path uses (comm/gossip.py::_choco_leaf_updates), and
XLA does not reassociate floats — so any residual cross-backend
difference is FMA-contraction rounding at fusion boundaries (ulp-level,
bounded in tests/test_fused.py), never association drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

LANES = 128


def _ef_kernel(xh_ref, xhat_ref, s_ref, qs_ref, qn_ref, coef_ref,
               x_out, xhat_out, s_out):
    w_self = coef_ref[0]
    w_nbr = coef_ref[1]
    gamma = coef_ref[2]
    q_self = qs_ref[...]
    xhat_n = xhat_ref[...] + q_self
    s_n = s_ref[...] + (w_self * q_self + w_nbr * qn_ref[...])
    x_out[...] = xh_ref[...] + gamma * (s_n - xhat_n)
    xhat_out[...] = xhat_n
    s_out[...] = s_n


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def ef_gossip_update(x_half, x_hat, s, q_self, q_nbr, w_self, w_nbr, gamma,
                     *, interpret=None, block_rows: int = 256):
    """All tensors (R, 128) f32.  Returns (x, x_hat, s).

    The three scalar coefficients ride in SMEM: the TPU lowering only
    loads from VMEM/SMEM refs, never from an ``ANY`` (HBM) ref."""
    R, C = x_half.shape
    assert C == LANES and R % block_rows == 0, (R, C)
    grid = (R // block_rows,)
    bs = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))
    coef = jnp.asarray([w_self, w_nbr, gamma], jnp.float32)
    return pl.pallas_call(
        _ef_kernel,
        grid=grid,
        in_specs=[bs, bs, bs, bs, bs,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[bs, bs, bs],
        out_shape=[jax.ShapeDtypeStruct((R, C), jnp.float32)] * 3,
        # in place: (x_half, x_hat, s) -> (x, x_hat, s); every tile is read
        # before it is written, so the state needs no second HBM copy
        input_output_aliases={0: 0, 1: 1, 2: 2},
        interpret=resolve_interpret(interpret),
    )(x_half, x_hat, s, q_self, q_nbr, coef)
