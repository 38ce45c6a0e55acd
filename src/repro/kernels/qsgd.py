"""Pallas TPU kernel: qsgd_s stochastic quantization (paper §3.5).

    q = sign(x) * floor(s |x| / ||x|| + xi),   xi ~ U[0,1)^d
    dequant(q) = q * ||x|| / (s * tau)

The global norm is a cheap jnp reduction computed once on the UNPADDED
buffer by the caller (so the pallas path shares the exact reduction
order with the jnp path); the kernel does the bandwidth-bound
elementwise pass HBM->VMEM->HBM in (8, 128)-aligned tiles, emitting
int8 codes for s <= 127 and int16 above — the same wire format as
``comm/packing.py::compress_bucket``.  No clip is needed: |x| <= ||x||
bounds every level by s.  The uniform noise is passed in as an input so
the pure-jnp oracle (ref.py) matches bit-exactly; a TPU-native variant
would fuse pltpu.prng_random_bits instead.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import resolve_interpret

BLOCK_ROWS = 8
LANES = 128


def _quant_kernel(x_ref, xi_ref, coef_ref, out_ref, *, ctype):
    x = x_ref[...]
    xi = xi_ref[...]
    inv_norm = coef_ref[0]
    s = coef_ref[1]
    level = jnp.floor(jnp.abs(x) * inv_norm * s + xi)
    out_ref[...] = (jnp.sign(x) * level).astype(ctype)


def _sign_kernel(x_ref, out_ref):
    out_ref[...] = jnp.sign(x_ref[...]).astype(jnp.int8)


def _dequant_kernel(codes_ref, scale_ref, out_ref):
    out_ref[...] = codes_ref[...].astype(jnp.float32) * scale_ref[0]


def code_dtype(s: int):
    """Wire code dtype for s quantization levels (int8 up to 127)."""
    return jnp.int8 if s <= 127 else jnp.int16


@functools.partial(jax.jit, static_argnames=("s", "interpret", "block_rows"))
def qsgd_quantize_codes(x, xi, inv_norm, s: int, *, interpret=None,
                        block_rows: int = BLOCK_ROWS):
    """Fused quantize pass: the elementwise half of qsgd, codes only.

    x, xi: (R, 128) f32 tiles (R % block_rows == 0); inv_norm: f32
    scalar, precomputed as 1/||x|| (0 for a zero vector) by the caller.
    Returns int8/int16 codes (R, 128) per :func:`code_dtype`.
    """
    R, C = x.shape
    assert C == LANES and R % block_rows == 0, (R, C)
    grid = (R // block_rows,)
    ctype = code_dtype(s)
    return pl.pallas_call(
        functools.partial(_quant_kernel, ctype=ctype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            # (inv_norm, s) broadcast to every tile, from SMEM: the TPU
            # lowering only loads from VMEM/SMEM refs, never from ANY (HBM)
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANES), ctype),
        interpret=resolve_interpret(interpret),
    )(x, xi, jnp.stack([jnp.asarray(inv_norm, jnp.float32),
                        jnp.float32(s)]))


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def signnorm_codes(x, *, interpret=None,
                   block_rows: int = BLOCK_ROWS):
    """SignNorm wire codes: x (R, 128) f32 tiles -> int8 sign(x)."""
    R, C = x.shape
    assert C == LANES and R % block_rows == 0, (R, C)
    return pl.pallas_call(
        _sign_kernel,
        grid=(R // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANES), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(x)


@functools.partial(jax.jit, static_argnames=("s", "interpret", "block_rows"))
def qsgd_quantize(x, xi, s: int, *, interpret=None,
                  block_rows: int = BLOCK_ROWS):
    """x, xi: (R, 128) f32 tiles (R % block_rows == 0).
    Returns (codes int8/int16 (R,128), scale f32 scalar)."""
    R, C = x.shape
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    inv_norm = jnp.where(norm == 0, 0.0, 1.0 / norm)
    codes = qsgd_quantize_codes(x, xi, inv_norm, s, interpret=interpret,
                                block_rows=block_rows)
    d = R * C
    tau = 1.0 + min(d / (s * s), math.sqrt(d) / s)
    scale = norm / (s * tau)
    return codes, scale.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def qsgd_dequantize(codes, scale, *, interpret=None,
                    block_rows: int = BLOCK_ROWS):
    """codes (R, 128) int8/int16, scale f32 scalar -> f32 (R, 128)."""
    R, C = codes.shape
    grid = (R // block_rows,)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANES), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(codes, jnp.stack([jnp.asarray(scale, jnp.float32)]))
