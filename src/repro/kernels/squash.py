"""Pallas TPU kernel: integer squash activation (paper Eq. 8 + Alg. 4).

Row-blocked over the capsule axis: each grid step loads a [block_rows, D]
tile of int8 capsule vectors into VMEM, computes the int32 sum of squares,
runs the fixed-iteration Newton-Raphson integer sqrt on the VPU, applies
the guarded power-of-two ratio, and writes int8 back.  D (the capsule
dimension, 4-8 in the paper) is far below the 128-lane width; the ops.py
wrapper keeps rows as the lane dimension by blocking many rows per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.int8_ops import SQUASH_GUARD_BITS

INT8_MIN, INT8_MAX = -128, 127


def _isqrt(n):
    x0 = jnp.maximum(n // 2, 1)

    def body(_, x):
        nxt = (x + n // jnp.maximum(x, 1)) // 2
        return jnp.where(nxt < x, nxt, x)

    x = jax.lax.fori_loop(0, 32, body, x0)
    return jnp.where(n <= 1, n, x)


def _squash_kernel(s_ref, o_ref, *, in_frac: int, out_frac: int):
    s = s_ref[...].astype(jnp.int32)
    Q = jnp.sum(s * s, axis=-1, keepdims=True)
    S = _isqrt(Q)
    P = SQUASH_GUARD_BITS
    shift = out_frac - in_frac + P
    num = jnp.left_shift(S, shift) if shift >= 0 \
        else jnp.right_shift(S, -shift)
    den = (1 << in_frac) + jnp.right_shift(Q, in_frac)
    ratio = num // jnp.maximum(den, 1)
    v = jnp.right_shift(ratio * s, P)
    o_ref[...] = jnp.clip(v, INT8_MIN, INT8_MAX).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("in_frac", "out_frac",
                                             "block_rows", "interpret"))
def squash_q7_pallas(s, *, in_frac: int, out_frac: int = 7,
                     block_rows: int = 256, interpret: bool):
    """s int8 [R, D] -> int8 [R, D] (rows padded by the ops wrapper)."""
    R, D = s.shape
    br = min(block_rows, R)
    assert R % br == 0
    return pl.pallas_call(
        functools.partial(_squash_kernel, in_frac=in_frac,
                          out_frac=out_frac),
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), jnp.int8),
        interpret=interpret,
    )(s)


def _squash_float_kernel(s_ref, o_ref):
    s = s_ref[...].astype(jnp.float32)
    sq = jnp.sum(s * s, axis=-1, keepdims=True)
    o_ref[...] = ((sq / (1.0 + sq)) * s * jax.lax.rsqrt(sq + 1e-7)) \
        .astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def squash_float_pallas(s, *, block_rows: int = 256, interpret: bool):
    """Float squash (Eq. 1) for the fp training path."""
    R, D = s.shape
    br = min(block_rows, R)
    assert R % br == 0
    return pl.pallas_call(
        _squash_float_kernel,
        grid=(R // br,),
        in_specs=[pl.BlockSpec((br, D), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, D), s.dtype),
        interpret=interpret,
    )(s)
