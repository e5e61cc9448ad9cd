"""Pallas TPU kernel: integer squash activation (paper Eq. 8 + Alg. 4).

Lane-dense: the kernel sees the capsules as `[D, rows, 128]` int8, each
capsule on one of the 128 lanes and its D components (4-8 in the paper)
on the leading axis; `ops.squash_q7` lays `[..., D]` out so, padding
with zero capsules.  Each grid step loads a `(D, rb, 128)` tile
into VMEM and runs `repro.quant.int8_ops.squash_q7` over the leading
axis, the oracle's arithmetic: the sum of squares is plain vector adds,
and the loop-free, division-free integer sqrt (`int8_ops.isqrt`) and the
guarded power-of-two ratio run on dense `[rb, 128]` int32 tiles; int8 is
written back in the same layout.  `tiling` picks the block from the
number of capsules and D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.int8_ops import squash_q7

LANES = 128
_SUBLANES_I8 = 32           # int8 tile: (32, 128)
_BLOCK_BYTES = 64 * 1024    # int8 input bytes per grid step


def tiling(rows: int, D: int) -> tuple[int, int]:
    """(rb, grid) for `rows` rows of 128 capsules of D components.

    One block when the rows fit `_BLOCK_BYTES`; else the fewest equal
    blocks that do, each a whole number of int8 tiles.  The caller pads
    the rows to `rb * grid`."""
    cap = max(_SUBLANES_I8, _BLOCK_BYTES // (D * LANES)
              // _SUBLANES_I8 * _SUBLANES_I8)
    if rows <= cap:
        return rows, 1
    grid = pl.cdiv(rows, cap)
    rb = pl.cdiv(pl.cdiv(rows, grid), _SUBLANES_I8) * _SUBLANES_I8
    return rb, grid


def _squash_kernel(s_ref, o_ref, *, in_frac: int, out_frac: int):
    o_ref[...] = squash_q7(s_ref[...], in_frac, out_frac, axis=0)


@functools.partial(jax.jit, static_argnames=("in_frac", "out_frac",
                                             "interpret"))
def squash_q7_pallas(s, *, in_frac: int, out_frac: int = 7,
                     interpret: bool):
    """s int8 [D, rows, 128] -> int8 [D, rows, 128], one capsule per lane
    (rows padded by the ops wrapper to `tiling`'s blocks)."""
    D, rows, lanes = s.shape
    assert lanes == LANES
    rb, grid = tiling(rows, D)
    assert rows == rb * grid
    spec = pl.BlockSpec((D, rb, LANES), lambda i: (0, i, 0))
    return pl.pallas_call(
        functools.partial(_squash_kernel, in_frac=in_frac,
                          out_frac=out_frac),
        grid=(grid,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(s.shape, jnp.int8),
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
