"""Pallas TPU kernel: W8A8 matmul with per-output-channel power-of-two
rescale (the paper's quantization framework generalized to transformer
serving — beyond-paper granularity, still shift-only: DESIGN §7).

Same MXU int8 tiling as q7_matmul; the epilogue applies a per-column shift
vector (int32, one entry per output channel) instead of a scalar shift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT8_MIN, INT8_MAX = -128, 127


def _w8a8_kernel(a_ref, w_ref, sh_ref, o_ref, acc_ref, *, n_k: int,
                 rounding: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.int32),
                            w_ref[...].astype(jnp.int32),
                            preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        sh = sh_ref[...].astype(jnp.int32)[None, :]
        if rounding == "nearest":
            acc = acc + jnp.where(
                sh > 0, jnp.left_shift(1, jnp.maximum(sh - 1, 0)), 0)
        acc = jnp.where(sh >= 0,
                        jnp.right_shift(acc, jnp.maximum(sh, 0)),
                        jnp.left_shift(acc, jnp.maximum(-sh, 0)))
        o_ref[...] = jnp.clip(acc, INT8_MIN, INT8_MAX).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("rounding", "bm", "bn", "bk",
                                             "interpret"))
def w8a8_matmul_pallas(a, w, col_shift, *, rounding: str = "nearest",
                       bm: int = 128, bn: int = 128, bk: int = 128,
                       interpret: bool):
    """a [M,K] int8, w [K,N] int8, col_shift [N] int32 -> int8 [M,N]."""
    M, K = a.shape
    _, N = w.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    n_k = K // bk
    return pl.pallas_call(
        functools.partial(_w8a8_kernel, n_k=n_k, rounding=rounding),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bn,), lambda i, j, k: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a, w, col_shift)
