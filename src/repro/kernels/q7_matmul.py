"""Pallas TPU kernel: int8 x int8 -> int32-accumulated matmul with
power-of-two requantization (the `mat_mult_q7` family, TPU-native).

Hardware adaptation (DESIGN.md §2): the paper's SIMD/transposed-B variants
are MCU register-blocking strategies; on TPU the equivalent decisions are
(a) MXU-native int8 pairs (jnp.dot with preferred_element_type=int32 — the
MXU runs int8 at 2x the bf16 rate), (b) BlockSpec tiles sized to VMEM and
aligned to the 128-lane MXU, (c) the K reduction as the innermost
("arbitrary") grid dimension accumulating into an int32 VMEM scratch, and
(d) the power-of-two rescale as a vector shift in the epilogue — no FP
multiplier anywhere, exactly the paper's Qm.n contract.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT8_MIN, INT8_MAX = -128, 127


def _q7_matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                      shift: int, rounding: str):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.int32),
                            b_ref[...].astype(jnp.int32),
                            preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...]
        if shift > 0:
            if rounding == "nearest":
                acc = acc + (1 << (shift - 1))
            acc = jnp.right_shift(acc, shift)
        elif shift < 0:
            acc = jnp.left_shift(acc, -shift)
        o_ref[...] = jnp.clip(acc, INT8_MIN, INT8_MAX).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("shift", "rounding", "bm", "bn",
                                             "bk", "interpret"))
def q7_matmul_pallas(a, b, *, shift: int, rounding: str = "floor",
                     bm: int = 128, bn: int = 128, bk: int = 128,
                     interpret: bool):
    """a [M,K] int8, b [K,N] int8 -> int8 [M,N].  Caller pads to tiles
    (zeros are exact in integer arithmetic)."""
    M, K = a.shape
    _, N = b.shape
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_q7_matmul_kernel, n_k=n_k, shift=shift,
                          rounding=rounding),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a, b)
