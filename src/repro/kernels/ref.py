"""Pure-jnp references of the kernels no served path runs (the W8A8
matmul and the float squash).  The served kernels' integer semantics
live in repro.quant.int8_ops, which their bodies call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.routing import squash as squash_float_ref  # noqa: F401
from repro.quant.int8_ops import INT8_MAX, INT8_MIN


def w8a8_matmul_ref(a, w, col_shift, rounding: str = "nearest"):
    """[M,K] int8 x [K,N] int8 -> int8 [M,N] with per-output-channel
    power-of-two shifts (beyond-paper granularity; still shift-only)."""
    acc = jax.lax.dot_general(a, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    sh = col_shift.astype(jnp.int32)[None, :]
    if rounding == "nearest":
        acc = acc + jnp.where(sh > 0, jnp.left_shift(1, jnp.maximum(sh - 1, 0)), 0)
    acc = jnp.where(sh >= 0, jnp.right_shift(acc, jnp.maximum(sh, 0)),
                    jnp.left_shift(acc, jnp.maximum(-sh, 0)))
    return jnp.clip(acc, INT8_MIN, INT8_MAX).astype(jnp.int8)
