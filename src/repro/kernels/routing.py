"""Pallas TPU kernel: FUSED int8 dynamic routing (beyond-paper, DESIGN §7).

The paper's capsule layer round-trips u_hat / b / c / v through memory
between its four support functions on every routing iteration (Alg. 5).
On TPU the whole routing state is tiny — u_hat for one sample is
J x I x O int8 (60 KB for the paper's MNIST layer) and b/c are J x I —
so the entire r-iteration loop fits in VMEM.  This kernel grids over the
batch, holds u_hat resident, and runs softmax -> weighted-sum -> squash ->
agreement entirely on-chip, eliminating (2r-1) HBM round-trips of u_hat.

Integer semantics match repro.kernels.ref.routing_q7_ref bit-for-bit.
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

    return jnp.where(n <= 1, n, jax.lax.fori_loop(0, 32, body, x0))


def _squash_caps(s32, in_frac: int, out_frac: int = 7):
    """Integer squash of each capsule s [J, O, 1] over its O axis."""
    Q = jnp.sum(s32 * s32, axis=1, keepdims=True)
    S = _isqrt(Q)
    P = SQUASH_GUARD_BITS
    shift = out_frac - in_frac + P
    num = jnp.left_shift(S, shift) if shift >= 0 \
        else jnp.right_shift(S, -shift)
    den = (1 << in_frac) + jnp.right_shift(Q, in_frac)
    ratio = num // jnp.maximum(den, 1)
    return jnp.clip(jnp.right_shift(ratio * s32, P), INT8_MIN, INT8_MAX)


def _softmax_q7_cols(b32, in_frac: int):
    """Shift-based integer softmax over axis 0 (the J axis of b)."""
    m = jnp.max(b32, axis=0, keepdims=True)
    e = jnp.maximum(jnp.right_shift(b32 - m, in_frac), -20)
    p = jnp.left_shift(jnp.ones_like(e), 20 + e)
    tot = jnp.sum(p, axis=0, keepdims=True)
    return jnp.clip(jnp.left_shift(p, 7) // jnp.maximum(tot, 1), 0, INT8_MAX)


def _rshift_sat8(acc, shift: int, rounding: str):
    if shift > 0:
        if rounding == "nearest":
            acc = acc + (1 << (shift - 1))
        acc = jnp.right_shift(acc, shift)
    elif shift < 0:
        acc = jnp.left_shift(acc, -shift)
    return jnp.clip(acc, INT8_MIN, INT8_MAX)


def _routing_kernel(u_ref, v_ref, *, num_iters, caps_out_shifts,
                    caps_out_fracs, agree_shifts, logit_frac, rounding):
    # I sits on the 128-wide lane axis and O on the sublanes; every
    # intermediate keeps rank 3 (keepdims) so no reduction or broadcast
    # has to move data between the two axes.  The two contractions are a
    # VPU multiply plus a reduction: at O = 4-6 they are far too narrow
    # for the MXU, and Mosaic cannot lower a batched dot in which one
    # operand has no free dimension.
    u = u_ref[0].astype(jnp.int32)              # [J, O, I] resident in VMEM
    J, O, I = u.shape
    b = jnp.zeros((J, 1, I), jnp.int32)
    v = None
    for r in range(num_iters):
        c = _softmax_q7_cols(b, logit_frac)                      # [J, 1, I]
        s = jnp.sum(c * u, axis=2, keepdims=True)                # [J, O, 1]
        s_q = _rshift_sat8(s, caps_out_shifts[r], rounding)
        v = _squash_caps(s_q, in_frac=caps_out_fracs[r])         # [J, O, 1]
        if r < num_iters - 1:
            a = jnp.sum(u * v, axis=1, keepdims=True)            # [J, 1, I]
            a = _rshift_sat8(a, agree_shifts[r], rounding)
            b = jnp.clip(b + a, INT8_MIN, INT8_MAX)              # q7 add
    v_ref[0] = v


@functools.partial(jax.jit, static_argnames=(
    "num_iters", "caps_out_shifts", "caps_out_fracs", "agree_shifts",
    "logit_frac", "rounding", "interpret"))
def routing_q7_pallas(u_hat, *, num_iters: int, caps_out_shifts: tuple,
                      caps_out_fracs: tuple, agree_shifts: tuple,
                      logit_frac: int, rounding: str, interpret: bool):
    """u_hat int8 [B, J, I, O] -> v int8 [B, J, O], all r iterations fused.

    The kernel reads u_hat as [B, J, O, I], with I on the lanes; the
    transpose is an XLA op outside the kernel."""
    B, J, I, O = u_hat.shape
    v = pl.pallas_call(
        functools.partial(
            _routing_kernel, num_iters=num_iters,
            caps_out_shifts=caps_out_shifts, caps_out_fracs=caps_out_fracs,
            agree_shifts=agree_shifts, logit_frac=logit_frac,
            rounding=rounding),
        grid=(B,),
        in_specs=[pl.BlockSpec((1, J, O, I), lambda b: (b, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, J, O, 1), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, J, O, 1), jnp.int32),
        interpret=interpret,
    )(jnp.swapaxes(u_hat, 2, 3))
    return v[..., 0].astype(jnp.int8)
