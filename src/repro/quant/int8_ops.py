"""Exact int8 operation semantics (the pure-jnp oracle layer).

These functions define the integer arithmetic of the quantized path:
int8 operands, int32 accumulation, power-of-two rescale (arithmetic
shift), saturation to [-128, 127] — the TPU analogue of the paper's
CMSIS-NN / PULP-NN kernels.  The Pallas kernel bodies (kernels/routing.py,
kernels/squash.py) call the requantize, squash and softmax below, so
there is one definition of each.  The `*_i32` forms are the same
arithmetic without the final int8 cast, for kernels that keep their
state in int32 between steps; the squash and the shift softmax reduce
over `axis` (the oracle's last axis, a kernel's layout axis).

`rounding="floor"` matches the paper/CMSIS `__SSAT(sum >> shift, 8)`
truncation; `rounding="nearest"` adds the half-LSB before shifting
(beyond-paper accuracy option, still shift-only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.obs import numerics as _health

INT8_MIN, INT8_MAX = -128, 127


def rshift_sat8_i32(acc, shift: int, rounding: str = "floor"):
    """`rshift_sat8` with the saturated result left in int32."""
    acc = acc.astype(jnp.int32)
    if shift > 0:
        if rounding == "nearest":
            acc = acc + (1 << (shift - 1))
        acc = jnp.right_shift(acc, shift)
    elif shift < 0:
        acc = jnp.left_shift(acc, -shift)
    return jnp.clip(acc, INT8_MIN, INT8_MAX)


def rshift_sat8(acc, shift: int, rounding: str = "floor"):
    """int32 accumulator -> int8 via arithmetic shift + saturate."""
    if _health._PROBE is not None:     # observer only; skips jit tracers
        _health.observe_requant(acc, shift, rounding)
    return rshift_sat8_i32(acc, shift, rounding).astype(jnp.int8)


def sat8(x):
    return jnp.clip(x.astype(jnp.int32), INT8_MIN, INT8_MAX).astype(jnp.int8)


def matmul_q7(a, b, shift: int, rounding: str = "floor"):
    """[..., M, K] int8 x [..., K, N] int8 -> int8, int32 accumulation.

    The `mat_mult_q7` family: the transposed-B / SIMD variants of the paper
    are memory layouts of the same arithmetic; on TPU the MXU consumes
    int8 pairs natively (preferred_element_type=int32)."""
    acc = jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (b.ndim - 2,)), ((), ())),
        preferred_element_type=jnp.int32)
    return rshift_sat8(acc, shift, rounding)


def matmul_q7_acc(a, b):
    """Raw int32 accumulator (for fused pipelines)."""
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (b.ndim - 2,)), ((), ())),
        preferred_element_type=jnp.int32)


def add_q7(a, b, shift_a: int = 0, shift_b: int = 0):
    """Saturating int8 addition with per-operand alignment shifts."""
    aa = jnp.left_shift(a.astype(jnp.int32), max(-shift_a, 0)) \
        if shift_a <= 0 else jnp.right_shift(a.astype(jnp.int32), shift_a)
    bb = jnp.left_shift(b.astype(jnp.int32), max(-shift_b, 0)) \
        if shift_b <= 0 else jnp.right_shift(b.astype(jnp.int32), shift_b)
    return sat8(aa + bb)


def conv2d_q7(x, w, bias, out_shift: int, bias_shift: int,
              stride: int = 1, padding: str = "VALID",
              rounding: str = "floor"):
    """NHWC int8 conv, int32 accumulation, shifted bias, shift+sat output.

    x [B,H,W,Cin] int8; w [KH,KW,Cin,Cout] int8; bias [Cout] int8.
    bias is left-shifted by `bias_shift` into the accumulator's Qm.n
    (paper Alg. 6 line 10)."""
    acc = jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    if bias is not None:
        b = bias.astype(jnp.int32)
        b = jnp.left_shift(b, bias_shift) if bias_shift >= 0 \
            else jnp.right_shift(b, -bias_shift)
        acc = acc + b
    return rshift_sat8(acc, out_shift, rounding)


def rshift_sat8_vec(acc, shifts, rounding: str = "floor"):
    """rshift_sat8 with a per-lane shift array broadcast against the
    accumulator's trailing axes (the per-channel requantization step).

    Semantics per lane match the scalar path exactly: positive shifts
    arithmetic-right-shift (nearest adds the half-LSB first), negative
    shifts left-shift, then saturate to int8."""
    if _health._PROBE is not None:     # observer only; skips jit tracers
        _health.observe_requant(acc, shifts, rounding)
    acc = acc.astype(jnp.int32)
    shifts = jnp.asarray(shifts, jnp.int32)
    if rounding == "nearest":
        half = jnp.left_shift(jnp.int32(1), jnp.maximum(shifts - 1, 0))
        acc = acc + jnp.where(shifts > 0, half, 0)
    acc = jnp.right_shift(acc, jnp.maximum(shifts, 0))
    acc = jnp.left_shift(acc, jnp.maximum(-shifts, 0))
    return jnp.clip(acc, INT8_MIN, INT8_MAX).astype(jnp.int8)


def conv2d_q7_per_channel(x, w, bias, out_shifts, bias_shifts,
                          stride: int = 1, padding: str = "VALID",
                          rounding: str = "floor"):
    """conv2d_q7 with per-output-channel weight formats: the accumulator
    for channel c carries in_frac + w_frac[c] fractional bits, so both
    the bias alignment and the output requantization are per-channel
    shift tables (still power-of-two — MCU cost is one extra q7 table).
    """
    acc = jax.lax.conv_general_dilated(
        x.astype(jnp.int32), w.astype(jnp.int32),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    if bias is not None:
        b = bias.astype(jnp.int32)
        bs = jnp.asarray(bias_shifts, jnp.int32)
        b = jnp.left_shift(b, jnp.maximum(bs, 0))
        b = jnp.right_shift(b, jnp.maximum(-bs, 0))
        acc = acc + b
    return rshift_sat8_vec(acc, out_shifts, rounding)


def relu_q7(x):
    return jnp.maximum(x, 0).astype(jnp.int8)


# ---------------------------------------------------------------------------
# integer square root (Newton-Raphson, paper Alg. 4) and squash (Eq. 8)
# ---------------------------------------------------------------------------
def isqrt_newton(n):
    """Integer sqrt of int32 n (elementwise, vectorized Newton-Raphson).

    Follows Alg. 4: x0 = n/2, x_{k+1} = (x_k + n/x_k)/2, stop when the next
    iterate stops decreasing.  A fixed 32-iteration loop (Newton from n/2
    halves the exponent gap per step; 32 covers any int32) with the
    monotonicity guard makes it bit-exact with the sequential algorithm."""
    n = n.astype(jnp.int32)
    x0 = jnp.maximum(n // 2, 1)

    def body(_, x):
        nxt = (x + n // jnp.maximum(x, 1)) // 2
        return jnp.where(nxt < x, nxt, x)

    # n in {0,1}: x0 heuristics.  The compare is traced before the loop:
    # the order of the ops is part of the kernels' compiled code.
    return jnp.where(n <= 1, n, jax.lax.fori_loop(0, 32, body, x0))


SQUASH_GUARD_BITS = 10


def _squash_scale(s32, norm, Q, in_frac: int, out_frac: int):
    """sat8((ratio * s) >> P) with Eq. 8's guarded factor
    ratio = (norm << (o - i + P)) // ((1 << i) + (Q >> i)), in int32."""
    P = SQUASH_GUARD_BITS
    shift = out_frac - in_frac + P
    num = jnp.left_shift(norm, shift) if shift >= 0 \
        else jnp.right_shift(norm, -shift)
    den = (1 << in_frac) + jnp.right_shift(Q, in_frac)
    ratio = num // jnp.maximum(den, 1)
    return jnp.clip(jnp.right_shift(ratio * s32, P), INT8_MIN, INT8_MAX)


def squash_q7_i32(s, in_frac: int, out_frac: int = 7, axis: int = -1):
    """`squash_q7` with the saturated result left in int32."""
    s32 = s.astype(jnp.int32)
    Q = jnp.sum(s32 * s32, axis=axis, keepdims=True)
    return _squash_scale(s32, isqrt_newton(Q), Q, in_frac, out_frac)


def squash_q7(s, in_frac: int, out_frac: int = 7, axis: int = -1):
    """Integer squash (paper Eq. 8) of the capsules along `axis`.

    s int8 [..., D] with `in_frac` (i) fractional bits; returns int8 with
    `out_frac` (o) fractional bits.  Derivation: with Q = sum(s^2) (2i frac
    bits) and S = isqrt(Q) (i frac bits),
        v_f  = (||s|| / (1 + ||s||^2)) * s_f
        v_q  = v_f * 2^o = [S * 2^o / (2^{2i} + Q)] * s_q
    The bracket is Eq. 8's  (||s|| << (o-i)) / ((1<<i) + (Q>>i))  up to the
    integer-division order; we carry SQUASH_GUARD_BITS (P) extra bits
    through the division so the factor keeps ~3 decimal digits:
        ratio = (S << (o - i + P)) // ((2^{2i} + Q) >> i)
        v     = sat8((ratio * s) >> P)
    Values: S <= 127*sqrt(D) < 2^9 for D <= 16, so int32 never overflows.
    """
    return squash_q7_i32(s, in_frac, out_frac, axis).astype(jnp.int8)


def _exp2_shift(x, in_frac: int, axis: int):
    """2^(20 + floor(x - max)) per element (floored at 2^0) and its sum
    along `axis` (<= n * 2^20, fits int32)."""
    x32 = x.astype(jnp.int32)
    m = jnp.max(x32, axis=axis, keepdims=True)
    e = jnp.maximum(jnp.right_shift(x32 - m, in_frac), -20)   # <= 0
    p = jnp.left_shift(jnp.ones_like(e), 20 + e)
    return p, jnp.sum(p, axis=axis, keepdims=True)


def softmax_q7_i32(x, in_frac: int, axis: int = -1):
    """`softmax_q7` with the saturated result left in int32."""
    p, tot = _exp2_shift(x, in_frac, axis)
    return jnp.clip(jnp.left_shift(p, 7) // jnp.maximum(tot, 1), 0, INT8_MAX)


def softmax_q7(x, in_frac: int, axis: int = -1):
    """Shift-based integer softmax along `axis` -> Q0.7 output.

    Faithful to the arm_softmax_q7 approach: probabilities are powers of two
    of the integer part of (x - max), normalized to 128 = 1.0, saturated to
    127.  Coarse but branch/LUT-free."""
    return softmax_q7_i32(x, in_frac, axis).astype(jnp.int8)


def softmax_q7_precise(x, in_frac: int):
    """Beyond-paper variant: dequantize -> fp32 softmax -> requant Q0.7.
    (What you would do on a TPU where the VPU has fast exp; kept for the
    accuracy/throughput trade-off study.)"""
    xf = x.astype(jnp.float32) * (2.0 ** -in_frac)
    p = jax.nn.softmax(xf, axis=-1)
    return jnp.clip(jnp.round(p * 128.0), 0, INT8_MAX).astype(jnp.int8)


def ceil_log2_int(tot):
    """ceil(log2(tot)) for positive int32 arrays: the bit length of
    tot - 1, counted with shifts so the semantics are integer-exact (and
    identical to the NumPy mirror in repro.nn.variants)."""
    t1 = tot.astype(jnp.int32) - 1
    k = jnp.zeros_like(t1)
    for j in range(31):
        k = k + (jnp.right_shift(t1, j) > 0)
    return k


def softmax_q7_approx(x, in_frac: int):
    """ISLPED'22 approximate softmax: shift-based exp with power-of-two
    normalization -> Q0.7 output.

    Probabilities are the same powers of two of floor(x - max) as
    `softmax_q7`, but the normalizer sum is rounded UP to a power of two
    (2^ceil(log2(sum))), so the per-element integer division becomes one
    arithmetic right shift — the cheapest softmax an MCU can run."""
    p, tot = _exp2_shift(x, in_frac, -1)
    k = ceil_log2_int(tot)          # >= 20: the max element contributes 2^20
    c = jnp.right_shift(p, k - 7)
    return jnp.clip(c, 0, INT8_MAX).astype(jnp.int8)


def squash_q7_approx(s, in_frac: int, out_frac: int = 7):
    """ISLPED'22 approximate squash: Eq. 8 with the L2 norm replaced by
    the L-inf norm M = max|s_i| — the 32-iteration Newton-Raphson
    integer sqrt (Alg. 4, the routing loop's hot spot) disappears:

        ratio = (M << (o - i + P)) // ((1 << i) + (M*M >> i))
        v     = sat8((ratio * s) >> P)

    M <= ||s||_2 <= sqrt(D) * M, so capsule probabilities keep their
    ordering; the factor error is bounded by the capsule dimension."""
    s32 = s.astype(jnp.int32)
    M = jnp.max(jnp.abs(s32), axis=-1, keepdims=True)
    return _squash_scale(s32, M, M * M, in_frac, out_frac).astype(jnp.int8)
