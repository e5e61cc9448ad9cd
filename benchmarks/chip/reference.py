"""The primitives of the plain reference that decides `correct`:
post-training quantization and integer inference of capsule networks
(paper Alg. 4-7) in NumPy.  Each model module (`models/<name>.py`)
composes them into its own reference, written from the configuration's
geometry and the benchmark's own float weights.  The reference imports
nothing of the program and takes nothing the program made: it derives
its own Qm.n formats from its own float64 calibration pass.

The semantics are the paper's CMSIS-style ones that the program's jnp
oracle also follows: power-of-two formats (the largest n with
round(max|x| * 2^n) <= qmax), round-to-nearest-even weights and input,
int32 accumulation, floor `>> shift` requantization with saturation, the
Newton-Raphson integer square root and 10 guard bits in the squash, and
the shift softmax with probabilities 2^floor(b - max b).

`bits` sets the integer width.  8 is the configuration's precision; 4 is
the control (the same arithmetic one precision lower), which has to come
out as not correct.  Matrix products run in float64, which is exact
here: every integer sum stays far below 2^53.
"""
from __future__ import annotations

import math

import numpy as np

MAX_FRAC_BITS = 24
SQUASH_GUARD_BITS = 10
EXP_FLOOR = -20


# ---------------------------------------------------------------------------
# formats
# ---------------------------------------------------------------------------
def qrange(bits: int) -> tuple:
    """(qmin, qmax, unit_frac): the integer range and the Q0.n format of
    a value in [-1, 1) at this width."""
    return -(1 << (bits - 1)), (1 << (bits - 1)) - 1, bits - 1


def frac_bits(max_abs: float, qmax: int) -> int:
    max_abs = float(max_abs)
    if max_abs <= 0 or math.isnan(max_abs):
        return MAX_FRAC_BITS
    n = int(math.floor(math.log2(qmax / max_abs)))
    while round(max_abs * 2.0 ** (n + 1)) <= qmax and n < MAX_FRAC_BITS:
        n += 1
    while round(max_abs * 2.0 ** n) > qmax and n > -MAX_FRAC_BITS:
        n -= 1
    return n


def quantize(x, n: int, bits: int) -> np.ndarray:
    qmin, qmax, _ = qrange(bits)
    scaled = np.asarray(x, np.float32) * np.float32(2.0 ** n)
    return np.clip(np.rint(scaled), qmin, qmax).astype(np.int32)


# ---------------------------------------------------------------------------
# float model (calibration)
# ---------------------------------------------------------------------------
def _patches(x, k: int, s: int) -> np.ndarray:
    """[N,H,W,C] -> [N,Ho,Wo,k*k*C] in (kh, kw, c) order (HWIO weights
    reshaped to [k*k*C, F] contract against it)."""
    N, H, W, C = x.shape
    ho, wo = (H - k) // s + 1, (W - k) // s + 1
    v = np.lib.stride_tricks.sliding_window_view(x, (k, k), axis=(1, 2))
    v = v[:, ::s, ::s][:, :ho, :wo]                 # [N,ho,wo,C,k,k]
    return v.transpose(0, 1, 2, 4, 5, 3).reshape(N, ho, wo, k * k * C)


def _conv(x, w, stride: int) -> np.ndarray:
    k, f = w.shape[0], w.shape[3]
    return _patches(np.asarray(x, np.float64), k, stride) @ \
        np.asarray(w, np.float64).reshape(-1, f)


def _squash_f(s):
    sq = np.sum(s * s, axis=-1, keepdims=True)
    return sq / (1.0 + sq) * s / np.sqrt(sq + 1e-7)


def _softmax_f(b, axis: int):
    e = np.exp(b - b.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


# ---------------------------------------------------------------------------
# integer inference
# ---------------------------------------------------------------------------
def _exact_int(y) -> np.ndarray:
    return np.rint(y).astype(np.int64).astype(np.int32)


def _rshift_sat(acc, shift: int, bits: int) -> np.ndarray:
    qmin, qmax, _ = qrange(bits)
    acc = np.asarray(acc, np.int32)
    if shift > 0:
        acc = np.right_shift(acc, shift)
    elif shift < 0:
        acc = np.left_shift(acc, -shift)
    return np.clip(acc, qmin, qmax).astype(np.int32)


def _isqrt(n) -> np.ndarray:
    """Integer square root by Newton-Raphson from n/2 (paper Alg. 4),
    stopped when the next iterate stops decreasing."""
    x = np.maximum(n // 2, 1)
    for _ in range(32):
        nxt = (x + n // np.maximum(x, 1)) // 2
        x = np.where(nxt < x, nxt, x)
    return np.where(n <= 1, n, x)


def squash_int(s, in_frac: int, bits: int) -> np.ndarray:
    """Eq. 8 over the last axis: v = S * 2^o / (2^2i + Q) * s, with
    Q = sum(s^2), S = isqrt(Q), o the Q0.n output format."""
    qmin, qmax, out_frac = qrange(bits)
    s32 = np.asarray(s, np.int32)
    Q = np.sum(s32 * s32, axis=-1, keepdims=True, dtype=np.int32)
    S = _isqrt(Q)
    shift = out_frac - in_frac + SQUASH_GUARD_BITS
    num = np.left_shift(S, shift) if shift >= 0 \
        else np.right_shift(S, -shift)
    den = (1 << in_frac) + np.right_shift(Q, in_frac)
    ratio = num // np.maximum(den, 1)
    v = np.right_shift(ratio * s32, SQUASH_GUARD_BITS)
    return np.clip(v, qmin, qmax).astype(np.int32)


def softmax_int(x, in_frac: int, bits: int) -> np.ndarray:
    """Shift softmax over the last axis into Q0.n: p = 2^floor(x - max),
    c = p * 2^n // sum(p)."""
    _, qmax, unit = qrange(bits)
    x32 = np.asarray(x, np.int32)
    e = np.maximum(np.right_shift(x32 - x32.max(axis=-1, keepdims=True),
                                  in_frac), EXP_FLOOR)
    p = np.left_shift(np.ones_like(e), 20 + e)
    tot = np.sum(p, axis=-1, keepdims=True, dtype=np.int32)
    c = np.left_shift(p, unit) // np.maximum(tot, 1)
    return np.clip(c, 0, qmax).astype(np.int32)


def predict(v) -> np.ndarray:
    """Class = longest capsule (first of equals)."""
    v = np.asarray(v, np.int64)
    return np.argmax(np.sum(v * v, axis=-1), axis=-1)


def as_float32(params: dict) -> dict:
    """Host float32 copies of a {layer: {name: array}} parameter dict."""
    return {k: {n: np.asarray(a, np.float32) for n, a in d.items()}
            for k, d in params.items()}


def answers(forward, images, bits: int, block: int = 64) -> tuple:
    """(v in Q0.7, pred) of `forward` (float images -> int capsules in
    Q0.n at width `bits`) over every image, in blocks of `block` images
    so that memory stays small."""
    vs = [forward(images[i:i + block]) for i in range(0, len(images), block)]
    v = np.concatenate(vs)
    return np.left_shift(v, 7 - qrange(bits)[2]), predict(v)
