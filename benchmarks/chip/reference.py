"""The plain reference that decides `correct`: post-training quantization
and integer inference of a CapsNet (paper Alg. 4-7) in NumPy, written
from the configuration's geometry and the benchmark's own float weights.
It imports nothing of the program and takes nothing the program made:
it derives its own Qm.n formats from its own float64 calibration pass.

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


def conv_specs(geom: dict) -> list:
    """(layer name, stride, relu) per conv, the primary caps last."""
    n = len(geom["conv_filters"])
    return [(f"conv{i}", geom["conv_strides"][i], True) for i in range(n)] \
        + [("pcap", geom["pcap_stride"], False)]


def calibrate(geom: dict, params: dict, x) -> dict:
    """max|x| per quantization point over the calibration images, from
    the float model in float64."""
    h = np.asarray(x, np.float64)
    taps = {"input": np.abs(h).max()}
    for name, stride, relu in conv_specs(geom):
        y = _conv(h, params[name]["w"], stride) + \
            np.asarray(params[name]["b"], np.float64)
        taps[f"{name}.out"] = np.abs(y).max()
        h = np.maximum(y, 0) if relu else y
    u = _squash_f(h.reshape(h.shape[0], -1, geom["pcap_dim"]))
    W = np.asarray(params["caps"]["W"], np.float64)
    u_hat = np.einsum("jiod,bid->bjio", W, u)
    taps["caps.u_hat"] = np.abs(u_hat).max()
    b = np.zeros(u_hat.shape[:3])
    for r in range(geom["routings"]):
        c = _softmax_f(b, axis=1)
        s = np.einsum("bji,bjio->bjo", c, u_hat)
        taps[f"caps.s/{r}"] = np.abs(s).max()
        v = _squash_f(s)
        if r < geom["routings"] - 1:
            b = b + np.einsum("bjio,bjo->bji", u_hat, v)
            taps[f"caps.logits/{r}"] = np.abs(b).max()
    return {k: float(v) for k, v in taps.items()}


# ---------------------------------------------------------------------------
# plan (Alg. 6/7) and weights
# ---------------------------------------------------------------------------
def make_plan(geom: dict, params: dict, taps: dict, bits: int) -> dict:
    _, qmax, unit = qrange(bits)

    def fb(v):
        return frac_bits(v, qmax)

    plan = {"input_frac": fb(taps["input"])}
    f_act = plan["input_frac"]
    for name, _, _ in conv_specs(geom):
        w, b = params[name]["w"], params[name]["b"]
        f_w = fb(np.abs(w).max())
        f_b = fb(np.abs(b).max()) if np.size(b) else f_w
        f_out = fb(taps[f"{name}.out"])
        plan[name] = {"w_frac": f_w, "b_frac": f_b,
                      "out_shift": f_act + f_w - f_out,
                      "bias_shift": f_act + f_w - f_b, "out_frac": f_out}
        f_act = f_out
    R = geom["routings"]
    f_W = fb(np.abs(params["caps"]["W"]).max())
    f_uhat = fb(taps["caps.u_hat"])
    max_logit = max([taps[f"caps.logits/{r}"] for r in range(R - 1)]
                    + [1e-6])
    f_logit = min(fb(max_logit), unit)
    f_s = [fb(taps[f"caps.s/{r}"]) for r in range(R)]
    plan["caps"] = {
        "W_frac": f_W, "uhat_shift": unit + f_W - f_uhat,
        "logit_frac": f_logit, "caps_out_fracs": f_s,
        "caps_out_shifts": [f_uhat + unit - f for f in f_s],
        "agree_shifts": [f_uhat + unit - f_logit] * (R - 1)}
    return plan


def quantize_weights(geom: dict, params: dict, plan: dict,
                     bits: int) -> dict:
    qw = {name: {"w": quantize(params[name]["w"], plan[name]["w_frac"], bits),
                 "b": quantize(params[name]["b"], plan[name]["b_frac"], bits)}
          for name, _, _ in conv_specs(geom)}
    qw["caps"] = {"W": quantize(params["caps"]["W"], plan["caps"]["W_frac"],
                                bits)}
    return qw


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


def forward_int(geom: dict, qw: dict, plan: dict, x, bits: int):
    """float images [N,H,W,C] -> class capsules v [N,J,O] (int, Q0.n)."""
    qmin, qmax, unit = qrange(bits)
    h = quantize(x, plan["input_frac"], bits)
    for name, stride, relu in conv_specs(geom):
        p = plan[name]
        acc = _exact_int(_conv(h, qw[name]["w"], stride))
        b = qw[name]["b"]
        b = np.left_shift(b, p["bias_shift"]) if p["bias_shift"] >= 0 \
            else np.right_shift(b, -p["bias_shift"])
        h = _rshift_sat(acc + b, p["out_shift"], bits)
        if relu:
            h = np.maximum(h, 0)
    u = squash_int(h.reshape(h.shape[0], -1, geom["pcap_dim"]),
                   plan["pcap"]["out_frac"], bits)          # [N, I, D]
    cp = plan["caps"]
    W = qw["caps"]["W"].astype(np.float64)                   # [J, I, O, D]
    acc = np.einsum("jiod,bid->bjio", W, u.astype(np.float64),
                    optimize=True)
    u_hat = _rshift_sat(_exact_int(acc), cp["uhat_shift"], bits)
    uf = u_hat.astype(np.float64)
    b = np.zeros(u_hat.shape[:3], np.int32)                  # [N, J, I]
    v = None
    for r in range(geom["routings"]):
        c = softmax_int(b.swapaxes(1, 2), cp["logit_frac"], bits) \
            .swapaxes(1, 2)
        s = _exact_int(np.matmul(c[:, :, None, :].astype(np.float64),
                                 uf))[:, :, 0, :]             # [N, J, O]
        s = _rshift_sat(s, cp["caps_out_shifts"][r], bits)
        v = squash_int(s, cp["caps_out_fracs"][r], bits)
        if r < geom["routings"] - 1:
            a = _exact_int(np.matmul(uf, v[..., None].astype(np.float64)))
            a = _rshift_sat(a[..., 0], cp["agree_shifts"][r], bits)
            b = np.clip(b + a, qmin, qmax).astype(np.int32)
    return v


def predict(v) -> np.ndarray:
    """Class = longest capsule (first of equals)."""
    v = np.asarray(v, np.int64)
    return np.argmax(np.sum(v * v, axis=-1), axis=-1)


def reference(geom: dict, params: dict, calib, images, bits: int = 8,
              block: int = 64) -> tuple:
    """(v in Q0.7, pred) for every image, at integer width `bits`; the
    images are run in blocks so that memory stays small."""
    params = {k: {n: np.asarray(a, np.float32) for n, a in d.items()}
              for k, d in params.items()}
    plan = make_plan(geom, params, calibrate(geom, params, calib), bits)
    qw = quantize_weights(geom, params, plan, bits)
    vs = [forward_int(geom, qw, plan, images[i:i + block], bits)
          for i in range(0, len(images), block)]
    v = np.concatenate(vs)
    return np.left_shift(v, 7 - qrange(bits)[2]), predict(v)
