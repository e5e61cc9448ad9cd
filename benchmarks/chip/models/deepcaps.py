"""DeepCaps (Rajasegaran et al., CVPR 2019, arXiv:1904.09546): a SAME
conv stem, four capsule cells with skips (the last one's skip a 3D
convolutional capsule layer with local dynamic routing), and class
capsules routed from the last two cells' capsules.  A configuration
names it with `"model": "deepcaps"`; its geometry holds `input_shape`,
`stem_filters`, `cells` ([capsule types, atoms] per cell),
`num_classes`, `caps_dim`, `routings` and the weights' `init` scales.

The program's layer names (`CapsPipeline.from_deepcaps`) key the
weights: "stem", "cell<n>/a|b|c|skip" (conv capsules), "cell<n>/skip3d"
(the last cell's 3D layer) and "caps".
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.chip.reference import (_conv, _exact_int, _rshift_sat,
                                       _softmax_f, _squash_f, answers,
                                       as_float32, frac_bits, qrange,
                                       quantize, softmax_int, squash_int)


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------
def _cells(g: dict) -> list:
    """(cell n, types, atoms, in h, w, channels, out h, w) per cell."""
    h, w = g["input_shape"][:2]
    c, out = g["stem_filters"], []
    for n, (caps, dim) in enumerate(g["cells"], start=1):
        ho, wo = -(-h // 2), -(-w // 2)
        out.append((n, caps, dim, h, w, c, ho, wo))
        h, w, c = ho, wo, caps * dim
    return out


def _convs(g: dict) -> list:
    """(layer name, kernel, stride, in h, w, c, out h, w, f) per conv,
    the stem and every conv-capsule layer, in the program's order."""
    h, w, c = g["input_shape"]
    out = [("stem", 3, 1, h, w, c, h, w, g["stem_filters"])]
    last = len(g["cells"])
    for n, caps, dim, h, w, c, ho, wo in _cells(g):
        f = caps * dim
        out.append((f"cell{n}/a", 3, 2, h, w, c, ho, wo, f))
        for part in ("b", "c") + (() if n == last else ("skip",)):
            out.append((f"cell{n}/{part}", 3, 1, ho, wo, f, ho, wo, f))
    return out


def _skip3d(g: dict) -> tuple:
    """(name, I, D, J, O, out h, w) of the last cell's 3D layer."""
    n, caps, dim, _, _, _, ho, wo = _cells(g)[-1]
    return f"cell{n}/skip3d", caps, dim, caps, dim, ho, wo


def input_caps(g: dict) -> int:
    """I of the class layer: the capsules of the last two cells."""
    return sum(caps * ho * wo for _, caps, _, _, _, _, ho, wo in
               _cells(g)[-2:])


def out_shape(g: dict) -> tuple:
    return g["num_classes"], g["caps_dim"]


# ---------------------------------------------------------------------------
# weights and the program's pipeline
# ---------------------------------------------------------------------------
def make_params(g: dict, rng: np.random.Generator) -> dict:
    """Float32 weights, in the program's parameter layout, made on the
    device from the seed in one jitted call: every conv normal with
    variance init[kind] / fan_in (kind "stem" or "caps"), conv biases
    normal * 0.01, the 3D votes kernel variance init["votes"] / fan_in,
    its routing bias init["bias"] everywhere, class W normal *
    init["class"]."""
    import jax
    import jax.numpy as jnp
    key = jax.random.key(int(rng.integers(0, 2 ** 32)))
    init = g["init"]
    convs = _convs(g)
    name3d, _, D, J, O, _, _ = _skip3d(g)
    W_shape = (g["num_classes"], input_caps(g), g["caps_dim"],
               g["cells"][-1][1])

    def make(key):
        ks = jax.random.split(key, 2 * len(convs) + 2)
        out = {}
        for i, (name, k, _, _, _, c, _, _, f) in enumerate(convs):
            gain = init["stem"] if name == "stem" else init["caps"]
            w = jax.random.normal(ks[2 * i], (k, k, c, f), jnp.float32)
            b = jax.random.normal(ks[2 * i + 1], (f,), jnp.float32)
            out[name] = {"w": w * (gain / (k * k * c)) ** 0.5,
                         "b": b * 0.01}
        w3 = jax.random.normal(ks[-2], (3, 3, D, J * O), jnp.float32)
        out[name3d] = {"W": w3 * (init["votes"] / (9 * D)) ** 0.5,
                       "b": jnp.full((J, O), init["bias"], jnp.float32)}
        out["caps"] = {"W": jax.random.normal(ks[-1], W_shape, jnp.float32)
                       * init["class"]}
        return out

    return jax.jit(make)(key)


def pipeline(config: dict):
    """The program's pipeline for the configuration's geometry."""
    from repro.nn.config import DeepCapsConfig
    from repro.nn.pipeline import CapsPipeline
    g = config["geometry"]
    cfg = DeepCapsConfig(
        config["name"], tuple(g["input_shape"]), g["stem_filters"],
        tuple(tuple(c) for c in g["cells"]), g["num_classes"],
        g["caps_dim"], g["routings"])
    return CapsPipeline.from_deepcaps(cfg)


# ---------------------------------------------------------------------------
# work counts
# ---------------------------------------------------------------------------
def layers(g: dict) -> list:
    """One entry per layer: name, kind ("conv", "uhat" or "routing"),
    `macs` and `act_bytes` per image, and `weight_bytes` per wave."""
    out = [{"name": name.replace("/", "."), "kind": "conv",
            "macs": ho * wo * f * k * k * c,
            "act_bytes": h * w * c + ho * wo * f,
            "weight_bytes": k * k * c * f + f}
           for name, k, _, h, w, c, ho, wo, f in _convs(g)]
    name3d, I, D, J, O, ho, wo = _skip3d(g)
    name3d, r = name3d.replace("/", "."), g["routings"]
    groups = ho * wo
    out.append({"name": f"{name3d}.votes", "kind": "conv",
                "macs": I * ho * wo * J * O * 9 * D,
                "act_bytes": groups * I * D + groups * J * I * O,
                "weight_bytes": 9 * D * J * O})
    out.append({"name": f"{name3d}.routing", "kind": "routing",
                "macs": groups * (2 * r - 1) * J * I * O,
                "act_bytes": groups * (J * I * O + J * O),
                "weight_bytes": J * O})
    Jc, Oc, Dc, Ic = g["num_classes"], g["caps_dim"], D, input_caps(g)
    out.append({"name": "caps.uhat", "kind": "uhat",
                "macs": Jc * Ic * Oc * Dc,
                "act_bytes": Ic * Dc + Jc * Ic * Oc,
                "weight_bytes": Jc * Ic * Oc * Dc})
    out.append({"name": "caps.routing", "kind": "routing",
                "macs": (2 * r - 1) * Jc * Ic * Oc,
                "act_bytes": Jc * Ic * Oc + Jc * Oc, "weight_bytes": 0})
    return out


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _same(x, k: int, s: int) -> np.ndarray:
    """Zero-pad [N, H, W, C] so that a VALID conv of kernel k, stride s
    gives the SAME output ceil(H / s): the lower side gets half the
    padding, rounded down."""
    pads = []
    for n in x.shape[1:3]:
        total = max((-(-n // s) - 1) * s + k - n, 0)
        pads.append((total // 2, total - total // 2))
    return np.pad(x, ((0, 0), *pads, (0, 0)))


def _conv_same(x, w, s: int) -> np.ndarray:
    return _conv(_same(x, w.shape[0], s), w, s)


def _types(a) -> np.ndarray:
    """[N, H, W, I, D] -> [N * I, H, W, D]."""
    N, H, W, I, D = a.shape
    return a.transpose(0, 3, 1, 2, 4).reshape(N * I, H, W, D)


def _groups(votes, N: int, I: int, J: int, O: int) -> np.ndarray:
    """[N * I, H, W, J * O] -> [N * H * W, J, I, O]."""
    _, H, W, _ = votes.shape
    u = votes.reshape(N, I, H, W, J, O).transpose(0, 2, 3, 4, 1, 5)
    return u.reshape(N * H * W, J, I, O)


def _concat(xs) -> np.ndarray:
    return np.concatenate([x.reshape(x.shape[0], -1, x.shape[-1])
                           for x in xs], axis=1)


def _route_float(u_hat, routings: int, taps: dict, name: str, bias=None):
    taps[f"{name}.u_hat"] = np.abs(u_hat).max()
    b = np.zeros(u_hat.shape[:3])
    v = None
    for r in range(routings):
        s = np.einsum("bji,bjio->bjo", _softmax_f(b, axis=1), u_hat)
        if bias is not None:
            s = s + bias
        taps[f"{name}.s/{r}"] = np.abs(s).max()
        v = _squash_f(s)
        if r < routings - 1:
            b = b + np.einsum("bjio,bjo->bji", u_hat, v)
            taps[f"{name}.logits/{r}"] = np.abs(b).max()
    return v


def calibrate(g: dict, params: dict, x) -> dict:
    """max|x| per quantization point over the calibration images, from
    the float model in float64."""
    taps = {}

    def conv(name, h, s, relu):
        y = _conv_same(h, params[name]["w"], s) + \
            np.asarray(params[name]["b"], np.float64)
        taps[f"{name}.out"] = np.abs(y).max()
        return np.maximum(y, 0) if relu else y

    def caps(name, h, s, types, atoms):
        y = conv(name, h.reshape(h.shape[:3] + (-1,)), s, False)
        return _squash_f(y.reshape(y.shape[:3] + (types, atoms)))

    h = np.asarray(x, np.float64)
    taps["input"] = np.abs(h).max()
    h = conv("stem", h, 1, True)
    name3d, I, D, J, O, _, _ = _skip3d(g)
    outs = []
    for n, types, atoms, *_ in _cells(g):
        a = caps(f"cell{n}/a", h, 2, types, atoms)
        c = caps(f"cell{n}/c", caps(f"cell{n}/b", a, 1, types, atoms), 1,
                 types, atoms)
        if n < len(g["cells"]):
            skip = caps(f"cell{n}/skip", a, 1, types, atoms)
        else:
            votes = _conv_same(_types(a), params[name3d]["W"], 1)
            v = _route_float(_groups(votes, a.shape[0], I, J, O),
                             g["routings"], taps, name3d,
                             np.asarray(params[name3d]["b"], np.float64))
            skip = v.reshape(a.shape[:3] + (J, O))
        h = c + skip
        taps[f"cell{n}.out"] = np.abs(h).max()
        outs.append(h)
    u = _concat([outs[-1], outs[-2]])
    W = np.asarray(params["caps"]["W"], np.float64)
    _route_float(np.einsum("jiod,bid->bjio", W, u), g["routings"], taps,
                 "caps")
    return {k: float(v) for k, v in taps.items()}


def _shift_left(b, sh: int) -> np.ndarray:
    return np.left_shift(b, sh) if sh >= 0 else np.right_shift(b, -sh)


def _squash(s, f: int, bits: int) -> np.ndarray:
    """squash_int of s in Q(f); a negative format (only at int4) is read
    at Q.0 after a left shift, which keeps its value."""
    return squash_int(_shift_left(s, -f), 0, bits) if f < 0 \
        else squash_int(s, f, bits)


def _route_int(u_hat, routings: int, fb, taps: dict, name: str, bits: int,
               bias=None, bias_frac: int = 0):
    """Integer Alg. 5 over u_hat [G, J, I, O] (int, Q(f_uhat)); `bias`
    float [J, O] quantized at `bias_frac` and added to every s."""
    qmin, qmax, unit = qrange(bits)
    f_uhat = fb(taps[f"{name}.u_hat"])
    f_logit = min(fb(max([taps[f"{name}.logits/{r}"]
                          for r in range(routings - 1)] + [1e-6])), unit)
    bq = None if bias is None else _shift_left(
        quantize(bias, bias_frac, bits), f_uhat + unit - bias_frac)
    uf = u_hat.astype(np.float64)
    b = np.zeros(u_hat.shape[:3], np.int32)
    v = None
    for r in range(routings):
        c = softmax_int(b.swapaxes(1, 2), f_logit, bits).swapaxes(1, 2)
        s = _exact_int(np.matmul(c[:, :, None, :].astype(np.float64),
                                 uf))[:, :, 0, :]
        if bq is not None:
            s = s + bq
        f_s = fb(taps[f"{name}.s/{r}"])
        v = _squash(_rshift_sat(s, f_uhat + unit - f_s, bits), f_s, bits)
        if r < routings - 1:
            a = _exact_int(np.matmul(uf, v[..., None].astype(np.float64)))
            a = _rshift_sat(a[..., 0], f_uhat + unit - f_logit, bits)
            b = np.clip(b + a, qmin, qmax).astype(np.int32)
    return v


def forward_int(g: dict, params: dict, taps: dict, x, bits: int):
    """float images [N,H,W,C] -> class capsules v [N,J,O] (int, Q0.n),
    from the float64 calibration `taps`: per-tensor power-of-two formats
    (Alg. 6/7), int32 accumulation, floor shifts with saturation."""
    qmin, qmax, unit = qrange(bits)

    def fb(v):
        return frac_bits(v, qmax)

    def wfb(a):
        return fb(np.abs(a).max())

    def conv(name, h, f_in, s, relu):
        w, b = params[name]["w"], params[name]["b"]
        f_w, f_b, f_out = wfb(w), wfb(b), fb(taps[f"{name}.out"])
        acc = _exact_int(_conv_same(h, quantize(w, f_w, bits), s))
        acc = acc + _shift_left(quantize(b, f_b, bits), f_in + f_w - f_b)
        y = _rshift_sat(acc, f_in + f_w - f_out, bits)
        return (np.maximum(y, 0) if relu else y), f_out

    def caps(name, h, f_in, s, types, atoms):
        y, f_y = conv(name, h.reshape(h.shape[:3] + (-1,)), f_in, s, False)
        return _squash(y.reshape(y.shape[:3] + (types, atoms)), f_y, bits)

    def add(x1, x2, f1, f2, f_out):
        return np.clip(_shift_left(x1, f_out - f1)
                       + _shift_left(x2, f_out - f2), qmin, qmax)

    f_in = fb(taps["input"])
    h, f_h = conv("stem", quantize(x, f_in, bits), f_in, 1, True)
    name3d, I, D, J, O, _, _ = _skip3d(g)
    outs = []
    for n, types, atoms, *_ in _cells(g):
        a = caps(f"cell{n}/a", h, f_h, 2, types, atoms)
        c = caps(f"cell{n}/c", caps(f"cell{n}/b", a, unit, 1, types, atoms),
                 unit, 1, types, atoms)
        if n < len(g["cells"]):
            skip = caps(f"cell{n}/skip", a, unit, 1, types, atoms)
        else:
            W = params[name3d]["W"]
            f_W, f_uhat = wfb(W), fb(taps[f"{name3d}.u_hat"])
            votes = _rshift_sat(_exact_int(_conv_same(
                _types(a), quantize(W, f_W, bits), 1)),
                unit + f_W - f_uhat, bits)
            bias = params[name3d]["b"]
            v = _route_int(_groups(votes, a.shape[0], I, J, O),
                           g["routings"], fb, taps, name3d, bits, bias,
                           wfb(bias))
            skip = v.reshape(a.shape[:3] + (J, O))
        f_h = fb(taps[f"cell{n}.out"])
        h = add(c, skip, unit, unit, f_h)
        outs.append((h, f_h))
    (h4, f4), (h3, f3) = outs[-1], outs[-2]
    f_u = min(f4, f3)
    u = _concat([_rshift_sat(h4, f4 - f_u, bits),
                 _rshift_sat(h3, f3 - f_u, bits)])
    W = params["caps"]["W"]
    f_W, f_uhat = wfb(W), fb(taps["caps.u_hat"])
    acc = np.einsum("jiod,bid->bjio", quantize(W, f_W, bits)
                    .astype(np.float64), u.astype(np.float64), optimize=True)
    u_hat = _rshift_sat(_exact_int(acc), f_u + f_W - f_uhat, bits)
    return _route_int(u_hat, g["routings"], fb, taps, "caps", bits)


def reference(g: dict, params: dict, calib, images, bits: int = 8) -> tuple:
    """(v in Q0.7, pred) for every image, at integer width `bits`.  The
    images are answered in blocks of 16 on a few threads (NumPy lets go
    of the interpreter lock inside its array operations)."""
    params = as_float32(params)
    taps = calibrate(g, params, calib)
    blocks = [images[i:i + 16] for i in range(0, len(images), 16)]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
        parts = list(ex.map(lambda x: answers(
            lambda y: forward_int(g, params, taps, y, bits), x, bits),
            blocks))
    return (np.concatenate([v for v, _ in parts]),
            np.concatenate([p for _, p in parts]))
