"""The CapsNet template of arXiv:2110.02911: int8 convs -> primary
capsules -> one layer of class capsules routed from the primary capsules.
A configuration names it with `"model": "capsnet"`; its geometry holds
`input_shape`, `conv_*`, `pcap_*`, `num_classes`, `caps_dim`, `routings`.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import (_conv, _exact_int, _rshift_sat,
                                       _softmax_f, _squash_f, answers,
                                       as_float32, frac_bits, qrange,
                                       quantize, softmax_int, squash_int)


def make_params(geom: dict, rng: np.random.Generator) -> dict:
    """Float32 weights, in the program's parameter layout, made on the
    device from the seed in one jitted call."""
    import jax
    import jax.numpy as jnp
    key = jax.random.key(int(rng.integers(0, 2 ** 32)))
    specs = conv_specs(geom)
    filters = list(geom["conv_filters"]) + [geom["pcap_caps"]
                                            * geom["pcap_dim"]]
    kernels = list(geom["conv_kernels"]) + [geom["pcap_kernel"]]
    W_shape = (geom["num_classes"], input_caps(geom), geom["caps_dim"],
               geom["pcap_dim"])

    def init(key):
        ks = jax.random.split(key, 2 * len(specs) + 1)
        out, cin = {}, geom["input_shape"][2]
        for i, ((name, _, relu), f, k) in enumerate(
                zip(specs, filters, kernels)):
            gain = 2.0 if relu else 1.0             # He-normal / 1/fan_in
            w = jax.random.normal(ks[2 * i], (k, k, cin, f), jnp.float32)
            b = jax.random.normal(ks[2 * i + 1], (f,), jnp.float32)
            out[name] = {"w": w * (gain / (k * k * cin)) ** 0.5,
                         "b": b * 0.01}
            cin = f
        out["caps"] = {"W": jax.random.normal(ks[-1], W_shape,
                                              jnp.float32) * 0.1}
        return out

    return jax.jit(init)(key)


def pipeline(config: dict):
    """The program's pipeline for the configuration's geometry."""
    from repro.nn.config import CapsNetConfig
    from repro.nn.pipeline import CapsPipeline
    g = config["geometry"]
    cfg = CapsNetConfig(
        config["name"], tuple(g["input_shape"]), tuple(g["conv_filters"]),
        tuple(g["conv_kernels"]), tuple(g["conv_strides"]),
        pcap_caps=g["pcap_caps"], pcap_dim=g["pcap_dim"],
        pcap_kernel=g["pcap_kernel"], pcap_stride=g["pcap_stride"],
        num_classes=g["num_classes"], caps_dim=g["caps_dim"],
        routings=g["routings"])
    return CapsPipeline.from_config(cfg, per_channel=config["per_channel"])


def out_shape(geom: dict) -> tuple:
    return geom["num_classes"], geom["caps_dim"]


def _convs(geom: dict) -> list:
    """(name, in h, w, c, out h, w, filters, kernel) per conv, primary
    caps last."""
    h, w, c = geom["input_shape"]
    specs = list(zip(geom["conv_filters"], geom["conv_kernels"],
                     geom["conv_strides"]))
    specs.append((geom["pcap_caps"] * geom["pcap_dim"], geom["pcap_kernel"],
                  geom["pcap_stride"]))
    out = []
    for i, (f, k, s) in enumerate(specs):
        ho, wo = (h - k) // s + 1, (w - k) // s + 1
        name = "pcap" if i == len(specs) - 1 else f"conv{i}"
        out.append((name, h, w, c, ho, wo, f, k))
        h, w, c = ho, wo, f
    return out


def input_caps(geom: dict) -> int:
    """I, the number of primary capsules the geometry yields."""
    _, _, _, _, ho, wo, _, _ = _convs(geom)[-1]
    return ho * wo * geom["pcap_caps"]


def layers(geom: dict) -> list:
    """One entry per layer: name, kind ("conv", "uhat" or "routing"),
    `macs` and `act_bytes` per image, and `weight_bytes` per wave."""
    out = [{"name": name, "kind": "conv", "macs": ho * wo * f * k * k * c,
            "act_bytes": h * w * c + ho * wo * f,
            "weight_bytes": k * k * c * f + f}
           for name, h, w, c, ho, wo, f, k in _convs(geom)]
    J, O, D = geom["num_classes"], geom["caps_dim"], geom["pcap_dim"]
    I, r = input_caps(geom), geom["routings"]
    out.append({"name": "uhat", "kind": "uhat", "macs": J * I * O * D,
                "act_bytes": I * D + J * I * O,
                "weight_bytes": J * I * O * D})
    # r weighted sums s_j = sum_i c_ij u_hat_ji, and r - 1 agreements
    out.append({"name": "routing", "kind": "routing",
                "macs": (2 * r - 1) * J * I * O,
                "act_bytes": J * I * O + J * O, "weight_bytes": 0})
    return out


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


def reference(geom: dict, params: dict, calib, images, bits: int = 8) -> tuple:
    """(v in Q0.7, pred) for every image, at integer width `bits`."""
    params = as_float32(params)
    plan = make_plan(geom, params, calibrate(geom, params, calib), bits)
    qw = quantize_weights(geom, params, plan, bits)
    return answers(lambda x: forward_int(geom, qw, plan, x, bits), images,
                   bits)
