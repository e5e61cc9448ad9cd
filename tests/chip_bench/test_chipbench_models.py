"""Model modules (`benchmarks/chip/models/<name>.py`): the configuration
names its module, the CapsNet template draws and counts what it did
before it moved into `models/capsnet.py` (digests recorded from the
harness before the move), and a module of another structure runs
through the harness with no file of the benchmark changed."""
import hashlib
import json
import pathlib
import textwrap

import jax
import numpy as np
import pytest

from benchmarks.chip import bench, harness, images, traffic

from test_chipbench_correct import CLOSED, TINY

ROOT = pathlib.Path(__file__).resolve().parents[2]
CAPSNET = bench.model(TINY)


def _config(name):
    return json.loads((ROOT / "benchmarks/chip/configs" / f"{name}.json")
                      .read_text())


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# which module
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["capsnet_mnist_L", "capsnet_cifar10_S"])
def test_configurations_name_the_capsnet_module(name):
    config = _config(name)
    assert config["model"] == "capsnet"
    assert bench.model(config).out_shape(config["geometry"]) == (
        config["geometry"]["num_classes"], config["geometry"]["caps_dim"])


@pytest.mark.parametrize("model", [None, "nope", "../configs/x"])
def test_a_configuration_without_a_known_model_is_refused(model):
    config = {k: v for k, v in TINY.items() if k != "model"}
    if model is not None:
        config["model"] = model
    with pytest.raises(ValueError, match=r"must name one of \['capsnet'\]"):
        bench.model(config)
    with pytest.raises(ValueError, match="capsnet"):
        harness.build_cell(config, CLOSED, 1)


# ---------------------------------------------------------------------------
# pins: the template draws and counts what it did before the move
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name, digest", [
    ("capsnet_mnist_L", "e23988f45283037a"),
    ("capsnet_cifar10_S", "0d485456947c43e8")])
def test_weights_are_the_ones_drawn_before(name, digest):
    g = _config(name)["geometry"]
    p = jax.device_get(CAPSNET.make_params(
        g, traffic.streams(2**31 + 7)["weights"]))
    assert _digest(*[np.asarray(p[k][n]) for k in sorted(p)
                     for n in sorted(p[k])]) == digest


def _layer(name, kind, macs, act, weight):
    return {"name": name, "kind": kind, "macs": macs, "act_bytes": act,
            "weight_bytes": weight}


@pytest.mark.parametrize("name, layers", [
    ("capsnet_mnist_L", [
        _layer("conv0", "conv", 379456, 8528, 800),
        _layer("pcap", "conv", 3211264, 11840, 50240),
        _layer("uhat", "uhat", 245760, 65536, 245760),
        _layer("routing", "routing", 307200, 61500, 0)]),
    ("capsnet_cifar10_S", [
        _layer("conv0", "conv", 777600, 31872, 896),
        _layer("conv1", "conv", 7225344, 53888, 9248),
        _layer("conv2", "conv", 3115008, 35904, 18496),
        _layer("conv3", "conv", 1327104, 13120, 36928),
        _layer("pcap", "conv", 147456, 2560, 36928),
        _layer("uhat", "uhat", 12800, 3456, 12800),
        _layer("routing", "routing", 16000, 3250, 0)])])
def test_layers_are_the_ones_counted_before(name, layers):
    assert CAPSNET.layers(_config(name)["geometry"]) == layers


MNIST_L = {"input_shape": [28, 28, 1], "conv_filters": [16],
           "conv_kernels": [7], "conv_strides": [1], "pcap_caps": 16,
           "pcap_dim": 4, "pcap_kernel": 7, "pcap_stride": 2,
           "num_classes": 10, "caps_dim": 6, "routings": 3}


@pytest.mark.parametrize("geometry, digest, pred", [
    ("tiny", "aecb627a8bd0e924", [1, 1, 1, 0, 2, 1]),
    ("mnist_L", "b33f6e540e94a85d", [8, 8, 6, 4, 6, 1])])
def test_reference_answers_are_the_ones_given_before(geometry, digest, pred):
    """The inputs of test_reference_is_the_program_bit_for_bit."""
    g, kind = (TINY["geometry"], "edge_tiny") if geometry == "tiny" \
        else (MNIST_L, "mnist")
    rngs = traffic.streams(11)
    params = jax.device_get(CAPSNET.make_params(g, rngs["weights"]))
    calib = images.make_images(kind, g["input_shape"], 16, rngs["calib"])
    x = images.make_images(kind, g["input_shape"], 6, rngs["pool"])
    v, p = CAPSNET.reference(g, params, calib, x, 8)
    assert (_digest(v), p.tolist()) == (digest, pred)


@pytest.mark.parametrize("kind, digest", [
    ("cifar10", "ac445c7a39c4ec87"), ("edge_tiny", "5798a262ee752a83"),
    ("mnist", "7c1d8ecd380c90cc"), ("smallnorb", "9342f1c5ebdaa374")])
def test_images_are_the_ones_drawn_before(kind, digest):
    shape = images.KINDS[kind][:3]
    x = images.make_images(kind, shape, 5, np.random.default_rng(2**31 + 9))
    assert _digest(x) == digest


# ---------------------------------------------------------------------------
# room for another architecture
# ---------------------------------------------------------------------------
# conv -> primary capsules -> capsules routed from them -> class capsules
# routed from those: the structure of DeepCaps' last two capsule layers,
# built from the program's layer classes and compared with a reference
# composed from reference.py's primitives
CAPS2 = textwrap.dedent('''
    """conv -> primary caps -> routed capsules -> routed class capsules."""
    import numpy as np

    from benchmarks.chip.reference import (
        _conv, _exact_int, _rshift_sat, _softmax_f, _squash_f, answers,
        as_float32, frac_bits, qrange, quantize, softmax_int, squash_int)


    def _convs(g):
        """(name, kernel, stride, in ch, out ch, relu, out h, out w)."""
        h, w, c = g["input_shape"]
        out = []
        for name, k, s, f, relu in (
                ("conv0", g["conv"]["kernel"], g["conv"]["stride"],
                 g["conv"]["filters"], True),
                ("pcap", g["primary"]["kernel"], g["primary"]["stride"],
                 g["primary"]["caps"] * g["primary"]["dim"], False)):
            h, w = (h - k) // s + 1, (w - k) // s + 1
            out.append((name, k, s, c, f, relu, h, w))
            c = f
        return out


    def _caps(g):
        """(name, J, I, O, D, routings) per routed layer, classes last."""
        *_, h, w = _convs(g)[-1]
        i, d = h * w * g["primary"]["caps"], g["primary"]["dim"]
        out = []
        for n, l in enumerate(g["capsules"]):
            name = "caps" if n == len(g["capsules"]) - 1 else f"caps{n + 1}"
            out.append((name, l["caps"], i, l["dim"], d, l["routings"]))
            i, d = l["caps"], l["dim"]
        return out


    def make_params(g, rng):
        import jax
        import jax.numpy as jnp
        key = jax.random.key(int(rng.integers(0, 2 ** 32)))

        def init(key):
            ks = iter(jax.random.split(key, 8))
            out = {}
            for name, k, _, c, f, relu, _, _ in _convs(g):
                w = jax.random.normal(next(ks), (k, k, c, f), jnp.float32)
                out[name] = {"w": w * ((2.0 if relu else 1.0) / (k * k * c))
                             ** 0.5,
                             "b": jax.random.normal(next(ks), (f,)) * 0.01}
            for name, J, I, O, D, _ in _caps(g):
                # unit variance keeps |s| near 1 through both layers
                out[name] = {"W": jax.random.normal(next(ks), (J, I, O, D))}
            return out

        return jax.jit(init)(key)


    def pipeline(config):
        from repro.nn.config import CapsNetConfig
        from repro.nn.layers import CapsuleRouting, PrimaryCaps, QuantConv2D
        from repro.nn.pipeline import CapsPipeline
        g = config["geometry"]
        (_, k0, s0, c0, f0, _, _, _), (_, k1, s1, c1, _, _, _, _) = \\
            _convs(g)
        layers = (QuantConv2D("conv0", k0, s0, c0, f0, relu=True),
                  PrimaryCaps("pcap", k1, s1, c1, g["primary"]["caps"],
                              g["primary"]["dim"]))
        layers += tuple(CapsuleRouting(name, J, I, O, D, r)
                        for name, J, I, O, D, r in _caps(g))
        cfg = CapsNetConfig(config["name"], tuple(g["input_shape"]), (), (),
                            ())
        return CapsPipeline(cfg=cfg, layers=layers)


    def out_shape(g):
        _, J, _, O, _, _ = _caps(g)[-1]
        return J, O


    def layers(g):
        out = [{"name": name, "kind": "conv",
                "macs": ho * wo * f * k * k * c, "act_bytes": ho * wo * f,
                "weight_bytes": k * k * c * f + f}
               for name, k, _, c, f, _, ho, wo in _convs(g)]
        for name, J, I, O, D, r in _caps(g):
            out.append({"name": f"{name}.uhat", "kind": "uhat",
                        "macs": J * I * O * D, "act_bytes": J * I * O,
                        "weight_bytes": J * I * O * D})
            out.append({"name": f"{name}.routing", "kind": "routing",
                        "macs": (2 * r - 1) * J * I * O,
                        "act_bytes": J * O, "weight_bytes": 0})
        return out


    def _calibrate(g, params, x):
        h = np.asarray(x, np.float64)
        taps = {"input": np.abs(h).max()}
        for name, _, stride, _, _, relu, _, _ in _convs(g):
            y = _conv(h, params[name]["w"], stride) + params[name]["b"]
            taps[f"{name}.out"] = np.abs(y).max()
            h = np.maximum(y, 0) if relu else y
        u = _squash_f(h.reshape(h.shape[0], -1, g["primary"]["dim"]))
        for name, _, _, _, _, routings in _caps(g):
            u_hat = np.einsum("jiod,bid->bjio",
                              params[name]["W"].astype(np.float64), u)
            taps[f"{name}.u_hat"] = np.abs(u_hat).max()
            b = np.zeros(u_hat.shape[:3])
            for r in range(routings):
                s = np.einsum("bji,bjio->bjo", _softmax_f(b, axis=1), u_hat)
                taps[f"{name}.s/{r}"] = np.abs(s).max()
                u = _squash_f(s)
                if r < routings - 1:
                    b = b + np.einsum("bjio,bjo->bji", u_hat, u)
                    taps[f"{name}.logits/{r}"] = np.abs(b).max()
        return taps


    def _forward_int(g, params, taps, x, bits):
        qmin, qmax, unit = qrange(bits)

        def fb(v):
            return frac_bits(v, qmax)

        f_act = fb(taps["input"])
        h = quantize(x, f_act, bits)
        for name, _, stride, _, _, relu, _, _ in _convs(g):
            w, b = params[name]["w"], params[name]["b"]
            f_w, f_b = fb(np.abs(w).max()), fb(np.abs(b).max())
            f_out = fb(taps[f"{name}.out"])
            acc = _exact_int(_conv(h, quantize(w, f_w, bits), stride))
            qb = quantize(b, f_b, bits)
            sh = f_act + f_w - f_b
            qb = np.left_shift(qb, sh) if sh >= 0 else np.right_shift(qb, -sh)
            h = _rshift_sat(acc + qb, f_act + f_w - f_out, bits)
            h = np.maximum(h, 0) if relu else h
            f_act = f_out
        u = squash_int(h.reshape(h.shape[0], -1, g["primary"]["dim"]),
                       f_act, bits)
        for name, _, _, _, _, R in _caps(g):
            W = params[name]["W"]
            f_W, f_uhat = fb(np.abs(W).max()), fb(taps[f"{name}.u_hat"])
            f_logit = min(fb(max([taps[f"{name}.logits/{r}"]
                                  for r in range(R - 1)] + [1e-6])), unit)
            acc = np.einsum("jiod,bid->bjio",
                            quantize(W, f_W, bits).astype(np.float64),
                            u.astype(np.float64))
            u_hat = _rshift_sat(_exact_int(acc), unit + f_W - f_uhat, bits)
            uf = u_hat.astype(np.float64)
            b = np.zeros(u_hat.shape[:3], np.int32)
            for r in range(R):
                c = softmax_int(b.swapaxes(1, 2), f_logit, bits).swapaxes(1, 2)
                s = _exact_int(np.matmul(c[:, :, None, :].astype(np.float64),
                                         uf))[:, :, 0, :]
                f_s = fb(taps[f"{name}.s/{r}"])
                u = squash_int(_rshift_sat(s, f_uhat + unit - f_s, bits), f_s,
                               bits)
                if r < R - 1:
                    a = _exact_int(np.matmul(uf, u[..., None]
                                             .astype(np.float64)))[..., 0]
                    a = _rshift_sat(a, f_uhat + unit - f_logit, bits)
                    b = np.clip(b + a, qmin, qmax).astype(np.int32)
        return u


    def reference(g, params, calib, images, bits=8):
        params = as_float32(params)
        taps = _calibrate(g, params, calib)
        return answers(lambda x: _forward_int(g, params, taps, x, bits),
                       images, bits)
''')

CAPS2_CONFIG = {
    "name": "caps2_tiny", "model": "caps2",
    "geometry": {"input_shape": [16, 16, 1],
                 "conv": {"filters": 8, "kernel": 5, "stride": 2},
                 "primary": {"caps": 4, "dim": 4, "kernel": 3, "stride": 2},
                 "capsules": [{"caps": 8, "dim": 8, "routings": 2},
                              {"caps": 10, "dim": 6, "routings": 2}]},
    "precision": "int8", "rounding": "floor", "per_channel": False,
    "backend": "pallas", "calib_n": 16, "calibration_precision": "highest",
    "images": "edge_tiny",
}


def test_a_model_of_another_structure_runs_through_the_harness(
        tmp_path, monkeypatch):
    """A module written into a models directory of its own: two routed
    capsule layers, built from the program's layer classes into a
    `CapsPipeline`.  build_cell -> drive -> check on the CPU is correct,
    and its int4 control is not."""
    (tmp_path / "caps2.py").write_text(CAPS2)
    monkeypatch.setattr(bench, "MODELS_DIR", tmp_path)
    cell = harness.build_cell(CAPS2_CONFIG, CLOSED, 2**31 + 21)
    assert [l.name for l in cell.registry.model(cell.model_id)
            .pipeline.layers] == ["conv0", "pcap", "caps1", "caps"]
    win = harness.drive(cell, 0.3)
    assert win.v_q.shape[1:] == (10, 6)
    served = ~np.isnan(win.done_s)
    assert np.count_nonzero(win.v_q[served]) > win.v_q[served].size // 2
    assert len(np.unique(win.pred[served])) > 1
    chk = harness.check(cell, win)
    assert chk["checked"] > 0 and chk["failed"] == 0, chk
    assert all(chk["numbers"][k] <= harness.LIMITS[k]
               for k in harness.LIMITS)
    # the comparison sees the second routed layer: its reference one
    # precision lower does not pass
    v4, _ = cell.model.reference(CAPS2_CONFIG["geometry"], cell.params,
                                 cell.calib, cell.pool[win.pool_idx[served]],
                                 4)
    assert np.sum(v4 != win.v_q[served]) > 0
