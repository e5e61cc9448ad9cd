"""Operations and bytes per image of each layer, from a configuration's
geometry alone (the benchmark's own config file, not the program).

An int8 multiply-accumulate is one MAC and two operations.  Bytes are
the int8 operands the algorithm has to move through HBM: activations in
and out per image, weights once per wave.
"""
from __future__ import annotations


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


def macs_per_image(geom: dict, kinds=None) -> int:
    return sum(l["macs"] for l in layers(geom)
               if kinds is None or l["kind"] in kinds)


def work(geom: dict, kinds, rows: int, waves: int) -> tuple:
    """(operations, bytes) of the layers of `kinds` over `waves` waves
    that compute `rows` rows in all (padding rows included: the device
    computes them)."""
    ops = nbytes = 0
    for l in layers(geom):
        if l["kind"] in kinds:
            ops += 2 * l["macs"] * rows
            nbytes += l["act_bytes"] * rows + l["weight_bytes"] * waves
    return ops, nbytes
