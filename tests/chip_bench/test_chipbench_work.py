"""Work counts, peaks and the BENCHMARK.json they serve: pinned to hand
counts from the paper's geometries, no chip needed."""
import json
import pathlib
import re

import pytest

from benchmarks.chip import bench, peaks, work

ROOT = pathlib.Path(__file__).resolve().parents[2]
CAPSNET = bench.model({"model": "capsnet"})


def _geom(name):
    return json.loads((ROOT / "benchmarks/chip/configs" / f"{name}.json")
                      .read_text())["geometry"]


@pytest.mark.parametrize("config, macs, per_layer", [
    # MNIST "L": conv1 22x22x16 from 7x7x1, primary caps 8x8x64 from
    # 7x7x16, u_hat 10x1024x6x4, routing 5 x 10x1024x6
    ("capsnet_mnist_L", 4_143_680,
     {"conv0": 379_456, "pcap": 3_211_264, "uhat": 245_760,
      "routing": 307_200}),
    # CIFAR-10 "S": four 3x3 convs, primary caps 2x2x64, I = 64
    ("capsnet_cifar10_S", 12_621_312,
     {"conv0": 777_600, "conv1": 7_225_344, "conv2": 3_115_008,
      "conv3": 1_327_104, "pcap": 147_456, "uhat": 12_800,
      "routing": 16_000}),
])
def test_macs_per_image_match_hand_counts(config, macs, per_layer):
    g = _geom(config)
    assert work.macs_per_image(CAPSNET.layers(g)) == macs
    assert {l["name"]: l["macs"] for l in CAPSNET.layers(g)} == per_layer


def test_conv_share_of_mnist_work():
    g = _geom("capsnet_mnist_L")
    assert work.macs_per_image(CAPSNET.layers(g), {"conv"}) == 3_590_720
    assert CAPSNET.input_caps(g) == 1024


def test_work_counts_padding_rows_and_weights_once_per_wave():
    g = _geom("capsnet_mnist_L")
    ops, nbytes = work.work(CAPSNET.layers(g), {"routing"}, rows=128,
                            waves=2)
    assert ops == 2 * 307_200 * 128
    # u_hat int8 read (10 x 1024 x 6) and v written (10 x 6) per row
    assert nbytes == (61_440 + 60) * 128
    ops, nbytes = work.work(CAPSNET.layers(g), {"conv"}, rows=64, waves=1)
    conv_w = 7 * 7 * 1 * 16 + 16 + 7 * 7 * 16 * 64 + 64
    assert nbytes == (784 + 7744 + 7744 + 4096) * 64 + conv_w


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v99")
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["int8_ops"], v5e["hbm_bytes_per_s"]) \
        == (197e12, 393e12, 819e9)


def test_least_time_names_its_bound():
    assert peaks.least_time_s(393e12, 1.0, 393e12, 819e9) == (1.0,
                                                             "compute")
    assert peaks.least_time_s(1.0, 819e9, 393e12, 819e9) == (1.0, "memory")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_resolves_every_cell():
    b = bench.load(ROOT)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(bench.reader(m["name"]))
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in b["workloads"]:
        spec = bench.resolve(b, w["name"], ROOT)
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"], w["name"]
        for m in spec["per_layer"]:
            assert m["moves"] in names, (w["name"], m["name"])
        assert spec["config"]["name"] == w["config"]
        assert len(w["why"]) <= 200


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError, match="no workload"):
        bench.resolve(bench.load(ROOT), "nope.backlog", ROOT)


@pytest.mark.parametrize("config, program", [
    ("capsnet_mnist_L", "MNIST"), ("capsnet_cifar10_S", "CIFAR10")])
def test_configs_hold_the_published_geometry(config, program):
    from repro.nn import config as nn_config
    c = getattr(nn_config, program)
    g = _geom(config)
    assert tuple(g["input_shape"]) == c.input_shape
    assert (tuple(g["conv_filters"]), tuple(g["conv_kernels"]),
            tuple(g["conv_strides"])) == (c.conv_filters, c.conv_kernels,
                                          c.conv_strides)
    assert (g["pcap_caps"], g["pcap_dim"], g["pcap_kernel"],
            g["pcap_stride"], g["num_classes"], g["caps_dim"],
            g["routings"]) == (c.pcap_caps, c.pcap_dim, c.pcap_kernel,
                               c.pcap_stride, c.num_classes, c.caps_dim,
                               c.routings)
    assert CAPSNET.input_caps(g) == c.num_input_caps
