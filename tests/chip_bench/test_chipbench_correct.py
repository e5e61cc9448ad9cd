"""The comparison that decides `correct`, on the CPU at a tiny geometry.

The reference has to agree with the program bit for bit; its control
(the same reference at int4) and a run whose answers are altered where
the wave produces them have to come out as not correct.  The harness
runs without its look for a chip: `run_cell` is the rest of a run.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import (bench, control, harness, images, peaks,
                             reference, run, traffic)

# 16x16 gray -> conv8 k5 s2 -> 6x6 -> primary caps 4x4 k3 s2 -> 2x2x4 = 16
# capsules -> 4 classes of 4, 2 routings
TINY = {
    "name": "capsnet_tiny",
    "model": "capsnet",
    "geometry": {"input_shape": [16, 16, 1], "conv_filters": [8],
                 "conv_kernels": [5], "conv_strides": [2], "pcap_caps": 4,
                 "pcap_dim": 4, "pcap_kernel": 3, "pcap_stride": 2,
                 "num_classes": 4, "caps_dim": 4, "routings": 2},
    "precision": "int8", "rounding": "floor", "per_channel": False,
    "backend": "pallas", "calib_n": 16, "calibration_precision": "highest",
    "images": "edge_tiny",
}
CLOSED = {"arrival": "closed", "depth_waves": 2, "pool": 24,
          "warm": "max_bucket"}
OPEN = {"arrival": "open", "rate_per_s": 400, "pool": 24, "warm": "all"}
CAPSNET = bench.model(TINY)


def _spec(mix):
    return {"config": TINY, "mix": mix, "cell": {"chips": 1},
            "end_to_end": [{"name": "setup_s", "unit": "s"},
                           {"name": "images_per_s", "unit": "images/s"},
                           {"name": "p99_ms", "unit": "ms"}],
            "per_layer": []}


def _run(mix, seconds=0.4):
    return run.run_cell(_spec(mix), 2**31 + 3, seconds, False,
                        jax.devices()[:1], peaks.PEAKS["TPU v5 lite"],
                        time.perf_counter())


@pytest.mark.parametrize("geometry", ["tiny", "mnist_L"])
def test_reference_is_the_program_bit_for_bit(geometry):
    from repro.nn.config import CapsNetConfig
    from repro.nn.pipeline import CapsPipeline
    if geometry == "tiny":
        g, kind = TINY["geometry"], "edge_tiny"
    else:
        g, kind = {"input_shape": [28, 28, 1], "conv_filters": [16],
                   "conv_kernels": [7], "conv_strides": [1],
                   "pcap_caps": 16, "pcap_dim": 4, "pcap_kernel": 7,
                   "pcap_stride": 2, "num_classes": 10, "caps_dim": 6,
                   "routings": 3}, "mnist"
    rngs = traffic.streams(11)
    params = CAPSNET.make_params(g, rngs["weights"])
    calib = images.make_images(kind, g["input_shape"], 16, rngs["calib"])
    x = images.make_images(kind, g["input_shape"], 6, rngs["pool"])
    cfg = CapsNetConfig("t", tuple(g["input_shape"]),
                        tuple(g["conv_filters"]), tuple(g["conv_kernels"]),
                        tuple(g["conv_strides"]), pcap_caps=g["pcap_caps"],
                        pcap_dim=g["pcap_dim"], pcap_kernel=g["pcap_kernel"],
                        pcap_stride=g["pcap_stride"],
                        num_classes=g["num_classes"], caps_dim=g["caps_dim"],
                        routings=g["routings"])
    qnet = CapsPipeline.from_config(cfg).quantize(params, jnp.asarray(calib))
    v = np.asarray(qnet.forward(qnet.quantize_input(jnp.asarray(x))))
    v_ref, pred_ref = CAPSNET.reference(g, jax.device_get(params), calib,
                                        x, 8)
    np.testing.assert_array_equal(v, v_ref)
    lengths = np.asarray(qnet.class_lengths(jnp.asarray(v)))
    np.testing.assert_array_equal(lengths.argmax(-1), pred_ref)


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_one_precision_lower_is_not_correct(seed):
    nums = control.control_numbers(TINY, CLOSED, seed)
    assert nums["vq_mismatch"] > harness.LIMITS["vq_mismatch"]


def test_isqrt_and_shift_softmax_are_exact():
    n = np.arange(0, 5000, dtype=np.int32)
    np.testing.assert_array_equal(reference._isqrt(n),
                                  np.floor(np.sqrt(n)).astype(np.int32))
    c = reference.softmax_int(np.array([[0, 0, -128, 64]]), 6, 8)
    # 2^floor((b - max) / 2^6): 1/2, 1/2, 2^-3, 1 over a sum of 2.125
    np.testing.assert_array_equal(c, [[30, 30, 7, 60]])


@pytest.mark.parametrize("mix", [CLOSED, OPEN], ids=["closed", "open"])
def test_harness_run_is_correct(mix):
    result, log = _run(mix)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in result["checks"].values())
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) >= {"setup_s"}
    assert result["device"]["count"] == 1


def test_an_answer_altered_in_the_wave_is_not_correct(monkeypatch):
    from repro.serving import sharded
    wave = sharded.CompiledWave.__call__

    def altered(self, x):
        v_q, lengths, pred = wave(self, x)
        v_q = np.array(v_q)
        v_q[0, 0, 0] ^= 1                   # one bit of one answer
        return v_q, lengths, pred

    monkeypatch.setattr(sharded.CompiledWave, "__call__", altered)
    result, _ = _run(CLOSED)
    assert not result["correct"]
    assert result["checks"]["vq_mismatch"]["value"] > 0
    assert result["failed"] > 0


def _cli(cwd, env_extra):
    import os
    import subprocess
    import sys
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env_extra}
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mnist_L.backlog", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_exits_1_and_prints_no_result():
    import pathlib
    r = _cli(pathlib.Path(run.__file__).resolve().parents[2], {})
    assert r.returncode == 1, r.stderr[-2000:]
    assert "{" not in r.stdout and "needs 1 TPU" in r.stderr


def test_the_benchmark_alone_does_not_run(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's own files but
    no program exits non-zero and prints no result."""
    import pathlib
    import shutil
    root = pathlib.Path(run.__file__).resolve().parents[2]
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cli(tmp_path, {"PYTHONPATH": ""})
    assert r.returncode != 0 and "{" not in r.stdout
