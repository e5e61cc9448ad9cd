"""The reduction from a profiler trace to metrics, on synthetic events
and on a trace recorded on one TPU v5e (trimmed, committed under
benchmarks/chip/fixtures/)."""
import json
import pathlib

import pytest

from benchmarks.chip import trace

FIXTURES = pathlib.Path(trace.__file__).resolve().parent / "fixtures"


def test_union_of_intervals():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9), (4, 4)]) == \
        [[0, 3], [5, 9]]
    assert trace.busy_ns([(0, 10), (2, 4), (8, 12), (20, 21)]) == 13.0
    assert trace.busy_ns([]) == 0.0


def test_clip_to_window():
    evs = [("a", 0, 10), ("b", 15, 10), ("c", 30, 5)]
    assert trace.clip(evs, 5, 20) == [("a", 5, 5), ("b", 15, 5)]


def _events():
    # one device, ops at [10,20) [15,30) [50,60) [90,95) inside a window
    # [0,100); the host was in bench.step over [0,40) and [45,100),
    # in bench.submit over [40,45)
    device = {"/device:TPU:0": [("conv.1", 10, 10), ("routing.1", 15, 15),
                                ("conv.1", 50, 10), ("other.3", 90, 5),
                                ("late", 200, 5)]}
    host = [("bench.window", 0, 100), ("bench.step", 0, 40),
            ("bench.submit", 40, 5), ("bench.step", 45, 55)]
    return {"device": device, "host": host}


def test_reduce_idle_and_classes():
    r = trace.reduce(_events(), (0, 100),
                     {"conv.1": "conv", "routing.1": "routing_kernel"},
                     {"routing.1": "routing"})
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(35e-9)          # 10..30, 50..60, 90..95
    assert r["class_s"] == pytest.approx(
        {"conv": 20e-9, "routing_kernel": 15e-9, "other": 5e-9})
    assert r["kernel_s"] == pytest.approx({"routing": 15e-9})
    assert r["device_ops"][0] == ["conv.1", pytest.approx(20e-9)]
    # gaps [0,10) [30,50) [60,90) [95,100), longest first, each named by
    # the innermost host span that covers most of it
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [30e-9, 20e-9, 10e-9, 5e-9])
    assert [g[0] for g in r["idle_gaps"]] == \
        ["bench.step", "bench.step", "bench.step", "bench.step"]


def test_tpu_events_named_by_instruction_text():
    """A TPU "XLA Ops" event carries the instruction's whole text; its
    class is looked up by the instruction's name."""
    ev = _events()
    ev["device"]["/device:TPU:0"] = [
        ("%clamp_convert_fusion.2 = s8[64,8,8,64]{3,0,2,1:T(8,128)(4,1)S(1)} "
         "fusion(s32[7,7,16,64]{3,2,1,0:T(8,128)} %constant.25), "
         "kind=kOutput, calls=%fused_computation.5", 10, 10),
        ("%routing_q7_pallas.1 = s32[64,10,6,1]{3,2,1,0:T(8,128)S(1)} "
         "custom-call(s8[64,10,6,1024]{3,2,1,0} %fusion.2), "
         "custom_call_target=\"tpu_custom_call\"", 15, 15),
        ("%copy.10 = s32[1024,64,10,6]{0,3,2,1:T(8,128)S(1)} copy(%fusion)",
         90, 5)]
    r = trace.reduce(ev, (0, 100), {"clamp_convert_fusion.2": "conv",
                                    "routing_q7_pallas.1": "routing_kernel"},
                     {"routing_q7_pallas.1": "routing_q7_pallas"})
    assert r["class_s"] == pytest.approx(
        {"conv": 10e-9, "routing_kernel": 15e-9, "other": 5e-9})
    assert trace.instr_name("routing_q7_pallas.1") == "routing_q7_pallas.1"


def test_gap_named_by_the_span_covering_most_of_it():
    host = [("bench.window", 0, 100), ("bench.submit", 30, 15),
            ("bench.step", 45, 55)]
    assert trace._cover(host, 30, 50) == "bench.submit"
    assert trace._cover(host, 0, 10) == "bench.window"
    assert trace._cover([], 0, 10) == "untraced"


def test_no_device_ops_reads_nothing():
    r = trace.reduce({"device": {}, "host": []}, (0, 100), {}, {})
    assert r["busy_s"] is None and r["devices"] == 0
    assert r["kernel_s"] == {}


HLO = """\
%fused_computation.6 (p0: s8[1,28,28,1], p1: s32[7,7,1,16]) -> s8[1,22,22,16] {
  %c = s32[1,22,22,16]{3,2,1,0} convolution(%a, %b), window={size=7x7}, metadata={op_name="jit(fn)/conv_general_dilated"}
  ROOT %r = s8[1,22,22,16]{3,2,1,0} convert(%c)
}

%fused_computation.5 (p0: s32[7,7,16,64], p1: s8[1,28,28,1]) -> s8[1,8,8,64] {
  %fusion.3.clone = s8[1,22,22,16]{3,2,1,0} fusion(%p1, %w), kind=kOutput, calls=%fused_computation.6
  ROOT %x = s8[1,8,8,64]{3,2,1,0} convert(%y)
}

%fused_computation.1 (p0: s8[10,1024,6,4]) -> s32[1024,1,10,6] {
  ROOT %d = s32[1024,1,10,6]{3,2,1,0} convolution(%p0, %q), window={size=1024x6}, metadata={op_name="jit(fn)/jiod,bid->bjio/dot_general"}
}

ENTRY %main.11 (x.1: f32[1,28,28,1]) -> s8[1,10,6] {
  %clamp_convert_fusion.2 = s8[1,8,8,64]{3,2,1,0} fusion(%c25, %x3, %c30), kind=kOutput, calls=%fused_computation.5, metadata={op_name="conv"}
  %squash_q7_pallas.1 = s8[4096,4]{1,0} custom-call(%r2), custom_call_target="tpu_custom_call"
  %fusion = s32[1024,1,10,6]{3,2,1,0} fusion(%cc, %b13), kind=kOutput, calls=%fused_computation.1
  %routing_q7_pallas.1 = s32[1,10,6,1]{3,2,1,0} custom-call(%f2), custom_call_target="tpu_custom_call"
  ROOT %t = (s8[1,10,6]) tuple(%b14)
}
"""


def test_classify_hlo():
    c = trace.classify_hlo(HLO)
    assert c["clamp_convert_fusion.2"] == "conv"
    assert c["routing_q7_pallas.1"] == "routing_kernel"
    assert c["squash_q7_pallas.1"] == "other"
    assert "fusion" not in c      # u_hat's dot, lowered to a convolution


def test_kernel_names():
    """Every Mosaic kernel by its own name, and nothing else."""
    assert trace.kernel_names(HLO) == {
        "squash_q7_pallas.1": "squash_q7_pallas",
        "routing_q7_pallas.1": "routing_q7_pallas"}


@pytest.mark.parametrize("name, convs", [("mnist_L", 4), ("cifar10_S", 10)])
def test_classify_a_wave_compiled_for_v5e(name, convs):
    """The bucket-64 `@pallas` wave of each configuration as the TPU
    compiler emits it (compiled for a described v5e)."""
    c = trace.classify_hlo((FIXTURES / f"{name}.wave64.hlo.txt").read_text())
    assert sorted(k for k, v in c.items() if v == "routing_kernel") == \
        ["routing_q7_pallas.1"]
    assert c["squash_q7_pallas.1"] == "other"
    assert sum(v == "conv" for v in c.values()) == convs


@pytest.mark.parametrize("name, busy, conv, routing", [
    ("mnist_L", 0.067446541, 0.000169939, 0.013903861),
    ("cifar10_S", 0.016135216, 0.000575672, 0.012189514)])
def test_reduce_a_trace_recorded_on_v5e(name, busy, conv, routing):
    """The first three waves of a backlog window traced on one TPU v5e:
    device ops named by instruction text, the `bench.*` host spans, and
    the classes `classify_hlo` read off that run's compiled wave."""
    rec = json.loads((FIXTURES / f"{name}.backlog.trace.json").read_text())
    hlo = (FIXTURES / f"{name}.wave64.hlo.txt").read_text()
    r = trace.reduce(rec, tuple(rec["window"]), rec["classes"],
                     trace.kernel_names(hlo))
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(busy)
    assert r["busy_s"] <= r["window_s"]
    assert r["class_s"]["conv"] == pytest.approx(conv)
    assert r["class_s"]["routing_kernel"] == pytest.approx(routing)
    assert all(g[0].startswith("bench.") for g in r["idle_gaps"])
    # the chip's compile classifies as the described v5e's did
    c = trace.classify_hlo(hlo)
    assert rec["classes"].items() <= c.items()


@pytest.mark.parametrize("name", ["mnist_L", "cifar10_S"])
def test_kernel_time_on_a_trace_recorded_on_v5e(name):
    """Device time per Pallas kernel: the routing kernel's is its class's,
    and the squash kernel, which has no class of its own, has one."""
    rec = json.loads((FIXTURES / f"{name}.backlog.trace.json").read_text())
    kernels = trace.kernel_names(
        (FIXTURES / f"{name}.wave64.hlo.txt").read_text())
    assert sorted(kernels.values()) == ["routing_q7_pallas",
                                        "squash_q7_pallas"]
    r = trace.reduce(rec, tuple(rec["window"]), rec["classes"], kernels)
    assert set(r["kernel_s"]) == {"routing_q7_pallas", "squash_q7_pallas"}
    assert r["kernel_s"]["routing_q7_pallas"] == \
        r["class_s"]["routing_kernel"]
    assert 0 < r["kernel_s"]["squash_q7_pallas"] <= r["class_s"]["other"]
