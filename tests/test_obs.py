"""Tests for the unified observability layer (repro.obs + the MCU cost
model + the bench artifact schema).

Pins, in order:
  * span trees under a fake clock: nesting, timestamps, Chrome
    trace-event export — exact, no wall-clock flakiness;
  * tracing off == zero objects: `obs.span()` returns the one shared
    NULL_SPAN when no tracer is ambient;
  * the metrics registry: labeled series, kind conflicts, JSON-safe
    snapshots, and the Counter-shaped views the pre-obs attributes
    became (PallasBackend.fallbacks, ModelRegistry counts);
  * ServeMetrics empty-window behavior: summary()/report() are explicit
    (None / "no completed requests"), never formatted NaNs, while the
    low-level accessors keep their pinned nan-on-empty contract;
  * traced serving is bit-identical to untraced and emits the nested
    enqueue -> wave -> bucket/transfer/dispatch/wait/readback/complete
    span forest as valid Chrome JSON; under the JAX profiler the same
    spans land on its host plane; the engine clocks each wave's phases
    into ServeMetrics whether or not a tracer is installed;
  * EdgeVM with `profile`/`trace`/ambient tracing returns the same bits
    as the bare hot path, for every config x rounding;
  * the static MCU cost model reproduces the paper's four latencies
    (Cortex-M7 119.94/90.60 ms, GAP-8 7.02/38.03 ms) on the smallNORB
    "M" geometry within CALIB_REL_TOL;
  * BENCH_*.json artifacts validate against the repro.bench/v1 schema
    and the validator actually fails on broken invariants.
"""
import json

import numpy as np
import pytest

from repro import obs
from repro.edge import (EdgeOp, EdgeProgram, EdgeVM, TensorSpec,
                        costmodel, lower)
from repro.serving import (EDGE_TINY, CapsServeEngine, ModelRegistry,
                           ModelSpec, ServeMetrics)

import test_edge


class FakeClock:
    """Monotone fake clock: every read advances 1s."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(autouse=True)
def _no_ambient_tracer():
    """Every test starts and ends with tracing off (module-global)."""
    obs.set_tracer(None)
    yield
    obs.set_tracer(None)


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------
def test_span_nesting_and_fake_clock():
    tr = obs.Tracer(clock=FakeClock())
    with tr.span("outer", model="m") as outer:
        with tr.span("inner.a"):
            pass
        with tr.span("inner.b"):
            pass
    assert [r.name for r in tr.roots] == ["outer"]
    assert [c.name for c in outer.children] == ["inner.a", "inner.b"]
    assert outer.children[0].children == []
    # fake clock reads: outer t0=1, a=[2,3], b=[4,5], outer t1=6
    assert (outer.t0, outer.t1) == (1.0, 6.0)
    assert outer.children[0].dur_s == 1.0
    assert outer.args == {"model": "m"}
    assert tr.span_count() == 3
    assert len(tr.find("inner.a")) == 1
    assert outer.find("inner.b")[0] is outer.children[1]


def test_span_forest_and_reset():
    tr = obs.Tracer(clock=FakeClock())
    with tr.span("a"):
        pass
    with tr.span("b"):
        pass
    assert [r.name for r in tr.roots] == ["a", "b"]
    tr.reset()
    assert tr.roots == [] and tr.span_count() == 0


def test_span_exception_unwind_keeps_stack_sane():
    tr = obs.Tracer(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            with tr.span("inner"):
                raise RuntimeError("boom")
    assert tr._stack == []                       # fully unwound
    inner = tr.find("inner")[0]
    assert inner.t1 is not None                  # still closed
    with tr.span("after"):
        pass
    assert [r.name for r in tr.roots] == ["outer", "after"]


def test_chrome_trace_export(tmp_path):
    tr = obs.Tracer(clock=FakeClock())
    with tr.span("serve.wave", bucket=4):
        with tr.span("serve.wait"):
            pass
    doc = tr.chrome_trace()
    ev = {e["name"]: e for e in doc["traceEvents"]}
    assert set(ev) == {"serve.wave", "serve.wait"}
    assert all(e["ph"] == "X" for e in ev.values())
    # fake clock: wave=[1,4], wait=[2,3]; epoch shift -> wave ts=0
    assert ev["serve.wave"]["ts"] == 0.0
    assert ev["serve.wave"]["dur"] == pytest.approx(3e6)
    assert ev["serve.wait"]["ts"] == pytest.approx(1e6)
    assert ev["serve.wave"]["cat"] == "serve"
    assert ev["serve.wave"]["args"] == {"bucket": 4}
    path = tr.write_chrome_trace(tmp_path / "t" / "trace.json")
    assert json.loads(path.read_text()) == json.loads(json.dumps(doc))


def test_ambient_span_is_null_when_off():
    assert obs.get_tracer() is None
    s = obs.span("anything", arg=1)
    assert s is obs.NULL_SPAN                    # shared, no allocation
    with s as inner:
        assert inner is obs.NULL_SPAN
    assert s.find("anything") == []


def test_tracing_scopes_and_restores():
    tr = obs.Tracer(clock=FakeClock())
    with obs.tracing(tr):
        assert obs.get_tracer() is tr
        with obs.span("root"):
            with obs.span("child"):
                pass
        inner = obs.Tracer()
        with obs.tracing(inner):
            assert obs.get_tracer() is inner
        assert obs.get_tracer() is tr
    assert obs.get_tracer() is None
    assert [r.name for r in tr.roots] == ["root"]
    assert tr.roots[0].children[0].name == "child"


def test_explicit_tracer_beats_ambient():
    amb, exp = obs.Tracer(clock=FakeClock()), obs.Tracer(clock=FakeClock())
    with obs.tracing(amb):
        with obs.span("explicit", tracer=exp):
            pass
    assert amb.span_count() == 0 and exp.span_count() == 1


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_counter_labels_and_total():
    reg = obs.MetricsRegistry("t")
    c = reg.counter("hits", help="h")
    c.inc(op="a", variant="x")
    c.inc(2, op="a", variant="y")
    c.inc(op="a", variant="x")
    assert c.value(op="a", variant="x") == 2
    assert c.value(op="a", variant="y") == 2
    assert c.value(op="never") == 0
    assert c.total() == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    # get-or-create: same object back, kind mismatch is loud
    assert reg.counter("hits") is c
    with pytest.raises(ValueError):
        reg.gauge("hits")


def test_gauge_and_histogram():
    reg = obs.MetricsRegistry("t")
    g = reg.gauge("depth")
    g.set(3)
    g.set(7)
    assert g.value() == 7
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    assert h.buckets[-1] == float("inf")         # inf auto-appended
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(2.55)
    s = h.series()[()]
    assert s["bucket_counts"] == [1, 1, 1]
    assert (s["min"], s["max"]) == (0.05, 2.0)


def test_snapshot_is_json_safe():
    reg = obs.MetricsRegistry("t")
    reg.counter("c").inc(model="m@jnp")
    reg.gauge("g").set(1.5)
    h = reg.histogram("h")
    h.observe(0.2)
    snap = reg.snapshot()
    text = json.dumps(snap)                      # must not raise
    assert json.loads(text) == snap
    assert snap["c"]["kind"] == "counter"
    assert snap["c"]["series"] == [
        {"labels": {"model": "m@jnp"}, "value": 1}]
    assert snap["h"]["buckets"][-1] == "inf"
    # untouched histogram min/max never leak inf into JSON
    reg2 = obs.MetricsRegistry()
    reg2.histogram("h2").observe(float("inf"))
    json.dumps(reg2.snapshot())
    reg.reset()
    assert reg.snapshot()["c"]["series"] == []


def test_series_view_is_counter_shaped():
    reg = obs.MetricsRegistry("t")
    c = reg.counter("f")
    view = c.view("op", "variant")
    assert not view                              # falsy when empty
    c.inc(op="squash", variant="approx")
    assert view                                  # live view
    assert view[("squash", "approx")] == 1
    assert ("squash", "approx") in view
    assert ("routing.squash", "approx") not in view
    assert dict(view) == {("squash", "approx"): 1}
    single = c.view("op")
    assert single["squash"] == 1


def test_pallas_backend_fallbacks_are_registry_backed():
    from repro.nn.backend import BACKENDS, PallasBackend
    from repro.obs import METRICS
    be = PallasBackend()                         # private registry
    assert not be.fallbacks
    with pytest.warns(RuntimeWarning):
        be._fallback("squash", "approx")
    assert be.fallbacks[("squash", "approx")] == 1
    assert be.metrics.counter("pallas.fallback_decisions").total() == 1
    # the BACKENDS singleton records into the process registry instead
    assert BACKENDS["pallas"].metrics is METRICS
    assert "pallas.fallback_decisions" in METRICS.names()


def test_model_registry_counts_are_views():
    reg = ModelRegistry(specs={"tiny": ModelSpec(
        "tiny", EDGE_TINY, dataset="uniform", calib_n=4)})
    assert (reg.quantize_count, reg.compile_count, reg.exec_hits) == (0, 0, 0)
    with pytest.raises(AttributeError):          # views are read-only now
        reg.quantize_count = 5
    reg.executable("tiny", 1)
    reg.executable("tiny", 1)
    assert (reg.quantize_count, reg.compile_count, reg.exec_hits) == (1, 1, 1)
    # labeled series carry the model id
    assert reg.metrics.counter("serving.quantize_builds") \
        .value(model="tiny") == 1
    snap = reg.metrics.snapshot()
    assert snap["serving.wave_compiles"]["series"][0]["labels"] == {
        "bucket": "1", "model": "tiny"}


# ---------------------------------------------------------------------------
# ServeMetrics empty-state handling
# ---------------------------------------------------------------------------
def test_servemetrics_empty_is_explicit_not_nan():
    m = ServeMetrics()
    # pinned low-level contract: nan on empty
    assert np.isnan(m.latency_percentile(50))
    assert np.isnan(m.occupancy())
    assert np.isnan(m.images_per_s())
    s = m.summary()
    assert s["empty"] is True
    assert s["images"] == 0
    assert s["p50_ms"] is None and s["occupancy"] is None
    assert s["images_per_s"] is None
    json.dumps(s)                                # NaN would break this
    r = m.report()
    assert "no completed requests" in r
    assert "nan" not in r.lower()


def test_servemetrics_partial_window_report():
    m = ServeMetrics()
    m.record_submit(1.0, 1)                      # submitted, never served
    s = m.summary()
    assert s["empty"] is True and s["max_queue_depth"] == 1
    assert "nan" not in m.report().lower()
    # ... and a full window keeps the old report shape
    m.record_wave(bucket=4, n_real=2, exec_s=0.5, t_done=2.0,
                  latencies_s=[0.5, 1.0])
    s = m.summary()
    assert s["empty"] is False
    assert s["occupancy"] == pytest.approx(0.5)
    assert "2 imgs in 1 waves" in m.report()
    assert "nan" not in m.report().lower()


def test_servemetrics_optional_registry_mirror():
    reg = obs.MetricsRegistry("t")
    m = ServeMetrics(registry=reg)
    m.record_submit(1.0, 3)
    m.record_wave(bucket=4, n_real=2, exec_s=0.5, t_done=2.0,
                  latencies_s=[0.5, 1.0])
    assert reg.counter("serve.requests_total").value(bucket="4") == 2
    assert reg.histogram("serve.latency_seconds").count() == 2
    assert reg.gauge("serve.queue_depth").value() == 3
    assert reg.gauge("serve.wave_occupancy").value() == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# traced serving: bit parity + span forest
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def edge_tiny_registry():
    return ModelRegistry(specs={"tiny": ModelSpec(
        "tiny", EDGE_TINY, dataset="uniform", calib_n=8)})


def _serve(registry, images, tracer=None):
    engine = CapsServeEngine(registry, buckets=(1, 4), tracer=tracer)
    engine.submit_many(images, "tiny")
    return engine.drain()


def test_traced_serving_bit_identical_and_nested(edge_tiny_registry,
                                                 tmp_path):
    rng = np.random.default_rng(7)
    images = rng.uniform(0, 1, (6,) + tuple(EDGE_TINY.input_shape)) \
        .astype(np.float32)
    base = _serve(edge_tiny_registry, images)
    tracer = obs.Tracer()
    traced = _serve(edge_tiny_registry, images, tracer=tracer)
    assert len(base) == len(traced) == 6
    for b, t in zip(base, traced):
        assert np.array_equal(b.v_q, t.v_q)      # bit-identical
        assert (b.pred, b.wave, b.bucket) == (t.pred, t.wave, t.bucket)

    # span forest: enqueue roots + one wave root per wave, with the
    # wave's phases nested inside
    assert len(tracer.find("serve.enqueue")) == 6
    waves = [r for r in tracer.roots if r.name == "serve.wave"]
    assert len(waves) == len({c.wave for c in traced}) == 2
    for w in waves:
        kids = [c.name for c in w.children]
        assert kids == ["serve.bucket", "serve.transfer", "serve.dispatch",
                        "serve.wait", "serve.readback", "serve.complete"]
        assert w.t0 <= w.children[0].t0 and w.children[-1].t1 <= w.t1
    # valid Chrome JSON with the nesting visible as containment
    path = tracer.write_chrome_trace(tmp_path / "trace.json")
    doc = json.loads(path.read_text())
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("serve.wave") == 2
    assert names.count("serve.wait") == 2
    for e in doc["traceEvents"]:
        assert e["ph"] == "X" and e["dur"] >= 0


def test_ambient_tracer_reaches_engine_and_ptq(edge_tiny_registry):
    # a FRESH registry so the lazy PTQ build happens inside the traced
    # window (the module fixture's model is already built)
    registry = ModelRegistry(specs={"tiny": ModelSpec(
        "tiny", EDGE_TINY, dataset="uniform", calib_n=8)})
    rng = np.random.default_rng(8)
    images = rng.uniform(0, 1, (2,) + tuple(EDGE_TINY.input_shape)) \
        .astype(np.float32)
    tracer = obs.Tracer()
    with obs.tracing(tracer):
        done = _serve(registry, images)
    assert len(done) == 2
    assert tracer.find("serving.ptq_build")      # registry spans
    assert tracer.find("ptq.calibrate")          # pipeline spans
    assert tracer.find("serving.compile_wave")
    wave = tracer.find("serve.wave")[0]
    assert wave.find("serve.wait")               # nested under the wave
    # a cache miss compiles inside the wave that needed it
    assert wave.find("serving.compile_wave")


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
def test_engine_clocks_wave_phases(edge_tiny_registry, traced):
    """The engine reads its own clock at each phase boundary of every
    wave, so ServeMetrics keeps the phases with or without a tracer.
    Read k returns 1 + 2 + ... + k, so each phase has its own length."""
    reads = iter(np.cumsum(np.arange(1, 100)).tolist())
    tracer = obs.Tracer(clock=FakeClock()) if traced else None
    engine = CapsServeEngine(edge_tiny_registry, buckets=(4,),
                             clock=lambda: float(next(reads)),
                             tracer=tracer)
    engine.warmup("tiny")
    rng = np.random.default_rng(9)
    engine.submit_many(rng.uniform(0, 1, (2,) + tuple(
        EDGE_TINY.input_shape)).astype(np.float32), "tiny")   # reads 1, 3
    done = engine.step()
    # wave start 6, transfer 10 -> 15, dispatch -> 21, wait -> 28,
    # readback -> 36 (done), bookkeeping -> 45
    (w,) = engine.metrics.waves
    assert w == {"bucket": 4, "n_real": 2, "exec_s": 26.0,
                 "transfer_s": 5.0, "dispatch_s": 6.0, "wait_s": 7.0,
                 "readback_s": 8.0, "host_s": 45.0 - 6.0 - 7.0}
    assert [c.latency_s for c in done] == [35.0, 33.0]
    assert engine.metrics.t_last_done == 36.0


def test_profiler_host_plane_holds_the_tracer_spans(edge_tiny_registry,
                                                    tmp_path):
    """Under the JAX profiler every serve.* span of an installed Tracer
    is also a host-plane event of the same name, nesting and length."""
    import glob

    import jax
    from jax.profiler import ProfileData

    engine = CapsServeEngine(edge_tiny_registry, buckets=(4,))
    engine.warmup("tiny")
    rng = np.random.default_rng(10)
    images = rng.uniform(0, 1, (8,) + tuple(EDGE_TINY.input_shape)) \
        .astype(np.float32)
    tracer = obs.Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with obs.tracing(tracer):
            engine.submit_many(images, "tiny")
            assert len(engine.drain()) == 8
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events: dict = {}               # name -> [(start_ns, end_ns)] in order
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    for ivs in events.values():
        ivs.sort()

    waves = [r for r in tracer.roots if r.name == "serve.wave"]
    assert len(waves) == 2
    seen: dict = {}

    def match(span, parent_iv):
        k = seen.get(span.name, 0)
        seen[span.name] = k + 1
        start, end = events[span.name][k]
        if parent_iv is not None:
            assert parent_iv[0] <= start and end <= parent_iv[1], span.name
        dur_s = (end - start) / 1e9
        assert abs(dur_s - span.dur_s) <= max(2e-4, 0.2 * span.dur_s), \
            (span.name, dur_s, span.dur_s)
        for c in span.children:
            match(c, (start, end))

    for root in tracer.roots:
        match(root, None)
    assert seen == {name: len(ivs) for name, ivs in events.items()}
    assert set(seen) == {"serve.enqueue", "serve.wave", "serve.bucket",
                         "serve.transfer", "serve.dispatch", "serve.wait",
                         "serve.readback", "serve.complete"}


# ---------------------------------------------------------------------------
# EdgeVM profiler: bit parity + rows
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rounding", ["floor", "nearest"])
@pytest.mark.parametrize("name", sorted(test_edge.CONFIGS))
def test_edgevm_profile_bit_parity(name, rounding):
    qnet, x_q = test_edge.built(name, rounding)
    vm = EdgeVM(lower(qnet))
    base = vm.run(x_q)
    prof: list = []
    profiled = vm.run(x_q, profile=prof)
    assert np.array_equal(base, profiled)
    assert [r["name"] for r in prof] == [op.name for op in vm.program.ops]
    assert all(r["wall_s"] >= 0 for r in prof)
    assert {"name", "kind", "wall_s"} <= set(prof[0])
    # ambient tracing alone must not perturb the bits either
    tracer = obs.Tracer()
    with obs.tracing(tracer):
        traced = vm.run(x_q)
    assert np.array_equal(base, traced)
    run = tracer.find("edgevm.run")[0]
    assert len(run.children) == len(vm.program.ops)


# ---------------------------------------------------------------------------
# MCU cost model: calibration against the paper's latency tables
# ---------------------------------------------------------------------------
def _m_geometry_program() -> EdgeProgram:
    """The paper's smallNORB "M" layer shapes (Table 1): pcap
    26x26x32 -k7 s2-> 10x10x64 (16 caps x D4 per position -> I=1600),
    routing J=5, I=1600, O=6, D=4, r=3 — weights zeroed (the cost model
    reads geometry only)."""
    tensors = (
        TensorSpec(0, "input", (26, 26, 32), 7),
        TensorSpec(1, "pcap.out", (1600, 4), 7),
        TensorSpec(2, "caps.out", (5, 6), 7),
    )
    pcap = EdgeOp(
        kind="PRIMARY_CAPS_Q7", name="pcap", inputs=(0,), output=1,
        attrs={"kernel": 7, "stride": 2, "in_ch": 32, "out_ch": 64,
               "dim": 4, "relu": False, "bias_shift": 0, "out_shift": 0,
               "squash_in_frac": 7, "squash_out_frac": 7},
        weights={"w": np.zeros((7, 7, 32, 64), np.int8),
                 "b": np.zeros((64,), np.int32)})
    caps = EdgeOp(
        kind="CAPS_ROUTING_Q7", name="caps", inputs=(1,), output=2,
        attrs={"num_in": 1600, "num_out": 5, "in_dim": 4, "out_dim": 6,
               "routings": 3, "uhat_shift": 0, "logit_frac": 7,
               "caps_out_shifts": (0, 0, 0), "caps_out_fracs": (7, 7, 7),
               "agree_shifts": (0, 0), "squash_out_frac": 7},
        weights={"W": np.zeros((5, 1600, 6, 4), np.int8)})
    return EdgeProgram(name="smallnorb_M", rounding="floor", input_frac=7,
                       tensors=tensors, ops=(pcap, caps))


def test_m_geometry_workload_counts():
    program = _m_geometry_program()
    pcap, caps = program.ops
    assert costmodel.op_counts(program, pcap)["macs"] == 10_035_200
    c = costmodel.op_counts(program, caps)
    assert c["macs"] + c["elems"] == 456_090


@pytest.mark.parametrize("profile", sorted(costmodel.MCU_PROFILES))
def test_costmodel_reproduces_paper_latencies(profile):
    est = costmodel.estimate_program(_m_geometry_program(), profile)
    want = costmodel.PAPER_LATENCY_MS[profile]
    by_name = {r["name"]: r["ms"] for r in est["rows"]}
    assert by_name["pcap"] == pytest.approx(
        want["primary_caps"], rel=costmodel.CALIB_REL_TOL)
    assert by_name["caps"] == pytest.approx(
        want["caps_routing"], rel=costmodel.CALIB_REL_TOL)
    assert est["total_ms"] == pytest.approx(
        want["primary_caps"] + want["caps_routing"],
        rel=costmodel.CALIB_REL_TOL)


def test_costmodel_surfaces():
    qnet, _ = test_edge.built("capsnet_edge_tiny")
    program = lower(qnet)
    ests = costmodel.estimate_all(program)
    assert set(ests) == set(costmodel.MCU_PROFILES)
    for est in ests.values():
        assert est["total_cycles"] == pytest.approx(
            sum(r["cycles"] for r in est["rows"]))
    assert costmodel.total_latency_ms(program, "cortex-m7") \
        == ests["cortex-m7"]["total_ms"]
    with pytest.raises(ValueError):
        costmodel.get_profile("z80")
    text = costmodel.format_estimates(program)
    assert "cortex-m7" in text and "gap8" in text
    # the memory report integration (arena.py)
    from repro.edge import memory_report
    report = memory_report(program, profile="gap8")
    assert report["profile"] == "gap8"
    assert report["est_total_ms"] == pytest.approx(
        ests["gap8"]["total_ms"])
    assert all("est_ms" in r for r in report["rows"])
    from repro.edge import format_report
    assert "est. latency on gap8" in format_report(report)
    # without a profile: no estimate keys (pre-obs shape)
    assert "profile" not in memory_report(program)


def test_table2_rows_carry_latency_axis():
    from repro.captrain.evalq import Table2Row, format_rows
    row = Table2Row(name="n", rounding="floor", acc_f32=0.9, acc_ptq=0.88,
                    acc_qat=0.89, saving_pct=74.0, est_ms_m7=119.94,
                    est_ms_gap8=7.02)
    out = format_rows([row])
    assert "m7_ms" in out and "gap8_ms" in out
    assert "119.94" in out and "7.02" in out


# ---------------------------------------------------------------------------
# bench artifacts: schema + validator gates
# ---------------------------------------------------------------------------
def _bench_doc(**over):
    doc = {"schema": "repro.bench/v1", "section": "serving",
           "stamp": "s", "smoke": True, "config": {}, "figures": {},
           "rows": [{"name": "serve_batched_x", "us_per_call": 1.0,
                     "derived": "d", "figures": {"occupancy": 0.9}}]}
    doc.update(over)
    return doc


def test_bench_recorder_writes_schema(tmp_path):
    from benchmarks import util, validate
    rec = util.BenchRecorder(tmp_path, stamp="abc")
    rec.begin_section("serving", models=["tiny"])
    rec.add_row("serve_batched_tiny", 12.5, "fast", {"occupancy": 1.0})
    rec.add_figures(total=1)
    rec.end_section()
    path = tmp_path / "BENCH_serving.json"
    assert rec.written == [path]
    doc = json.loads(path.read_text())
    assert validate.validate_doc(doc, "t") == []
    assert validate.validate_invariants(doc, "t") == []
    assert doc["stamp"] == "abc"
    assert doc["config"] == {"models": ["tiny"]}
    assert doc["figures"] == {"total": 1}
    assert doc["rows"][0]["figures"]["occupancy"] == 1.0
    paths, findings = validate.validate_dir(tmp_path)
    assert paths == [path] and findings == []


def test_bench_validator_catches_schema_breaks():
    from benchmarks import validate
    assert validate.validate_doc(_bench_doc(schema="nope/v9"), "t")
    bad = _bench_doc()
    del bad["stamp"]
    assert any("stamp" in f for f in validate.validate_doc(bad, "t"))
    bad = _bench_doc(rows=[{"name": "x"}])
    assert validate.validate_doc(bad, "t")


def test_bench_validator_gates_invariants(tmp_path):
    from benchmarks import validate
    # occupancy must be > 0 on batched serving rows
    bad = _bench_doc()
    bad["rows"][0]["figures"]["occupancy"] = 0.0
    assert any("occupancy" in f
               for f in validate.validate_invariants(bad, "t"))
    # default-variant fallbacks must be zero
    ob = _bench_doc(section="observability", rows=[],
                    figures={"default_variant_fallbacks": 3})
    assert any("default_variant_fallbacks" in f
               for f in validate.validate_invariants(ob, "t"))
    # empty dir and unreadable file are findings, and main() exits 1
    (tmp_path / "BENCH_broken.json").write_text("{not json")
    _, findings = validate.validate_dir(tmp_path)
    assert findings
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert validate.main([str(tmp_path)]) == 1
    assert "FINDING" in buf.getvalue()


# ---------------------------------------------------------------------------
# CLI --profile smoke
# ---------------------------------------------------------------------------
def test_export_caps_profile_cli(tmp_path, capsys):
    from repro.launch import export_caps
    rc = export_caps.main(["--model", "edge_tiny", "--out",
                           str(tmp_path), "--verify-n", "2", "--profile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "estimated cost on" in out
    assert "cortex-m7" in out and "gap8" in out
    assert "cycles" in out


def test_analysis_cli_profile(tmp_path, capsys):
    qnet, _ = test_edge.built("capsnet_edge_tiny")
    program = lower(qnet)
    paths = program.save(tmp_path / "p")
    from repro.analysis.__main__ import main as analysis_main
    rc = analysis_main([str(paths["capsbin"]), "--profile"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "estimated cost on" in out and "gap8" in out


# ---------------------------------------------------------------------------
# trainer spans
# ---------------------------------------------------------------------------
def test_trainer_emits_spans(tmp_path):
    from repro.captrain import CapsTrainer, TrainConfig
    tcfg = TrainConfig(dataset="edge_tiny", batch=8, microbatches=2,
                       recon_weight=0.0, recalib_every=2, calib_n=8,
                       ckpt_every=2, ckpt_dir=str(tmp_path))
    trainer = CapsTrainer(EDGE_TINY, tcfg)
    state = trainer.init_state()
    tracer = obs.Tracer()
    with obs.tracing(tracer):
        state, plan, hist = trainer.fit(state, 2, qat=True)
    assert len(hist) == 2
    assert len(tracer.find("train.step")) == 2
    assert tracer.find("train.recalibrate")      # entry derivation
    assert tracer.find("train.ckpt")             # step 2 checkpoint
    # the final PTQ entry point carries the ptq.* spans
    with obs.tracing(tracer):
        trainer.quantize(state)
    assert tracer.find("ptq.calibrate")
    assert tracer.find("ptq.plan")
    assert tracer.find("ptq.quantize_weights")
