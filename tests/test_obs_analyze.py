"""Tests for the analyze -> regress half of the observability loop
(repro.obs.analyze + repro.obs.baseline + the bench schema gates).

Pins, in order:
  * the analyzer under a fake clock: per-span stats, self vs child
    time, wave critical paths, the per-phase wave breakdown and
    the reconstructed per-request timelines — all EXACT, and bit-equal
    whether the source is the live Tracer or its own Chrome export;
  * the repo-wide tiny-sample percentile policy on obs.Histogram:
    n < 3 returns the exact max (never interpolates), empty returns
    None, snapshots carry p50/p95/p99;
  * req_id propagation through a REAL serving run: one request's
    enqueue -> wave -> complete timeline reconstructed from the trace
    alone matches what the engine reported for that request;
  * cost-model drift: 100% join coverage of the schedule for every
    config x rounding, both MCU profiles, shares summing to 1;
  * the perf-baseline gate: the committed benchmarks/baselines/ snapshot
    self-compares clean, a doctored 3x slowdown fails with the metric
    named, direction-awareness (improvements never fail), --slack
    widening timing tolerances only;
  * the bench validator's stamp / known-section rules;
  * CLI smokes: obs.analyze, obs.baseline, serve_caps --trace-summary /
    --metrics-out, export_caps --drift.
"""
import json
import pathlib

import numpy as np
import pytest

from repro import obs
from repro.edge import EdgeVM, lower
from repro.obs import analyze, baseline
from repro.serving import EDGE_TINY, CapsServeEngine, ModelRegistry, ModelSpec

import test_edge
from test_obs import FakeClock

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _no_ambient_tracer():
    obs.set_tracer(None)
    yield
    obs.set_tracer(None)


def _fake_serve_trace() -> obs.Tracer:
    """A hand-built serve-shaped forest under the fake clock (every
    read advances 1s), so every analyzer number is exact:

      enqueue#0 [1,2]  enqueue#1 [3,4]
      wave [5,22]: bucket [6,7]  serving.compile_wave [8,9]
                   transfer [10,11]  dispatch [12,13]
                   wait [14,17] > edgevm.run [15,16]
                   readback [18,19]  complete [20,21]
    """
    tr = obs.Tracer(clock=FakeClock())
    with tr.span("serve.enqueue", model="m", req_id=0):
        pass
    with tr.span("serve.enqueue", model="m", req_id=1):
        pass
    with tr.span("serve.wave", wave=0, model="m") as w:
        with tr.span("serve.bucket"):
            pass
        with tr.span("serving.compile_wave", model="m", bucket=4):
            pass
        with tr.span("serve.transfer"):
            pass
        with tr.span("serve.dispatch"):
            pass
        with tr.span("serve.wait"):
            with tr.span("edgevm.run"):
                pass
        with tr.span("serve.readback"):
            pass
        with tr.span("serve.complete"):
            pass
        w.note(bucket=4, n_real=2, req_ids="0,1")
    return tr


# ---------------------------------------------------------------------------
# analyzer: exact numbers under the fake clock
# ---------------------------------------------------------------------------
def test_span_stats_exact_under_fake_clock():
    report = analyze.analyze(_fake_serve_trace())
    assert report["span_count"] == 11
    s = report["spans"]
    # epoch-normalized: the first enqueue starts at 0.0
    assert s["serve.enqueue"] == {
        "count": 2, "total_s": 2.0, "mean_s": 1.0, "p50_s": 1.0,
        "p95_s": 1.0, "max_s": 1.0, "self_s": 2.0}
    # wave [4,21]: dur 17, children 1+1+1+1+3+1+1 -> self 8
    assert s["serve.wave"]["total_s"] == 17.0
    assert s["serve.wave"]["self_s"] == 8.0
    # wait [13,16] contains edgevm.run [14,15] -> self 2
    assert s["serve.wait"]["total_s"] == 3.0
    assert s["serve.wait"]["self_s"] == 2.0
    assert s["edgevm.run"]["self_s"] == 1.0


def test_wave_critical_path_and_summary():
    report = analyze.analyze(_fake_serve_trace())
    (w,) = report["waves"]
    assert (w["wave"], w["model"], w["bucket"], w["n_real"]) \
        == (0, "m", 4, 2)
    assert w["req_ids"] == [0, 1]
    assert w["dur_s"] == 17.0
    # wait (3s) dominates the other phases (1s each)
    assert [p["name"] for p in w["critical_path"]] \
        == ["serve.wave", "serve.wait", "edgevm.run"]
    assert [p["dur_s"] for p in w["critical_path"]] == [17.0, 3.0, 1.0]


def test_request_timelines_exact():
    report = analyze.analyze(_fake_serve_trace())
    r0, r1 = report["requests"]
    # rid 0: enqueued [0,1], wave opens at 4, last complete exits at 20
    assert (r0["req_id"], r0["wave"], r0["bucket"]) == (0, 0, 4)
    assert (r0["t_enq"], r0["t_done"]) == (0.0, 20.0)
    assert (r0["e2e_s"], r0["queue_s"]) == (20.0, 3.0)
    # rid 1: enqueued [2,3] -> shorter queue, same completion
    assert (r1["t_enq"], r1["e2e_s"], r1["queue_s"]) == (2.0, 18.0, 1.0)


def test_wave_breakdown_exact():
    report = analyze.analyze(_fake_serve_trace())
    (b,) = report["breakdown"]
    assert (b["model"], b["bucket"], b["waves"], b["images"]) \
        == ("m", 4, 1, 2)
    assert b["wave_s"] == 17.0
    assert (b["bucket_s"], b["compile_s"], b["transfer_s"],
            b["dispatch_s"], b["wait_s"], b["readback_s"],
            b["complete_s"]) == (1.0, 1.0, 1.0, 1.0, 3.0, 1.0, 1.0)
    assert b["queue_s"] == 4.0                   # 3.0 + 1.0


def test_chrome_round_trip_is_bit_identical(tmp_path):
    tr = _fake_serve_trace()
    from_tracer = analyze.analyze(tr)
    from_dict = analyze.analyze(tr.chrome_trace())
    assert from_tracer == from_dict              # same report, bit for bit
    path = tr.write_chrome_trace(tmp_path / "trace.json")
    assert analyze.analyze(path) == from_tracer
    assert analyze.analyze(str(path)) == from_tracer
    # and the whole report is JSON-safe
    json.loads(json.dumps(from_tracer))


def test_load_trace_rejects_garbage():
    with pytest.raises(TypeError):
        analyze.load_trace(42)


def test_format_analysis_renders_every_block():
    report = analyze.analyze(_fake_serve_trace())
    text = analyze.format_analysis(report)
    assert "11 spans" in text
    assert "serve.wave > serve.wait > edgevm.run" in text
    assert "breakdown per (model, bucket)" in text
    assert "requests: 2 reconstructed" in text


# ---------------------------------------------------------------------------
# tiny-sample percentile policy (obs.Histogram + the analyzer's _pctl)
# ---------------------------------------------------------------------------
def test_histogram_percentile_tiny_samples():
    reg = obs.MetricsRegistry("t")
    h = reg.histogram("lat", buckets=(1.0, 10.0))
    assert h.percentile(50) is None              # empty: no number at all
    h.observe(3.0)
    # 1 and 2 observations: the exact max, never an interpolation
    assert h.percentile(50) == 3.0
    assert h.percentile(99) == 3.0
    h.observe(0.5)
    assert h.percentile(50) == 3.0
    assert h.percentile(95) == 3.0
    s = h.summary()
    assert (s["count"], s["p50"], s["p95"]) == (2, 3.0, 3.0)


def test_histogram_percentile_nearest_rank_and_snapshot():
    reg = obs.MetricsRegistry("t")
    h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 5.0):
        h.observe(v)
    # nearest-rank over cumulative buckets: p50 -> rank 2 -> bucket <=2.0
    assert h.percentile(50) == 2.0
    # p95 -> rank 4 -> last bucket, clamped to the observed max
    assert h.percentile(95) == 5.0
    snap = reg.snapshot()
    (series,) = snap["lat"]["series"]
    assert {"p50", "p95", "p99"} <= set(series["value"])
    assert series["value"]["p95"] == 5.0
    json.dumps(snap)                             # inf never leaks


def test_analyzer_pctl_matches_policy():
    assert analyze._pctl([], 50) is None
    assert analyze._pctl([7.0], 95) == 7.0
    assert analyze._pctl([1.0, 9.0], 50) == 9.0  # n<3 -> exact max
    vals = sorted(float(i) for i in range(1, 11))
    assert analyze._pctl(vals, 50) == 5.0        # nearest rank, 1-based
    assert analyze._pctl(vals, 95) == 10.0
    assert analyze._pctl(vals, 99) == 10.0


# ---------------------------------------------------------------------------
# req_id propagation through a real serving run
# ---------------------------------------------------------------------------
def test_real_serve_trace_reconstructs_requests():
    registry = ModelRegistry(specs={"tiny": ModelSpec(
        "tiny", EDGE_TINY, dataset="uniform", calib_n=8)})
    rng = np.random.default_rng(3)
    images = rng.uniform(0, 1, (6,) + tuple(EDGE_TINY.input_shape)) \
        .astype(np.float32)
    tracer = obs.Tracer()
    engine = CapsServeEngine(registry, buckets=(1, 4), tracer=tracer)
    rids = [engine.submit(img, "tiny") for img in images]
    done = {c.rid: c for c in engine.drain()}

    report = analyze.analyze(tracer)
    rows = {r["req_id"]: r for r in report["requests"]}
    assert set(rows) == set(rids) == set(done)
    # pin one full reconstructed timeline against the engine's own view
    r0, c0 = rows[rids[0]], done[rids[0]]
    assert (r0["wave"], r0["bucket"]) == (c0.wave, c0.bucket)
    assert r0["queue_s"] >= 0.0
    assert r0["e2e_s"] >= r0["queue_s"]
    assert r0["t_enq"] <= r0["t_done"]
    # every wave span carries its membership, covering all requests once
    member = [rid for w in report["waves"] for rid in w["req_ids"]]
    assert sorted(member) == sorted(rids)
    for w in report["waves"]:
        assert w["critical_path"][0]["name"] == "serve.wave"
        assert w["n_real"] == len(w["req_ids"])


# ---------------------------------------------------------------------------
# cost-model drift: 100% join coverage for every config x rounding
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rounding", ["floor", "nearest"])
@pytest.mark.parametrize("name", sorted(test_edge.CONFIGS))
def test_costmodel_drift_full_coverage(name, rounding):
    qnet, x_q = test_edge.built(name, rounding)
    program = lower(qnet)
    rows: list = []
    EdgeVM(program).run(x_q, profile=rows)
    batch = x_q.shape[0] if x_q.ndim == 4 else 1
    drift = analyze.costmodel_drift(program, rows, batch=batch)
    assert drift["coverage"] == 1.0
    assert drift["n_joined"] == drift["n_ops"] == len(program.ops)
    assert drift["unmatched"] == []
    assert set(drift["profiles"]) == {"cortex-m7", "gap8"}
    for p in drift["profiles"].values():
        assert len(p["rows"]) == len(program.ops)
        assert sum(r["est_share"] for r in p["rows"]) \
            == pytest.approx(1.0)
        assert sum(r["meas_share"] for r in p["rows"]) \
            == pytest.approx(1.0)
        assert p["total_est_ms"] > 0
    text = analyze.format_drift(drift)
    assert "100%" in text and "cortex-m7" in text


def test_costmodel_drift_reports_unjoined_ops():
    qnet, x_q = test_edge.built("capsnet_edge_tiny")
    program = lower(qnet)
    rows: list = []
    EdgeVM(program).run(x_q, profile=rows)
    drift = analyze.costmodel_drift(program, rows[:-1])
    assert drift["coverage"] < 1.0
    assert drift["unmatched"][0]["name"] == program.ops[-1].name
    assert "UNMATCHED" in analyze.format_drift(drift)


# ---------------------------------------------------------------------------
# perf-baseline gate
# ---------------------------------------------------------------------------
def test_committed_baselines_self_compare_clean():
    base_dir = REPO / "benchmarks" / "baselines"
    assert sorted(p.name for p in base_dir.glob("BENCH_*.json")) == [
        "BENCH_edge_vm.json", "BENCH_numerics.json",
        "BENCH_observability.json", "BENCH_search.json",
        "BENCH_serving.json", "BENCH_variants.json"]
    findings, notes = baseline.compare_dirs(base_dir, base_dir)
    assert findings == [] and notes == []


def test_injected_3x_slowdown_fails_with_named_metric(tmp_path):
    base_dir = REPO / "benchmarks" / "baselines"
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    for p in base_dir.glob("BENCH_*.json"):
        (run_dir / p.name).write_text(p.read_text())
    doc = json.loads((run_dir / "BENCH_serving.json").read_text())
    for row in doc["rows"]:
        row["us_per_call"] *= 3.0                # 3x slower everywhere
        figs = row["figures"]
        for k in ("images_per_s", "speedup"):
            if k in figs:
                figs[k] /= 3.0
        if "p95_ms" in figs:
            figs["p95_ms"] *= 3.0
    (run_dir / "BENCH_serving.json").write_text(json.dumps(doc))
    findings, _ = baseline.compare_dirs(run_dir, base_dir)
    assert findings
    assert any("us_per_call" in f for f in findings)
    assert any("images_per_s" in f for f in findings)
    assert all(f.startswith("BENCH_serving") for f in findings)
    # the CLI turns the findings into exit 1 and REGRESSION lines
    rc = baseline.main(["compare", str(run_dir),
                        "--baselines", str(base_dir)])
    assert rc == 1


def test_gate_is_direction_aware():
    base = {"schema": baseline.BENCH_SCHEMA, "section": "serving",
            "stamp": "s", "smoke": True, "config": {}, "figures": {},
            "rows": [{"name": "r", "us_per_call": 100.0, "derived": "",
                      "figures": {"images_per_s": 1000.0, "p95_ms": 2.0,
                                  "occupancy": 0.5}}]}
    better = json.loads(json.dumps(base))
    better["rows"][0]["us_per_call"] = 10.0      # 10x faster
    better["rows"][0]["figures"]["images_per_s"] = 9000.0
    better["rows"][0]["figures"]["p95_ms"] = 0.5
    assert baseline.compare_docs(base, better) == []
    # ... but an exact metric moving AT ALL is a finding, even "up"
    better["rows"][0]["figures"]["occupancy"] = 0.9
    (f,) = baseline.compare_docs(base, better)
    assert "occupancy" in f and "deterministic" in f
    # slack widens timing tolerances only
    slow = json.loads(json.dumps(base))
    slow["rows"][0]["us_per_call"] = 300.0       # 3x: fails at slack 1
    assert any("us_per_call" in f
               for f in baseline.compare_docs(base, slow))
    assert baseline.compare_docs(base, slow, slack=2.0) == []
    slow["rows"][0]["figures"]["occupancy"] = 0.9
    assert any("occupancy" in f                  # exact ignores slack
               for f in baseline.compare_docs(base, slow, slack=100.0))


def test_gate_catches_disappearing_rows_and_sections(tmp_path):
    base_dir, run_dir = tmp_path / "base", tmp_path / "run"
    base_dir.mkdir()
    run_dir.mkdir()
    doc = {"schema": baseline.BENCH_SCHEMA, "section": "serving",
           "stamp": "s", "smoke": True, "config": {}, "figures": {},
           "rows": [{"name": "r", "us_per_call": 1.0, "derived": "",
                     "figures": {}}]}
    (base_dir / "BENCH_serving.json").write_text(json.dumps(doc))
    gone = json.loads(json.dumps(doc))
    gone["rows"] = []
    (run_dir / "BENCH_serving.json").write_text(json.dumps(gone))
    extra = dict(doc, section="edge_vm")
    (run_dir / "BENCH_edge_vm.json").write_text(json.dumps(extra))
    findings, notes = baseline.compare_dirs(run_dir, base_dir)
    assert any("disappeared" in f for f in findings)
    # unbaselined sections are notes, not failures
    assert any("edge_vm" in n for n in notes)
    # a baselined section missing entirely IS a failure
    (run_dir / "BENCH_serving.json").unlink()
    findings, _ = baseline.compare_dirs(run_dir, base_dir)
    assert any("missing from the run" in f for f in findings)


def test_record_refuses_malformed_docs(tmp_path):
    out_dir, base_dir = tmp_path / "out", tmp_path / "base"
    out_dir.mkdir()
    bad = {"schema": baseline.BENCH_SCHEMA, "section": "serving",
           "stamp": "", "smoke": True, "config": {}, "figures": {},
           "rows": []}
    (out_dir / "BENCH_serving.json").write_text(json.dumps(bad))
    with pytest.raises(ValueError, match="stamp"):
        baseline.record(out_dir, base_dir)
    with pytest.raises(ValueError, match="nothing to record"):
        baseline.record(out_dir, base_dir, sections={"edge_vm"})
    good = dict(bad, stamp="s")
    (out_dir / "BENCH_serving.json").write_text(json.dumps(good))
    written = baseline.record(out_dir, base_dir)
    assert [p.name for p in written] == ["BENCH_serving.json"]
    findings, _ = baseline.compare_dirs(out_dir, base_dir)
    assert findings == []


# ---------------------------------------------------------------------------
# bench validator: stamp + known-section rules
# ---------------------------------------------------------------------------
def test_validator_refuses_unknown_section_and_empty_stamp():
    from benchmarks import util, validate
    assert util.SCHEMA == validate.SCHEMA        # single source of truth
    doc = {"schema": validate.SCHEMA, "section": "serving", "stamp": "x",
           "smoke": True, "config": {}, "figures": {}, "rows": []}
    assert validate.validate_doc(doc, "t") == []
    assert any("unknown section" in f for f in validate.validate_doc(
        dict(doc, section="made_up"), "t"))
    assert any("stamp" in f for f in validate.validate_doc(
        dict(doc, stamp="  "), "t"))
    assert "observability" in validate.KNOWN_SECTIONS


# ---------------------------------------------------------------------------
# CLI smokes
# ---------------------------------------------------------------------------
def test_analyze_cli(tmp_path, capsys):
    tr = _fake_serve_trace()
    path = tr.write_chrome_trace(tmp_path / "trace.json")
    metrics = tmp_path / "metrics.json"
    reg = obs.MetricsRegistry("r")
    reg.counter("serve.requests_total").inc(2)
    metrics.write_text(json.dumps(
        {"schema": "repro.metrics/v1", "process": {},
         "run": reg.snapshot(), "serve_summary": None}))
    assert analyze.main([str(path), "--metrics", str(metrics)]) == 0
    out = capsys.readouterr().out
    assert "serve.wave > serve.wait" in out
    assert "serve.requests_total (counter): 2" in out
    assert analyze.main([str(path), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["span_count"] == 11


def test_baseline_cli_compare_ok(capsys):
    rc = baseline.main(["compare", str(REPO / "benchmarks" / "baselines"),
                        "--baselines",
                        str(REPO / "benchmarks" / "baselines")])
    out = capsys.readouterr().out
    assert rc == 0
    assert "0 findings ok" in out


def test_serve_caps_trace_summary_and_metrics_out(tmp_path, capsys):
    from repro.launch import serve_caps
    metrics_path = tmp_path / "m.json"
    rc = serve_caps.main(["--model", "edge_tiny@jnp", "--requests", "4",
                          "--buckets", "1,4", "--trace-summary",
                          "--metrics-out", str(metrics_path)])
    out = capsys.readouterr().out
    assert rc is None or rc == 0
    assert "trace summary:" in out
    assert "waves (critical path):" in out
    assert "requests: 4 reconstructed" in out
    doc = json.loads(metrics_path.read_text())
    assert doc["schema"] == "repro.metrics/v1"
    assert doc["serve_summary"]["images"] == 4
    assert "serve.requests_total" in doc["run"]
    # the analyzer accepts the dump as its --metrics input
    text = analyze._format_metrics(doc)
    assert "serve.requests_total" in text


def test_export_caps_drift_cli(tmp_path, capsys):
    from repro.launch import export_caps
    rc = export_caps.main(["--model", "edge_tiny", "--out",
                           str(tmp_path), "--verify-n", "0", "--drift",
                           "--drift-n", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "cost-model drift" in out
    assert "join coverage 3/3 ops = 100%" in out
    assert "gap8" in out and "cortex-m7" in out
