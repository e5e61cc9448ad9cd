"""The engine's per-layer metrics, read from the program's own spans:
exact on a fake-clock tracer, silent where the program records no
phases, and read end to end from a traced run on the CPU."""
import time
import types

import jax

from benchmarks.chip import bench, peaks, run, spans
from repro import obs

from test_chipbench_correct import CLOSED, TINY

READERS = ("engine_host_share.tput", "transfer_ms.tput", "wait_ms.tput")


class Clock:
    """Reads `t`, which only the test moves."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _span(tr, clock, name, secs, **args):
    with tr.span(name, **args):
        clock.t += secs


def _traced(wait="serve.wait"):
    """Three waves (k = 1, 2, 3) of two requests, each enqueued in 0.5 s;
    wave k: bucket 1, transfer 2k, dispatch 1, wait 10k, readback 1 and
    complete 1 s, so it lasts 4 + 12k s.  One second of the caller's
    own between the enqueues and the wave."""
    clock = Clock()
    tr = obs.Tracer(clock=clock)
    for k in (1, 2, 3):
        rids = [2 * k, 2 * k + 1]
        for rid in rids:
            _span(tr, clock, "serve.enqueue", 0.5, model="m", req_id=rid)
        clock.t += 1.0
        with tr.span("serve.wave", model="m", wave=k) as w:
            for name, secs in (("serve.bucket", 1), ("serve.transfer", 2 * k),
                               ("serve.dispatch", 1), (wait, 10 * k),
                               ("serve.readback", 1),
                               ("serve.complete", 1)):
                _span(tr, clock, name, secs)
            w.note(bucket=4, n_real=2, req_ids=",".join(map(str, rids)))
    return tr


def _ctx(tracer, span_waves, span_s):
    window = types.SimpleNamespace(span_waves=span_waves, span_s=span_s)
    return types.SimpleNamespace(spans=tracer, window=window)


def test_readers_on_a_fake_clock():
    # the first two waves are the window's; the third serves the drain
    ctx = _ctx(_traced(), 2, 100.0)
    waves = spans.window_waves(ctx)
    assert [w.args["wave"] for w in waves] == [1, 2]
    assert [w.dur_s for w in waves] == [16.0, 28.0]
    assert bench.reader("transfer_ms.tput")(ctx) == 3e3     # median 2, 4 s
    assert bench.reader("wait_ms.tput")(ctx) == 15e3        # median 10, 20
    # enqueues 4 x 0.5 s, waves (16 - 10) + (28 - 20) s, of 100 s
    assert spans.engine_host_s(ctx) == 16.0
    assert bench.reader("engine_host_share.tput")(ctx) == 16.0


def test_readers_are_silent_without_the_phases():
    # untraced run
    assert all(bench.reader(m)(_ctx(None, 2, 100.0)) is None
               for m in READERS)
    # a program whose waves hold one execute span and no phases
    tr = _traced(wait="serve.execute")
    ctx = _ctx(tr, 2, 100.0)
    assert bench.reader("wait_ms.tput")(ctx) is None
    assert bench.reader("engine_host_share.tput")(ctx) is None
    # fewer waves recorded than the window ran
    assert all(bench.reader(m)(_ctx(_traced(), 4, 100.0)) is None
               for m in READERS)


def test_traced_run_reads_the_engine_metrics():
    """A traced run of the harness on the CPU: the program's spans give
    all three numbers, and the host share stays below the window."""
    spec = {"config": TINY, "mix": CLOSED, "cell": {"chips": 1},
            "end_to_end": [],
            "per_layer": [{"name": m, "unit": "x"} for m in READERS]}
    result, _ = run.run_cell(spec, 2**31 + 5, 0.4, True, jax.devices()[:1],
                             peaks.PEAKS["TPU v5 lite"], time.perf_counter())
    assert result["correct"], result["checks"]
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(got) == set(READERS)
    assert 0 < got["engine_host_share.tput"] < 100
    assert got["transfer_ms.tput"] > 0 and got["wait_ms.tput"] > 0
