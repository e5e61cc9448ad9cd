"""The program's own spans, as the engine's per-layer metrics read them.

A traced run installs an `obs.Tracer` around the window (`ctx.spans`).
The window's waves are the first `window.span_waves` `serve.wave` roots:
the waves the traffic loop ran inside `bench.window`; those that serve
the requests still queued at its close come after them.  Times are on
the tracer's clock, `time.perf_counter`, the same host clock as
`window.span_s`.

Every reader gives None when the run was not traced, or when a wave in
the window lacks the phase it reads: a program whose engine does not
record `serve.transfer` / `serve.wait` reads nothing.
"""
from __future__ import annotations

import statistics


def window_waves(ctx) -> list | None:
    """The `serve.wave` spans of the window, or None."""
    tracer, n = ctx.spans, ctx.window.span_waves
    if tracer is None or n <= 0:
        return None
    waves = [r for r in tracer.roots if r.name == "serve.wave"][:n]
    return waves if len(waves) == n else None


def phase_s(wave, name: str) -> float | None:
    """Seconds of the wave's child span `name`, or None."""
    for c in wave.children:
        if c.name == name and c.dur_s is not None:
            return c.dur_s
    return None


def phase_durations_s(ctx, name: str) -> list | None:
    """One duration of phase `name` per wave of the window, or None."""
    waves = window_waves(ctx)
    if waves is None:
        return None
    out = [phase_s(w, name) for w in waves]
    return None if None in out else out


def median_ms(ctx, name: str) -> float | None:
    """Median milliseconds per wave of phase `name`."""
    d = phase_durations_s(ctx, name)
    return None if d is None else 1e3 * statistics.median(d)


def engine_host_s(ctx) -> float | None:
    """Host seconds in engine code, not waiting on the device: the
    `serve.enqueue` spans of the window's waves' requests, plus each
    wave less its `serve.wait`."""
    waves = window_waves(ctx)
    waits = phase_durations_s(ctx, "serve.wait")
    if waves is None or waits is None:
        return None
    enqueue = {int(s.args["req_id"]): s.dur_s for s in ctx.spans.roots
               if s.name == "serve.enqueue" and "req_id" in s.args}
    rids = [int(r) for w in waves
            for r in str(w.args.get("req_ids", "")).split(",") if r]
    if any(r not in enqueue for r in rids):
        return None
    return (sum(enqueue[r] for r in rids)
            + sum(w.dur_s for w in waves) - sum(waits))
