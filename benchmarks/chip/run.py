"""Run one cell of the chip benchmark (BENCHMARK.json) and print its result.

    python3 benchmarks/chip/run.py --workload mnist_L.backlog --seed 7 \
        --seconds 10 --trace 0

One process: import, device check, weights and PTQ, the cell's buckets
compiled and run (all of that is `setup_s`), then `--seconds` of the
cell's traffic, then every answer served checked against the plain
reference.  With `--trace 1` the window runs under the JAX profiler and
the result carries the per-layer metrics instead of the end-to-end ones.
The last line of stdout is one JSON object; the numbers compared, each
beside its limit, are also the last lines of stderr.  Without a TPU, or
with fewer chips than the cell asks for, it exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    # run as a script: its own directory would shadow stdlib modules
    # (trace); the benchmark imports as `benchmarks.chip`, the program
    # from src/
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip import bench, harness, peaks, trace  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    geom: dict
    config: dict
    model: object                    # the configuration's models/<name>.py
    mix: dict
    seconds: float
    setup_s: float
    window: harness.Window
    peaks: dict
    reduction: dict | None = None    # trace.reduce of the traced window
    spans: object = None             # the program's obs.Tracer


def _fallbacks() -> int:
    from repro.nn.backend import BACKENDS
    return int(sum(dict(BACKENDS["pallas"].fallbacks).values()))


@contextlib.contextmanager
def gc_pauses():
    """Record (generation, seconds) of each garbage collection inside."""
    out, t0 = [], [0.0]

    def cb(phase, info):
        if phase == "start":
            t0[0] = time.perf_counter()
        else:
            out.append((info["generation"], time.perf_counter() - t0[0]))
    gc.callbacks.append(cb)
    try:
        yield out
    finally:
        gc.callbacks.remove(cb)


def run_cell(spec: dict, seed: int, seconds: float, traced: bool,
             devices: list, chip_peaks: dict, t_start: float) -> tuple:
    """Set up, measure and check one run on `devices`; returns (result,
    log lines)."""
    import jax
    from repro import obs

    config, mix = spec["config"], spec["mix"]
    annotate = jax.profiler.TraceAnnotation if traced else harness.no_span
    cell = harness.build_cell(config, mix, seed, annotate=annotate)
    compiles, fallbacks = cell.registry.compile_count, _fallbacks()
    spans = obs.Tracer() if traced else None
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # the device's ops and the `bench.*` annotations; no event per
        # Python call, which would slow the host loop being measured
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with (obs.tracing(spans) if traced else contextlib.nullcontext()), \
            gc_pauses() as pauses:
        win = harness.drive(cell, seconds, annotate=annotate)
    if traced:
        jax.profiler.stop_trace()
    log = []
    window_faults = {
        "compiles_in_window": cell.registry.compile_count - compiles,
        "fallbacks_in_window": _fallbacks() - fallbacks}
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    reduction = None
    if traced:
        classes, kernels = {}, {}
        for b in cell.buckets:
            hlo = cell.registry.executable(cell.model_id, b) \
                .compiled.as_text()
            classes.update(trace.classify_hlo(hlo))
            kernels.update(trace.kernel_names(hlo))
        events = trace.load_events(str(TRACE_DIR))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        marks = [(s, s + d) for n, s, d in events["host"]
                 if n == trace.WINDOW_SPAN]
        reduction = trace.reduce(events, marks[0], classes, kernels)
    # the program's state goes before the reference runs
    cell.engine = cell.registry = None
    chk = harness.check(cell, win)
    ctx = Context(geom=config["geometry"], config=config, model=cell.model,
                  mix=mix, seconds=seconds, setup_s=setup_s, window=win,
                  peaks=chip_peaks, reduction=reduction, spans=spans)
    metrics = {}
    for m in spec["per_layer" if traced else "end_to_end"]:
        v = bench.reader(m["name"])(ctx)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    numbers = {**chk["numbers"], **window_faults}
    limits = {**harness.LIMITS, "compiles_in_window": 0,
              "fallbacks_in_window": 0}
    correct = all(numbers[k] <= limits[k] for k in limits)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    if traced:
        device.update(busy_s=reduction["busy_s"],
                      window_s=reduction["window_s"])
    result = {"correct": correct, "attempted": int(len(win.due_s)),
              "failed": chk["failed"],
              "metrics": metrics, "device": device}
    if traced:
        result["breakdown"] = {"device_ops": reduction["device_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    result["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                        for k in limits}
    log.append(f"[window] {seconds} s asked, {win.seconds:.6f} s measured; "
               f"{len(win.due_s)} requests, {win.served_in_window} served "
               f"in the window, {win.waves} waves ({win.rows} rows); "
               f"{win.backlog_at_close} queued at the close; "
               f"{chk['checked']} answers checked over {chk['images']} "
               f"images")
    served = win.done_s[win.done_s <= win.seconds]
    per_slice, _ = np.histogram(served, bins=np.linspace(0, win.seconds, 11))
    log.append(f"[window] served in each tenth of it: {per_slice.tolist()}")
    gc_s = [d for _, d in pauses]
    log.append(f"[gc] {len(gc_s)} collections during the window, "
               f"{sum(g == 2 for g, _ in pauses)} of generation 2; "
               f"{sum(gc_s) * 1e3:.3f} ms in all, longest "
               f"{max(gc_s, default=0.0) * 1e3:.3f} ms")
    if mix["arrival"] == "open":
        late = sorted(win.late_s)
        log.append(f"[generator] submission late by p50 "
                   f"{late[len(late) // 2] * 1e3:.4f} ms, max "
                   f"{late[-1] * 1e3:.4f} ms")
    if traced:
        log.append(f"[trace] busy {reduction['busy_s']} s of "
                   f"{reduction['window_s']} s on {reduction['devices']} "
                   f"device(s); device time by class {reduction['class_s']}, "
                   f"by kernel {reduction['kernel_s']}")
    return result, log


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = bench.resolve(bench.load(), args.workload)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    devices = jax.devices()
    chips = spec["cell"]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    chip_peaks = peaks.peaks_for(devices[0].device_kind)
    result, log = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           devices[:chips], chip_peaks, T_START)
    for line in log:
        print(line, flush=True)
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
