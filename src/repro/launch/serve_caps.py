"""Batched int8 CapsNet serving driver (the capsule-side analogue of
launch/serve.py's LM loop).

  PYTHONPATH=src python -m repro.launch.serve_caps --model mnist@jnp \
      --requests 64 --buckets 1,4,16,64

Builds the model lazily in the registry (init -> PTQ on a synthetic
calibration set), warms the wave executables so compile time stays out of
the latency numbers, submits --requests synthetic images through the
bucketed micro-batch scheduler, and prints the serving metrics.  With
--compare-b1 it replays the same requests through a batch-size-1 loop to
show what micro-batching buys; with --mesh host the waves run sharded
over the logical BATCH axes of a mesh built from the local devices.
With --capsbin PATH the engine serves an exported MCU artifact instead:
the `.capsbin` is imported back into a QuantCapsNet (repro.edge
importer) and installed under its program name — the bits in flight are
exactly the bits that shipped.

Imported artifacts pass through the static verifier (repro.analysis)
before they are served; --no-check skips it.

--softmax/--squash select operator variants from the registry
(repro.nn.variants; e.g. the ISLPED'22 approximate softmax/squash) —
on a spec as a rebuilt ModelSpec, on a --capsbin artifact as a pure
plan edit.  Unknown names fail argparse with the registered ones
listed.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import numpy as np

from repro.analysis import CheckError
from repro.launch.mesh import make_host_mesh
from repro.nn.variants import REGISTRY, VariantSet
from repro.serving import ModelRegistry, default_specs, serve_window


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="mnist@jnp",
                    help=f"registry id ({', '.join(sorted(default_specs()))})"
                    "; ignored when --capsbin is given")
    ap.add_argument("--capsbin", metavar="PATH", default=None,
                    help="serve an exported .capsbin artifact (imported "
                    "via repro.edge, installed under its program name)")
    ap.add_argument("--softmax", choices=REGISTRY.names("softmax"),
                    default=None,
                    help="softmax operator variant (repro.nn.variants); "
                    "default: the spec's / artifact's own")
    ap.add_argument("--squash", choices=REGISTRY.names("squash"),
                    default=None,
                    help="squash operator variant; default: the spec's "
                    "/ artifact's own")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--buckets", default="1,4,16,64",
                    help="comma-separated micro-batch bucket sizes")
    ap.add_argument("--mesh", choices=("none", "host"), default="none",
                    help="host: shard waves over a mesh of local devices")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compare-b1", action="store_true",
                    help="also serve via a batch-size-1 loop and report "
                    "the batched speedup")
    ap.add_argument("--export", metavar="DIR", default=None,
                    help="also dump the served model as an MCU artifact "
                    "(.capsbin + manifest + .c/.h via repro.edge) and "
                    "print the flash/RAM report")
    ap.add_argument("--check", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="statically verify imported --capsbin artifacts "
                    "and --export programs (repro.analysis)")
    ap.add_argument("--profile", action="store_true",
                    help="print the static MCU cycle/latency estimate of "
                    "the served model (repro.edge.costmodel, both "
                    "calibrated profiles)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record spans for the whole run (PTQ, wave "
                    "compile, enqueue->execute) and write Chrome "
                    "trace-event JSON to PATH (load in "
                    "chrome://tracing / Perfetto)")
    ap.add_argument("--trace-summary", action="store_true",
                    help="print the trace analyzer's report of this "
                    "run (repro.obs.analyze: span stats, wave critical "
                    "paths, per-request timelines); implies recording "
                    "spans even without --trace")
    ap.add_argument("--metrics-out", metavar="PATH", default=None,
                    help="dump the run's final metrics snapshots as "
                    "JSON on exit (schema repro.metrics/v1: process + "
                    "run registries + the serve window summary); "
                    "repro.obs.analyze accepts it via --metrics")
    ap.add_argument("--numerics-out", metavar="PATH", default=None,
                    help="after serving, run a probed numeric-health "
                    "pass of the served model (repro.obs.numerics: "
                    "saturation, int32 clips, bound tightness, SNR) "
                    "and write the repro.numerics/v1 JSON doc to PATH")
    args = ap.parse_args(argv)

    from repro import obs
    tracer = None
    if args.trace or args.trace_summary:
        tracer = obs.Tracer()
        obs.set_tracer(tracer)
    # one run-scoped registry sees the model-registry counters and the
    # serve window's ServeMetrics mirror; METRICS (process) keeps the
    # singleton counters (pallas fallbacks)
    run_metrics = obs.MetricsRegistry("serve_caps") \
        if args.metrics_out else None

    # serving waves shard over BATCH=("pod","data"): give "data" the
    # devices (make_host_mesh fills the LAST axis; "model" would make the
    # batch constraint a 1x1 no-op and replicate every wave)
    mesh = make_host_mesh(("pod", "model", "data")) \
        if args.mesh == "host" else None
    registry = ModelRegistry(mesh=mesh, metrics=run_metrics)
    buckets = tuple(int(b) for b in args.buckets.split(","))

    if args.capsbin:
        try:
            qnet = registry.install_artifact(args.capsbin,
                                             check=args.check)
        except CheckError as e:      # refuse to serve a bad artifact
            print(f"[serve_caps] STATIC CHECK FAILED for "
                  f"{args.capsbin}:\n{e}", file=sys.stderr)
            return 1
        model_id = qnet.pipeline.cfg.name        # the program's name
        if args.softmax or args.squash:          # plan edit on the artifact
            vs = dataclasses.replace(
                qnet.variants,
                **{k: v for k, v in (("softmax", args.softmax),
                                     ("squash", args.squash)) if v})
            qnet = qnet.with_variants(vs)
            registry.install(model_id, qnet)
        rng = np.random.default_rng(args.seed)
        images = rng.uniform(0, 1, (args.requests,)
                             + registry.input_shape(model_id)) \
            .astype(np.float32)
        print(f"[serve_caps] imported {args.capsbin} as {model_id!r} "
              f"({qnet.memory_bytes() / 1000:.1f} KB int8) "
              f"variants={qnet.variants.tag} buckets={buckets} "
              f"mesh={'none' if mesh is None else dict(mesh.shape)}")
    else:
        model_id = args.model
        if model_id not in registry.specs:
            ap.error(f"unknown model {model_id!r}; have "
                     f"{sorted(registry.specs)} (or pass --capsbin)")
        spec = registry.specs[model_id]
        if args.softmax or args.squash:
            spec = dataclasses.replace(
                spec,
                **{f"{k}_impl": v for k, v in (("softmax", args.softmax),
                                               ("squash", args.squash))
                   if v})
            registry.register(spec)
        images = spec.images(args.requests, args.seed)
        print(f"[serve_caps] model={model_id} ({spec.config.name}, "
              f"backend={spec.backend}, variants={spec.variants.tag}) "
              f"buckets={buckets} "
              f"mesh={'none' if mesh is None else dict(mesh.shape)}")
        t0 = time.perf_counter()
        registry.model(model_id)
        print(f"[serve_caps] lazy PTQ build: "
              f"{time.perf_counter() - t0:.2f} s "
              f"({registry.model(model_id).memory_bytes() / 1000:.1f} "
              "KB int8)")
    if args.export:
        from repro.edge import format_export
        result = registry.export(model_id, args.export, check=args.check)
        print("[serve_caps] exported MCU artifact:")
        print(format_export(result))
    if args.profile:
        from repro.edge import format_estimates, lower
        program = lower(registry.model(model_id))
        print("[serve_caps] static MCU latency estimate:")
        print(format_estimates(program))

    engine, wall = serve_window(registry, buckets, images, model_id,
                                metrics_registry=run_metrics)
    print("[serve_caps]", engine.metrics.report())
    print(f"[serve_caps] executables compiled: {registry.compile_count}, "
          f"cache hits: {registry.exec_hits}")
    if registry.variant_fallbacks:
        print(f"[serve_caps] pallas->oracle variant fallbacks: "
              f"{registry.variant_fallbacks}")
    if args.compare_b1:
        b1_engine, b1_wall = serve_window(registry, (1,), images, model_id)
        print("[serve_caps] b1  :", b1_engine.metrics.report())
        print(f"[serve_caps] batched speedup over b1 loop: "
              f"{b1_wall / max(wall, 1e-9):.2f}x")
    if args.numerics_out:
        import json
        import pathlib

        from repro.obs import numerics as health
        qnet = registry.model(model_id)
        params = None
        if not args.capsbin:             # spec path: rebuild the float
            import jax                   # oracle weights for SNR rows
            params = qnet.pipeline.init(jax.random.key(spec.seed))
        report = health.run_numerics(qnet, images[:16], params=params,
                                     metrics=run_metrics)
        path = pathlib.Path(args.numerics_out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(report.to_doc(), indent=1,
                                   sort_keys=True))
        print(f"[serve_caps] numerics: int32 clips "
              f"{report.total_int32_clip()}, worst saturation "
              f"{report.worst_saturation_rate() * 100:.2f}%, "
              f"wrote {path}")
    if args.metrics_out:
        import json
        import pathlib
        doc = {"schema": "repro.metrics/v1",
               "process": obs.METRICS.snapshot(),
               "run": run_metrics.snapshot(),
               "serve_summary": engine.metrics.summary()}
        path = pathlib.Path(args.metrics_out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(doc, indent=1, sort_keys=True))
        print(f"[serve_caps] wrote metrics snapshot to {path}")
    if tracer is not None:
        obs.set_tracer(None)
        if args.trace:
            tracer.write_chrome_trace(args.trace)
            print(f"[serve_caps] wrote {tracer.span_count()} spans to "
                  f"{args.trace} (chrome://tracing)")
        if args.trace_summary:
            from repro.obs import analyze
            print("[serve_caps] trace summary:")
            print(analyze.format_analysis(analyze.analyze(tracer)))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
