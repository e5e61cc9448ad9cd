# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point:

    PYTHONPATH=src python -m benchmarks.run [--smoke]
        [--out artifacts/bench] [--stamp <id>] [--sections a,b,...]

Sections (one per paper table):
  Table 2  -> bench_quantization   (footprint / PTQ cost)
  Tables 3/4 -> bench_matmul       (int8 matmul variants)
  Tables 5/6 -> bench_primary_caps (primary capsule layer)
  Tables 7/8 -> bench_capsule_layer(capsule layer / dynamic routing,
                                    unfused vs fused-VMEM kernel)
beyond-paper:
  serving    -> bench_serving      (batched engine vs batch-1 loop)
  training   -> bench_train_caps   (float vs QAT step cost, Table-2
                                    accuracy deltas via repro.captrain)
  variants   -> bench_variants     (ISLPED'22 approx softmax/squash:
                                    accuracy/throughput per registered
                                    operator-variant set x rounding)
  numerics   -> bench_numerics      (probed q7 numeric health:
                                    saturation, bound tightness,
                                    q7-vs-f32 SNR; the validator gates
                                    on zero int32-clip events)
  search     -> bench_search        (quantization/variant Pareto search;
                                    the validator gates on a clean,
                                    mutually non-dominated frontier)
  observability -> process metrics snapshot (pallas fallback counters;
                                    the validator gates on zero
                                    default-variant fallbacks)
plus the roofline summary from the dry-run artifacts (if present).

Every section also lands as `<out>/BENCH_<section>.json`
(schema repro.bench/v1, see benchmarks/util.py); `--stamp` (or
REPRO_BENCH_STAMP — CI passes the commit SHA) identifies the run
instead of ambient time, so artifacts are reproducible.
`benchmarks.validate` checks the emitted set.

CPU wall-clock is the validation substrate (interpret-mode kernels); the
derived column carries the hardware-independent figure.  `--smoke` (CI)
runs every section at minimal reps/sizes so harness bit-rot fails fast.
"""
import argparse
import os
import sys


def _observability_section(util) -> None:
    """Snapshot the process metrics registry after every section ran:
    how often the pallas backend fell back to the jnp oracle, split
    default vs non-default variant (bench_variants legitimately drives
    non-default fallbacks; a DEFAULT-variant fallback would mean the
    fused kernels stopped covering the default plan — the validator
    fails the run on it)."""
    from repro.nn.backend import BACKENDS
    from repro.nn.variants import REGISTRY
    defaults = {REGISTRY.default("softmax"), REGISTRY.default("squash")}
    fallbacks = BACKENDS["pallas"].fallbacks
    total = sum(fallbacks.values())
    default_hits = sum(n for (op, variant), n in fallbacks.items()
                       if variant in defaults)
    util.begin_section("observability")
    util.add_figures(total_fallback_decisions=int(total),
                     default_variant_fallbacks=int(default_hits),
                     fallback_series={f"{op}:{variant}": int(n)
                                      for (op, variant), n
                                      in fallbacks.items()})
    util.csv_row("pallas_fallbacks", 0.0,
                 f"total={total}_default={default_hits}",
                 total=int(total), default=int(default_hits))
    util.end_section()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="minimal reps/sizes (CI bit-rot check)")
    ap.add_argument("--out", default=None, metavar="DIR",
                    help="also write BENCH_<section>.json artifacts "
                    "into DIR (schema repro.bench/v1)")
    ap.add_argument("--stamp", default=None,
                    help="run identifier stored in every artifact "
                    "(default: $REPRO_BENCH_STAMP, else 'unstamped'; "
                    "CI passes the commit SHA)")
    ap.add_argument("--sections", default=None,
                    help="comma-separated subset of sections to run "
                    "(default: all), e.g. serving,edge_vm,variants,"
                    "observability — the perf-gate set CI re-records")
    args = ap.parse_args(sys.argv[1:] if argv is None else argv)
    if args.smoke:
        # must land before benchmarks.util is imported (it reads the env)
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    from benchmarks import util
    if args.out:
        stamp = args.stamp or os.environ.get("REPRO_BENCH_STAMP",
                                             "unstamped")
        util.start_recording(args.out, stamp)
    print("name,us_per_call,derived")
    from benchmarks import (bench_capsule_layer, bench_edge_vm,
                            bench_matmul, bench_numerics,
                            bench_primary_caps, bench_quantization,
                            bench_search, bench_serving,
                            bench_train_caps, bench_variants)
    sections = [
        ("quantization", {"tables": [2]}, bench_quantization.main,
         "Table 2: quantization framework"),
        ("matmul", {"tables": [3, 4]}, bench_matmul.main,
         "Tables 3/4: int8 matmul variants"),
        ("primary_caps", {"tables": [5, 6]}, bench_primary_caps.main,
         "Tables 5/6: primary capsule layer"),
        ("capsule_layer", {"tables": [7, 8]}, bench_capsule_layer.main,
         "Tables 7/8: capsule layer (dynamic routing)"),
        ("serving", {}, bench_serving.main,
         "Serving: batched int8 engine vs b1 loop"),
        ("edge_vm", {}, bench_edge_vm.main,
         "Edge export: q7 VM + arena plan"),
        ("numerics", {}, bench_numerics.main,
         "Numerics: saturation / bound tightness / q7-vs-f32 SNR"),
        ("training", {}, bench_train_caps.main,
         "Training: float vs QAT steps + Table-2 accuracy"),
        ("variants", {}, bench_variants.main,
         "Operator variants: ISLPED'22 approx softmax/squash"),
        ("search", {}, bench_search.main,
         "Search: verified Pareto frontier over quantization/variants"),
        ("observability", {}, lambda: _observability_section(util),
         "Observability: process metrics snapshot"),
    ]
    only = None
    if args.sections:
        only = set(args.sections.split(","))
        unknown = only - util.KNOWN_SECTIONS
        if unknown:
            ap.error(f"unknown sections {sorted(unknown)}; known: "
                     f"{sorted(util.KNOWN_SECTIONS)}")
    for name, config, fn, title in sections:
        if only is not None and name not in only:
            continue
        print(f"# --- {title} ---")
        if name != "observability":    # it opens its own section
            util.begin_section(name, **config)
        fn()
        util.end_section()

    import pathlib
    if pathlib.Path("artifacts/dryrun").exists():
        from benchmarks import roofline
        opt = roofline.load("single", tag="opt")
        rows = opt or roofline.load("single")
        grid = "optimized (§Perf)" if opt else "baseline"
        base = {(r["arch"], r["shape"]): r
                for r in roofline.load("single")}
        print(f"# --- Roofline summary: {grid} grid, single-pod "
              "(full table: python -m benchmarks.roofline) ---")
        for r in rows:
            t = r["terms"]
            bound = max(t.values())
            b = base.get((r["arch"], r["shape"]))
            speedup = ""
            if b is not None and opt:
                b_bound = max(b["terms"].values())
                speedup = f"_speedup={b_bound/max(bound,1e-12):.1f}x"
            print(f"roofline_{r['arch']}_{r['shape']},"
                  f"{bound*1e6:.0f},"
                  f"dom={r['dominant'].replace('_s','')}"
                  f"_frac={r['roofline_fraction']:.4f}{speedup}")
    rec = util.recorder()
    if rec is not None:
        rec.end_section()
        print(f"# wrote {len(rec.written)} BENCH_*.json artifacts "
              f"(stamp={rec.stamp}) to {rec.out_dir}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
