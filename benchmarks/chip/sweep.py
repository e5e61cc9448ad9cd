"""Find the knee of an open-loop cell: the highest offered rate at which at
least 99% of the requests due in the window are served inside it and
fewer than one largest bucket of requests is queued at its close.

    python3 benchmarks/chip/sweep.py --config capsnet_mnist_L --seed 5 \
        --seconds 10 --base 3000 --fractions 0.3,0.5,0.7,0.8,0.9,1.0

One process builds the configuration once (a pool of 1024 images, every
bucket warmed) and offers each rate, a fraction of `--base` (the backlog
cell's measured images/s), as an open Poisson loop for `--seconds`; one
JSON row per rate: offered rate, share served in the window, backlog at
the close, p50 and p99 (ms).  An open-loop cell then fixes its rate in
its traffic file at 0.8 of the knee.
"""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from benchmarks.chip import bench, harness, traffic  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--base", type=float, required=True)
    ap.add_argument("--fractions", required=True)
    args = ap.parse_args(argv)

    b = bench.load()
    entry = {c["name"]: c for c in b["configs"]}[args.config]
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = {"arrival": "open", "rate_per_s": args.base, "pool": 1024,
           "warm": "all"}
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 1
    cell = harness.build_cell(config, mix, args.seed)
    for f in (float(x) for x in args.fractions.split(",")):
        rate = f * args.base
        w = harness.drive(cell, args.seconds, rate_per_s=rate)
        lat = traffic.latencies_s(w.due_s, w.done_s)
        served = w.served_in_window / len(w.due_s)
        knee_ok = served >= 0.99 and \
            w.backlog_at_close < cell.engine.max_bucket
        print(json.dumps({
            "fraction": f, "rate_per_s": rate, "requests": len(w.due_s),
            "served_in_window": served, "backlog_at_close":
            w.backlog_at_close, "p99_ms": 1e3 * traffic.percentile(lat, 99),
            "p50_ms": 1e3 * traffic.percentile(lat, 50),
            "waves": w.waves, "mean_wave_rows": w.rows / max(w.waves, 1),
            "late_max_ms": 1e3 * float(max(w.late_s)),
            "within_knee": knee_ok}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
