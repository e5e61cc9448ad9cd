"""Smoke run of the int8 CapsNet serving path and the QAT trainer on a TPU.

    python chip_smoke.py             # one chip: serving + QAT phases
    python chip_smoke.py --chips 4   # four chips: sharded wave vs one device

One process drives the chip through the entry points a user calls:
`ModelRegistry` -> `CapsServeEngine` -> AOT wave -> `PallasBackend`
(Pallas squash + fused routing kernels), and `CapsTrainer.fit`.  Weights
are random from each spec's seed.

  * serving: every geometry the registry serves (MNIST "L" at buckets
    1/4/16/64, smallNORB "M", CIFAR-10 "S" and edge_tiny at 1/64) on the
    `pallas` backend, checked request by request, bit for bit, against
    the `jnp` oracle backend on the same chip.  Every pallas wave must
    hold a Mosaic kernel; no pallas->oracle fallback and no compile may
    happen inside a served window.
  * QAT: a few float and fake-quant steps of MNIST "L"; every loss must
    be finite.
  * --chips 4: `mnist@pallas` waves batch-sharded over a mesh of the
    four chips (as `serve_caps --mesh host` builds it), bit for bit
    against the same waves on one device.

Any failed check raises, so the exit code is non-zero.  Without a TPU
the script exits 1 before any phase.  The last line of stdout is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
Timings printed on the way are of one unrepeated run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

# (geometry, buckets, request bursts): each burst is submitted and then
# drained, so every bucket serves waves, some of them padded
SERVE_PLAN = (
    ("mnist", (1, 4, 16, 64), (64, 40, 13, 3, 1) * 2 + (14,)),
    ("smallnorb", (1, 64), (40, 1, 6, 1)),
    ("cifar10", (1, 64), (40, 1, 6, 1)),
    ("edge_tiny", (1, 64), (40, 1, 6, 1)),
)
REQUEST_SEED = 7


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def serve_window(registry, model_id, buckets, images, bursts) -> list:
    """Warm `model_id` up, then serve `images` in bursts; returns the
    completions.  Fails if the window compiles anything."""
    from repro.serving import CapsServeEngine
    engine = CapsServeEngine(registry, buckets=buckets)
    t0 = time.perf_counter()
    engine.warmup(model_id)
    setup_s = time.perf_counter() - t0
    compiles = registry.compile_count
    done, start = [], 0
    t0 = time.perf_counter()
    for n in bursts:
        engine.submit_many(images[start:start + n], model_id)
        start += n
        done += engine.drain()
    wall_s = time.perf_counter() - t0
    check(registry.compile_count == compiles,
          f"{model_id}: {registry.compile_count - compiles} compiles "
          "inside the served window")
    check(len(done) == len(images),
          f"{model_id}: {len(done)} of {len(images)} requests completed")
    check({c.bucket for c in done} == set(buckets),
          f"{model_id}: served buckets {sorted({c.bucket for c in done})}"
          f", expected {sorted(buckets)}")
    print(f"[serve] {model_id}: warmup (PTQ + {len(buckets)} wave "
          f"compiles) {setup_s:.1f} s; {len(done)} requests in "
          f"{wall_s:.3f} s = {len(done) / wall_s:.1f} images/s "
          "(one unrepeated run)", flush=True)
    return done


def check_same(model, want: list, got: list) -> None:
    check([c.rid for c in want] == [c.rid for c in got],
          f"{model}: request order differs")
    for a, b in zip(want, got):
        check(np.array_equal(a.v_q, b.v_q) and a.pred == b.pred,
              f"{model}: request {a.rid} differs (v_q or pred)")


def serving_phase(registry) -> None:
    from repro.nn.backend import BACKENDS
    for geometry, buckets, bursts in SERVE_PLAN:
        pallas_id = f"{geometry}@pallas"
        images = registry.specs[pallas_id].images(sum(bursts), REQUEST_SEED)
        want = serve_window(registry, f"{geometry}@jnp", buckets, images,
                            bursts)
        got = serve_window(registry, pallas_id, buckets, images, bursts)
        check_same(geometry, want, got)
        for b in buckets:
            hlo = registry.executable(pallas_id, b).compiled.as_text()
            check("tpu_custom_call" in hlo,
                  f"{pallas_id} bucket {b}: no Mosaic kernel in the wave")
        print(f"[serve] {geometry}: @pallas == @jnp bit for bit on "
              f"{len(images)} requests", flush=True)
    check(not dict(BACKENDS["pallas"].fallbacks),
          f"pallas fallbacks: {dict(BACKENDS['pallas'].fallbacks)}")
    check(not registry.variant_fallbacks,
          f"registry variant fallbacks: {registry.variant_fallbacks}")


def qat_phase(cfg, tcfg, float_steps: int = 3, qat_steps: int = 3) -> None:
    from repro.captrain import CapsTrainer
    trainer = CapsTrainer(cfg, tcfg)
    state = trainer.init_state()
    losses = []
    for qat, steps in ((False, float_steps), (True, qat_steps)):
        t0 = time.perf_counter()
        state, plan, history = trainer.fit(state, steps, qat=qat)
        losses += [h["loss"] for h in history]
        print(f"[qat] {cfg.name} {'qat' if qat else 'float'}: {steps} "
              f"steps (first compiles) in {time.perf_counter() - t0:.1f} "
              f"s, losses {[h['loss'] for h in history]}", flush=True)
    check(plan is not None, "QAT derived no plan")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")


def mesh_phase(model_id: str = "mnist@pallas", bucket: int = 64) -> None:
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.serving import ModelRegistry
    # BATCH = ("pod", "data"): the devices go on "data"
    mesh = make_host_mesh(("pod", "model", "data"))
    sharded, single = ModelRegistry(mesh=mesh), ModelRegistry()
    images = sharded.specs[model_id].images(2 * bucket, REQUEST_SEED)
    bursts = (bucket, bucket)
    got = serve_window(sharded, model_id, (bucket,), images, bursts)
    want = serve_window(single, model_id, (bucket,), images, bursts)
    exe = sharded.executable(model_id, bucket)
    devices = exe.in_sharding.device_set
    check(len(devices) == jax.device_count()
          and not exe.in_sharding.is_fully_replicated,
          f"wave input sharding {exe.in_sharding} does not split over "
          f"all {jax.device_count()} devices")
    hlo = exe.compiled.as_text()
    check("tpu_custom_call" in hlo, f"{model_id}: no Mosaic kernel")
    check_same(model_id, want, got)
    print(f"[mesh] {model_id} bucket {bucket} sharded over "
          f"{sorted(d.id for d in devices)} (mesh {dict(mesh.shape)}): "
          f"== one device bit for bit on {len(images)} requests; HLO "
          f"all-gathers {hlo.count('all-gather(')}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-serving phase")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"[device] jax {jax.__version__}: platform={dev.platform} "
          f"kind={dev.device_kind!r} count={len(devices)}; compile cache "
          f"{cache_dir}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase()
    else:
        from repro.captrain import TrainConfig
        from repro.nn.config import MNIST
        from repro.serving import ModelRegistry
        serving_phase(ModelRegistry())
        qat_phase(MNIST, TrainConfig(dataset="mnist", batch=64))
    print(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
