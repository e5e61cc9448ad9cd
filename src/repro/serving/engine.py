"""CapsServeEngine: request queue + bucketed micro-batch scheduler.

The serving problem the paper leaves open: int8 CapsNet inference is
cheap per image, but XLA executables are shape-specialized — serving
arbitrary request counts naively either recompiles per batch size or
runs everything at batch 1.  The engine holds a FIFO request queue and
drains it in WAVES: each wave takes the longest run of queued requests
that share the head request's model, caps it at the largest bucket, and
pads the batch up to the smallest bucket that fits (default 1/4/16/64).
XLA therefore compiles once per (model, backend, bucket) — the registry
caches the executables — and every later wave of any size reuses one of
those few shapes.

Padding is semantically free: conv, squash and routing act per-row, so
pad rows cannot perturb real rows, and the engine's outputs are
bit-identical to calling `QuantCapsNet.forward` directly (pinned by
tests/test_serving.py).

Scheduling is deterministic: same submission order -> same waves, same
buckets, same bits.  The clock is injectable so tests can pin latency
accounting exactly.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import numpy as np

from repro import obs
from repro.serving.metrics import ServeMetrics
from repro.serving.registry import ModelRegistry

DEFAULT_BUCKETS = (1, 4, 16, 64)


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    model_id: str
    image: np.ndarray                # [H,W,C] float32
    t_enq: float


@dataclasses.dataclass(frozen=True)
class Completion:
    rid: int
    model_id: str
    v_q: np.ndarray                  # int8 class capsules [J, O]
    lengths: np.ndarray              # float32 [J]
    pred: int
    wave: int                        # index of the wave that served it
    bucket: int                      # padded wave size
    latency_s: float                 # enqueue -> completion


class CapsServeEngine:
    def __init__(self, registry: ModelRegistry,
                 buckets=DEFAULT_BUCKETS,
                 metrics: ServeMetrics | None = None,
                 clock=time.perf_counter,
                 tracer: obs.Tracer | None = None):
        buckets = tuple(sorted({int(b) for b in buckets}))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"need positive bucket sizes, got {buckets}")
        self.registry = registry
        self.buckets = buckets
        self.metrics = ServeMetrics() if metrics is None else metrics
        self.clock = clock
        # explicit tracer wins; otherwise the ambient obs tracer (if
        # installed) picks the spans up — NULL_SPAN no-ops when neither
        self.tracer = tracer
        self._queue: collections.deque = collections.deque()
        self._next_rid = 0
        self._next_wave = 0

    def _span(self, name: str, **args):
        if self.tracer is not None:
            return self.tracer.span(name, **args)
        return obs.span(name, **args)

    # ------------------------------------------------------------------
    # queue side
    # ------------------------------------------------------------------
    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def queue_depth(self) -> int:
        return len(self._queue)

    def bucket_for(self, n: int) -> int:
        """Smallest bucket that fits n rows (n is pre-capped by the
        scheduler, so the largest bucket always fits)."""
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"wave of {n} exceeds max bucket {self.max_bucket}")

    def submit(self, image, model_id: str) -> int:
        if not self.registry.has(model_id):
            raise KeyError(f"unknown model {model_id!r}; have "
                           f"{self.registry.model_ids()}")
        image = np.asarray(image, np.float32)
        shape = self.registry.input_shape(model_id)
        if image.shape != shape:
            raise ValueError(
                f"{model_id} expects image shape {shape}, got {image.shape}")
        rid = self._next_rid
        self._next_rid += 1
        with self._span("serve.enqueue", model=model_id, req_id=rid):
            t = self.clock()
            self._queue.append(Request(rid, model_id, image, t))
            self.metrics.record_submit(t, len(self._queue))
        return rid

    def submit_many(self, images, model_id: str) -> list:
        return [self.submit(img, model_id) for img in images]

    # ------------------------------------------------------------------
    # scheduler side
    # ------------------------------------------------------------------
    def step(self) -> list:
        """Drain ONE wave: the longest same-model run at the queue head,
        capped at the largest bucket.  Returns its completions in
        submission order ([] when idle)."""
        if not self._queue:
            return []
        model_id = self._queue[0].model_id
        with self._span("serve.wave", model=model_id,
                        wave=self._next_wave) as wave_span:
            t_start = self.clock()
            with self._span("serve.bucket"):
                wave: list = []
                for r in self._queue:            # peek, don't pop yet
                    if (r.model_id != model_id
                            or len(wave) == self.max_bucket):
                        break
                    wave.append(r)
                bucket = self.bucket_for(len(wave))
                x = np.zeros(
                    (bucket,) + self.registry.input_shape(model_id),
                    np.float32)
                for i, r in enumerate(wave):
                    x[i] = r.image
            if wave_span is not obs.NULL_SPAN:
                # the analyzer reconstructs per-request timelines by
                # joining enqueue req_id against this membership
                # (comma-joined: span args are scalar-or-string in the
                # Chrome export)
                wave_span.note(bucket=bucket, n_real=len(wave),
                               req_ids=",".join(str(r.rid) for r in wave))

            # a cache miss records serving.compile_wave (and
            # serving.ptq_build) inside the wave; a hit is a dict lookup
            exe = self.registry.executable(model_id, bucket)
            t0 = self.clock()
            with self._span("serve.transfer", bucket=bucket):
                x = exe.put(x)
            t1 = self.clock()
            with self._span("serve.dispatch"):
                out = exe(x)                     # returns once queued
            t2 = self.clock()
            with self._span("serve.wait"):
                jax.block_until_ready(out)       # host blocked on device
            t3 = self.clock()
            with self._span("serve.readback"):
                v_q, lengths, pred = (np.asarray(o) for o in out)
            t_done = self.clock()
            with self._span("serve.complete"):
                # only now is the wave irrevocably served: a raising
                # executable leaves the queue intact so the requests can
                # be retried
                for _ in wave:
                    self._queue.popleft()

                wave_idx = self._next_wave
                self._next_wave += 1
                done = [Completion(rid=r.rid, model_id=model_id,
                                   v_q=v_q[i], lengths=lengths[i],
                                   pred=int(pred[i]), wave=wave_idx,
                                   bucket=bucket,
                                   latency_s=t_done - r.t_enq)
                        for i, r in enumerate(wave)]
                wait_s = t3 - t2
                self.metrics.record_wave(
                    bucket=bucket, n_real=len(wave), exec_s=t_done - t0,
                    t_done=t_done,
                    latencies_s=[c.latency_s for c in done],
                    transfer_s=t1 - t0, dispatch_s=t2 - t1, wait_s=wait_s,
                    readback_s=t_done - t3,
                    host_s=self.clock() - t_start - wait_s)
        return done

    def drain(self) -> list:
        """Run waves until the queue is empty; completions in submission
        order per model run."""
        out: list = []
        while self._queue:
            out.extend(self.step())
        return out

    def warmup(self, model_id: str, buckets=None) -> None:
        """Pre-build the model and its wave executables so first-request
        latency excludes PTQ + XLA compile."""
        for b in (self.buckets if buckets is None else buckets):
            self.registry.executable(model_id, b)


def serve_window(registry, buckets, images, model_id, *,
                 metrics_registry=None) -> tuple:
    """The measurement harness serve_caps and bench_serving share: serve
    every image through a fresh warmed engine, timing submit -> drained.
    Returns (engine, wall_s).  `metrics_registry` mirrors the window's
    ServeMetrics into an obs.MetricsRegistry (serve_caps --metrics-out
    snapshots it next to the registry/process counters)."""
    metrics = None if metrics_registry is None \
        else ServeMetrics(registry=metrics_registry)
    engine = CapsServeEngine(registry, buckets=buckets, metrics=metrics)
    engine.warmup(model_id)
    t0 = time.perf_counter()
    engine.submit_many(images, model_id)
    done = engine.drain()
    wall = time.perf_counter() - t0
    assert len(done) == len(images)
    return engine, wall
