"""One run of one cell: build the model through the program's normal
path, warm up, drive the traffic for a timed window, then check every
answer served against the plain reference.

    build_cell    the configuration's model module (`bench.model`),
                  weights from the seed (one jitted call on the device),
                  post-training quantization by the program
                  (`CapsPipeline.quantize`), `ModelRegistry.install`,
                  `CapsServeEngine`, and the cell's buckets compiled and
                  run once each;
    drive         the traffic loop (closed backlog or open Poisson) for
                  the window, then the requests still queued served to
                  the end (they are due, so they are checked);
    check         the model module's reference over the images served,
                  compared with every answer the program gave.

The program is used as a user would use it; nothing here reaches into
its internals beyond the counters it exposes (`registry.compile_count`,
the Pallas backend's fallback decisions).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import numpy as np

from benchmarks.chip import bench, images, traffic as tr

# how long after the window's close the queued requests may take to be
# served before the rest count as never served
DRAIN_LIMIT_S = 60.0


@dataclasses.dataclass
class Cell:
    config: dict
    mix: dict
    model: object         # the configuration's models/<name>.py
    model_id: str
    registry: object
    engine: object
    params: dict          # host copy of the float weights
    calib: np.ndarray
    pool: np.ndarray
    rngs: dict
    buckets: tuple        # the buckets set-up compiled and ran


def build_cell(config: dict, mix: dict, seed: int, *, annotate=None) -> Cell:
    import jax
    import jax.numpy as jnp
    from repro.serving import CapsServeEngine, ModelRegistry

    annotate = annotate or no_span
    model = bench.model(config)
    g = config["geometry"]
    rngs = tr.streams(seed)
    with annotate("bench.make_inputs"):
        params = model.make_params(g, rngs["weights"])
        calib = images.make_images(config["images"], g["input_shape"],
                                   config["calib_n"], rngs["calib"])
        pool = images.make_images(config["images"], g["input_shape"],
                                  mix["pool"], rngs["pool"])
    pipe = model.pipeline(config)
    with annotate("bench.ptq"), \
            jax.default_matmul_precision(config["calibration_precision"]):
        qnet = pipe.quantize(params, jnp.asarray(calib),
                             rounding=config["rounding"],
                             backend=config["backend"])
    registry = ModelRegistry(specs={})
    model_id = f"{config['name']}@{config['backend']}"
    registry.install(model_id, qnet)
    engine = CapsServeEngine(registry)
    buckets = engine.buckets if mix["warm"] == "all" \
        else (engine.max_bucket,)
    with annotate("bench.compile"):
        engine.warmup(model_id, buckets)
    with annotate("bench.warm_waves"):
        for _ in range(2):                 # first runs land in set-up
            for b in buckets:
                engine.submit_many(pool[:b], model_id)
                engine.drain()
    return Cell(config=config, mix=mix, model=model, model_id=model_id,
                registry=registry, engine=engine,
                params=jax.device_get(params), calib=calib, pool=pool,
                rngs=rngs, buckets=tuple(buckets))


@contextlib.contextmanager
def no_span(name):
    yield


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Window:
    seconds: float            # the window's length on the host clock
    due_s: np.ndarray         # per request, from the window's start
    done_s: np.ndarray        # completion, nan if never served
    pool_idx: np.ndarray
    v_q: np.ndarray           # [n, J, O] int8 answers (0 if never served)
    pred: np.ndarray          # [n] (-1 if never served)
    late_s: np.ndarray        # submission time minus due time
    waves: int                # waves that completed inside the window
    rows: int                 # their padded rows
    backlog_at_close: int     # requests queued when the window closed
    span_s: float             # the traffic loop, drawn as "bench.window"
    span_waves: int           # waves run inside that span
    span_rows: int

    @property
    def served_in_window(self) -> int:
        return int(np.sum(self.done_s <= self.seconds))

    @property
    def served_in_span(self) -> int:
        return int(np.sum(self.done_s <= self.span_s))


class _Recorder:
    def __init__(self):
        self.due, self.done, self.idx, self.late = [], [], [], []
        self.rid_slot: dict = {}
        self.answers: dict = {}
        self.waves = self.rows = 0

    def submitted(self, rid: int, idx: int, due: float, now: float):
        self.rid_slot[rid] = len(self.due)
        self.due.append(due)
        self.done.append(math.nan)
        self.idx.append(idx)
        self.late.append(now - due)

    def completed(self, done: list, t: float):
        for c in done:
            slot = self.rid_slot.pop(c.rid)
            self.done[slot] = t
            self.answers[slot] = (c.v_q, c.pred)
        if done:
            self.waves += 1
            self.rows += done[0].bucket


def drive(cell: Cell, seconds: float, *, annotate=None,
          clock=time.perf_counter, rate_per_s: float | None = None
          ) -> Window:
    """Run the cell's traffic for `seconds`, then serve what is queued.
    `rate_per_s` overrides an open mix's rate (the knee sweep)."""
    annotate = annotate or no_span
    eng, mix = cell.engine, cell.mix
    rec = _Recorder()
    loop = _closed if mix["arrival"] == "closed" else _open
    with annotate("bench.window"):
        t0 = clock()
        window_s, waves, rows, backlog = loop(cell, rec, seconds, t0, clock,
                                              annotate, rate_per_s)
        span_s = clock() - t0
    span_waves, span_rows = rec.waves, rec.rows
    # what is still queued was due inside the window: serve and check it
    while eng.queue_depth() and clock() - t0 < span_s + DRAIN_LIMIT_S:
        rec.completed(eng.step(), clock() - t0)
    n = len(rec.due)
    J, O = cell.model.out_shape(cell.config["geometry"])
    v_q = np.zeros((n, J, O), np.int8)
    pred = np.full(n, -1, np.int64)
    for slot, (v, p) in rec.answers.items():
        v_q[slot], pred[slot] = v, p
    return Window(seconds=window_s, due_s=np.asarray(rec.due),
                  done_s=np.asarray(rec.done),
                  pool_idx=np.asarray(rec.idx, np.int64), v_q=v_q,
                  pred=pred, late_s=np.asarray(rec.late), waves=waves,
                  rows=rows, backlog_at_close=backlog, span_s=span_s,
                  span_waves=span_waves, span_rows=span_rows)


def _closed(cell, rec, seconds, t0, clock, annotate, _rate):
    """Keep `depth_waves` full waves queued; the window ends with the
    last wave that completes before `seconds` have passed."""
    eng, mid = cell.engine, cell.model_id
    depth = cell.mix["depth_waves"] * eng.max_bucket
    order = cell.rngs["order"]
    perm, pos = order.permutation(len(cell.pool)), 0
    t_last, waves, rows = 0.0, 0, 0
    while True:
        with annotate("bench.submit"):
            now = clock() - t0
            while eng.queue_depth() < depth:
                if pos == len(perm):
                    perm, pos = order.permutation(len(cell.pool)), 0
                idx = int(perm[pos])
                pos += 1
                rec.submitted(eng.submit(cell.pool[idx], mid), idx, now,
                              now)
        with annotate("bench.step"):
            done = eng.step()
        t = clock() - t0
        rec.completed(done, t)
        if t > seconds:         # the wave that crosses the close is
            break               # served and checked, not counted
        t_last, waves, rows = t, waves + 1, rows + done[0].bucket
    return t_last, waves, rows, eng.queue_depth()


def _open(cell, rec, seconds, t0, clock, annotate, rate_per_s):
    """Submit each request when it falls due, serve waves in between;
    the loop ends when every request due in the window is served (or
    DRAIN_LIMIT_S after the close)."""
    eng, mid = cell.engine, cell.model_id
    rate = cell.mix["rate_per_s"] if rate_per_s is None else rate_per_s
    due = tr.due_times(rate, seconds, cell.rngs["arrivals"])
    order = tr.request_order(len(due), len(cell.pool), cell.rngs["order"])
    i, n, backlog, waves, rows = 0, len(due), None, 0, 0
    while True:
        now = clock() - t0
        if i < n and due[i] <= now:
            with annotate("bench.submit"):
                while i < n and due[i] <= now:
                    idx = int(order[i])
                    rec.submitted(eng.submit(cell.pool[idx], mid), idx,
                                  float(due[i]), now)
                    i += 1
        if backlog is None and now >= seconds:
            backlog = eng.queue_depth()
        if eng.queue_depth():
            with annotate("bench.step"):
                done = eng.step()
            t = clock() - t0
            rec.completed(done, t)
            if t <= seconds:
                waves, rows = waves + 1, rows + done[0].bucket
        elif i < n:
            gap = due[i] - (clock() - t0)
            if gap > 2e-4:
                time.sleep(min(gap - 1e-4, 1e-3))
        else:
            break
        if now > seconds + DRAIN_LIMIT_S:
            break
    return float(seconds), waves, rows, backlog or 0


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
LIMITS = {"vq_mismatch": 0, "pred_mismatch": 0, "never_served": 0}


def check(cell: Cell, win: Window) -> dict:
    """Compare every answer served with the int8 reference over the
    images served; returns the numbers compared and the requests that
    failed."""
    served = ~np.isnan(win.done_s)
    uniq, row = np.unique(win.pool_idx[served], return_inverse=True)
    v_ref, p_ref = cell.model.reference(cell.config["geometry"], cell.params,
                                        cell.calib, cell.pool[uniq], 8)
    bad_v = (win.v_q[served].astype(np.int64) != v_ref[row])
    bad_p = win.pred[served] != p_ref[row]
    numbers = {"vq_mismatch": int(bad_v.sum()),
               "pred_mismatch": int(bad_p.sum()),
               "never_served": int((~served).sum())}
    failed = int((~served).sum() + np.sum(bad_v.any(axis=(1, 2)) | bad_p))
    return {"numbers": numbers, "failed": failed,
            "checked": int(served.sum()), "images": int(len(uniq))}
