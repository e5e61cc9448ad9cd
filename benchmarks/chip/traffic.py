"""The one traffic generator.  A mix is a JSON file of parameters under
`traffic/`, found by the name a cell gives:

  arrival     "closed": a backlog; the client keeps `depth_waves` times
              the engine's largest bucket of single-image requests
              queued, so every wave is full.
              "open": independent clients; requests are due at the times
              of a Poisson process of `rate_per_s`, whatever the server
              does, and each is timed from when it was due.
  pool        distinct request images, made from the seed in set-up.
  warm        "max_bucket" or "all": which of the engine's buckets set-up
              compiles and runs before the window.

Every seed gets the same amount of work: the same pool size and, in an
open mix, exactly round(rate_per_s * seconds) requests, whose gaps are
exponential and scaled so the last one falls at the window's end (a
Poisson process given its count).  The seed changes which images and
which gaps, not how many.
"""
from __future__ import annotations

import json
import math
import pathlib

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
ARRIVALS = ("closed", "open")


def load(name: str, directory=TRAFFIC_DIR) -> dict:
    mix = json.loads((pathlib.Path(directory) / f"{name}.json").read_text())
    if mix.get("arrival") not in ARRIVALS:
        raise ValueError(f"traffic {name!r}: arrival must be one of "
                         f"{ARRIVALS}, got {mix.get('arrival')!r}")
    if mix.get("warm") not in ("max_bucket", "all"):
        raise ValueError(f"traffic {name!r}: warm must be 'max_bucket' or "
                         f"'all', got {mix.get('warm')!r}")
    return mix


def streams(seed: int) -> dict:
    """Independent generators for each thing drawn from one seed."""
    names = ("weights", "calib", "pool", "order", "arrivals")
    children = np.random.SeedSequence(int(seed)).spawn(len(names))
    return {n: np.random.default_rng(c) for n, c in zip(names, children)}


def due_times(rate_per_s: float, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    """Due times in [0, seconds] of round(rate * seconds) requests:
    exponential gaps scaled so that they sum to `seconds`."""
    n = int(round(rate_per_s * seconds))
    if n < 1:
        raise ValueError(f"rate {rate_per_s}/s gives no request in "
                         f"{seconds} s")
    gaps = rng.exponential(1.0, n)
    return np.cumsum(gaps) * (seconds / gaps.sum())


def request_order(n: int, pool: int, rng: np.random.Generator) -> np.ndarray:
    """Pool index of each of n requests: the pool in a seeded order,
    again in a fresh order each time it runs out."""
    reps = -(-n // pool)
    return np.concatenate([rng.permutation(pool) for _ in range(reps)])[:n]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.inf
    k = max(int(math.ceil(q / 100.0 * v.size)) - 1, 0)
    return float(v[k])


def latencies_s(due_s, done_s) -> np.ndarray:
    """Completion time minus due time per request; a request that never
    completed (done = nan) is a miss, with infinite latency."""
    due, done = np.asarray(due_s, np.float64), np.asarray(done_s, np.float64)
    return np.where(np.isnan(done), math.inf, done - due)
