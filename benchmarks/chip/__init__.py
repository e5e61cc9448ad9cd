"""Chip benchmark of int8 capsule-network serving on a TPU (see
BENCHMARK.json).

`run.py` is the entry point.  Everything the benchmark measures against
lives here and imports nothing of the program: traffic generation
(`traffic.py` with the mixes in `traffic/`, `images.py`), the model
modules (`models/<name>.py`, one per model family, named by a
configuration's `"model"`: its weights, its pipeline, its plain
reference and its work counts), the reference's integer primitives and
the comparison that decides `correct` (`reference.py`, `harness.py`),
the work arithmetic (`work.py`), the table of peaks (`peaks.py`) and the
reduction from a profiler trace to metrics (`trace.py`, the readers in
`metrics/`).  From the program it takes only the system under test:
`CapsPipeline` -> `ModelRegistry` -> `CapsServeEngine`.
"""
