"""Chip benchmark of int8 CapsNet serving on a TPU (see BENCHMARK.json).

`run.py` is the entry point.  Everything the benchmark measures against
lives here and imports nothing of the program: traffic generation
(`traffic.py`, `images.py`), the plain reference and the comparison that
decides `correct` (`reference.py`), the work counts (`work.py`), the
table of peaks (`peaks.py`) and the reduction from a profiler trace to
metrics (`trace.py`, `metrics/`).  From the program it takes only the
system under test: `CapsPipeline` -> `ModelRegistry` -> `CapsServeEngine`.
"""
