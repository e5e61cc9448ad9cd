"""transfer_ms.tput: median per wave of `serve.transfer`, the engine's
host->device copy of the padded batch (ms)."""
from benchmarks.chip import spans


def read(ctx):
    return spans.median_ms(ctx, "serve.transfer")
