"""wait_ms.tput: median per wave of `serve.wait`, the host blocked on
the device until the wave's outputs are ready: the device time per wave
as the host sees it (compare busy / waves from the device trace) (ms)."""
from benchmarks.chip import spans


def read(ctx):
    return spans.median_ms(ctx, "serve.wait")
