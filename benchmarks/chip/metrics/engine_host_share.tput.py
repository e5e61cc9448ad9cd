"""engine_host_share.tput: share of the window in which the host runs
engine code and is not waiting on the device: the `serve.enqueue` spans
of the window's waves' requests, plus each `serve.wave` less its
`serve.wait`, over the window's length on the same clock (%)."""
from benchmarks.chip import spans


def read(ctx):
    host_s, span_s = spans.engine_host_s(ctx), ctx.window.span_s
    if host_s is None or not span_s > 0:
        return None
    return 100.0 * host_s / span_s
