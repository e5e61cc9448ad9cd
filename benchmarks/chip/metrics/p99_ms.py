"""p99_ms: 99th percentile, over every request due in the window, of its
completion time minus its due time; one never served counts as
infinitely late (and then there is no finite value to report)."""
from benchmarks.chip import traffic


def read(ctx):
    w = ctx.window
    return 1e3 * traffic.percentile(traffic.latencies_s(w.due_s, w.done_s),
                                    99)
