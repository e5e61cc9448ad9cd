"""routing_kernel_roofline: the least time all routing iterations need
for the rows computed in the traced window (per image (2r - 1) J I O
MACs; u_hat read once as int8, v written), at the chip's int8 peak or
HBM bandwidth, over the fused routing kernel's device time (%)."""
from benchmarks.chip import peaks, work


def read(ctx):
    r, w = ctx.reduction, ctx.window
    t = (r or {}).get("class_s", {}).get("routing_kernel")
    if not t:
        return None
    ops, nbytes = work.work(ctx.model.layers(ctx.geom), {"routing"},
                            w.span_rows, w.span_waves)
    least, _ = peaks.least_time_s(ops, nbytes, ctx.peaks["int8_ops"],
                                  ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
