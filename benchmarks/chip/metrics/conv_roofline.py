"""conv_roofline: the least time the int8 conv stack (every conv and the
primary-caps conv; the model module's layers of kind "conv") needs for
the rows computed in the traced window, at the chip's int8 peak or HBM
bandwidth, over the device time of the ops that hold a convolution
(%)."""
from benchmarks.chip import peaks, work


def read(ctx):
    r, w = ctx.reduction, ctx.window
    t = (r or {}).get("class_s", {}).get("conv")
    if not t:
        return None
    ops, nbytes = work.work(ctx.model.layers(ctx.geom), {"conv"},
                            w.span_rows, w.span_waves)
    least, _ = peaks.least_time_s(ops, nbytes, ctx.peaks["int8_ops"],
                                  ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
