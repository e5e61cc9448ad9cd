"""local_routing_roofline: the least time the local (3D) routing needs
for the rows computed in the traced window (the model module's layer
"cell4.skip3d.routing": per image, over its groups, (2r - 1) J I O MACs;
u_hat read once as int8, v written), at the chip's int8 peak or HBM
bandwidth, over the device time of the local routing kernel
(`routing3d_q7_pallas` in `kernel_s`) (%).  A program without that
kernel reads nothing."""
from benchmarks.chip import peaks, work

KERNEL = "routing3d_q7_pallas"
LAYER = "cell4.skip3d.routing"


def read(ctx):
    r, w = ctx.reduction, ctx.window
    t = (r or {}).get("kernel_s", {}).get(KERNEL)
    if not t:
        return None
    layers = [l for l in ctx.model.layers(ctx.geom) if l["name"] == LAYER]
    ops, nbytes = work.work(layers, {"routing"}, w.span_rows, w.span_waves)
    least, _ = peaks.least_time_s(ops, nbytes, ctx.peaks["int8_ops"],
                                  ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
