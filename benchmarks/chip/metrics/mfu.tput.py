"""mfu.tput: the whole wave's share of the chip's int8 peak: 2 x int8
MACs per image (every layer the model module lists, work.py) x images
served in the traced window / its length / peak (%)."""
from benchmarks.chip import work


def read(ctx):
    r, w = ctx.reduction, ctx.window
    if r is None or not r["window_s"]:
        return None
    ops = 2 * work.macs_per_image(ctx.model.layers(ctx.geom)) \
        * w.served_in_span
    return 100.0 * ops / r["window_s"] / ctx.peaks["int8_ops"]
