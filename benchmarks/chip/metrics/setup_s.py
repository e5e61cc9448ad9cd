"""setup_s: process start to the first instant of the window (import,
device, weights, PTQ, image pool, the cell's buckets compiled and run)."""


def read(ctx):
    return ctx.setup_s
