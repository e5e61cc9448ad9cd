"""images_per_s: images served inside the window over the window's
length (host clock), the wave that crosses the close left out of both."""


def read(ctx):
    w = ctx.window
    return w.served_in_window / w.seconds if w.seconds > 0 else None
