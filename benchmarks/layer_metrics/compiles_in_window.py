"""Backend compile requests (cache hit or miss) inside the timed
window. Must be 0: every program the window calls ran in the warm-up."""


def read(ctx):
    return float(ctx["window"]["compiles"])
