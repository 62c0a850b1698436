"""Wall clock of the timed window / ticks retired in it (`steps_run`)."""


def read(ctx):
    w = ctx["window"]
    return 1e3 * w["wall_s"] / w["ticks"] if w["ticks"] else None
