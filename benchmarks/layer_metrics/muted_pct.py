"""Share of the producers that are muted after the window's last tick
(`state.muted`, read outside the segments' clock)."""


def read(ctx):
    w = ctx["window"]
    if "muted_producers" not in w:
        return None
    return 100.0 * w["muted_producers"] / w["producers"]
