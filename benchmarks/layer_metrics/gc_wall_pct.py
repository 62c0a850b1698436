"""Share of the timed window's wall clock the run loop spent in its
`gc` phase (`run_loop_stats()["phase_s"]["gc"]`, always on: the host's
roots, the pass on the device, the answer). None where the mode does
not follow the collector."""


def read(ctx):
    w = ctx["window"]
    if "gc_s" not in w or not w["wall_s"]:
        return None
    return 100.0 * w["gc_s"] / w["wall_s"]
