"""Share of the window's wall clock beyond what its segments would have
taken at their median time: what the median rate (`msgs_per_s`) hides.
About 0 when every segment takes as long as the next; a program that
stalls periodically, or a host that stalled once, shows here. Only a
throughput-mode window has segments."""

import numpy as np


def read(ctx):
    w = ctx["window"]
    if "segment_dispatched" not in w:
        return None
    at_median = len(w["segment_s"]) * float(np.median(w["segment_s"]))
    return 100.0 * (w["wall_s"] - at_median) / w["wall_s"]
