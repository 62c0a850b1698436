"""90th percentile over the window's laps of lap time / hops. A
host-clock tail: reported per layer, never end to end. Only a lap-mode
window has laps."""

import numpy as np


def read(ctx):
    w = ctx["window"]
    if "lap_s" not in w:
        return None
    return float(np.percentile(np.asarray(w["lap_s"]) / w["hops_per_lap"],
                               90) * 1e6)
