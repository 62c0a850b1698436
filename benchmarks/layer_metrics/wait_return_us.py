"""How long the answer takes back: for each `pony:wait` span of the
traced part at whose end the device is idle, the span's end - the end
of the last device operation that ended inside it. The median, in
microseconds. See `launch_lead_us.py`."""

from benchmarks.layer_metrics.launch_lead_us import median_us


def read(ctx):
    return median_us("back")
