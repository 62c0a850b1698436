"""Peak device memory on the fullest chip after the window
(`device.memory_stats()["peak_bytes_in_use"]`). A guard: the world's
size is part of the configuration."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return float(peak) if peak else None
