"""From a profiler trace to numbers: device busy time, the operations
that took it, and the idle gaps by what the host was doing.

Two steps, so that the arithmetic can be checked without a chip:

  load(path)    an `.xplane.pb` written by `jax.profiler` -> plain data:
                {"planes": [{"name", "lines": [{"name", "events":
                [[name, start_ns, duration_ns], ...]}]}]}
  reduce(data)  plain data -> {"busy_s", "span_s", "devices",
                "device_ops", "idle_gaps"}

`tests/data/recorded_trace.json.gz` is load()'s output for a short run
on the v5e, cut down; `tests/test_reduce_trace.py` runs reduce() on it.

What counts as busy: the union of the intervals of every event on a
device plane's operation line (`XLA Ops`), averaged over the device
planes that have such events. An operation that contains others (a
`while` around its body) adds nothing to the union; in the list of
operations it is charged only its self time.

The span is from the start of the first host annotation named in
`annotations` to the end of the last: the part of the trace in which the
benchmark was driving the program. Device events outside it are cut to
it. A device idle gap is labelled by the annotation that covers its
middle (`unannotated` where none does).
"""

from __future__ import annotations

import glob
import gzip
import json
import os

DEVICE_PLANE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:CPU"
ANNOTATIONS = ("segment", "between-segments")
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    """The newest .xplane.pb under a `jax.profiler` trace directory."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def load(path: str, keep_host=ANNOTATIONS) -> dict:
    """Read an .xplane.pb into plain data. Device planes are kept whole;
    of the host planes only the events named in `keep_host` are kept
    (a run loop writes hundreds of thousands of others)."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        if not device and not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name in keep_host]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_recorded(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def short_name(name: str) -> str:
    """XLA names a device operation by its whole HLO line, `%fusion.20 =
    s32[64,2]{0,1:T(2,128)} fusion(...)`: keep the name and the result's
    shape, `fusion.20 s32[64,2]` (`(tuple)` for a tuple)."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = "(tuple)" if rhs.startswith("(") else rhs.split("{")[0].split(" ")[0]
    return f"{lhs.lstrip('%')} {shape}"[:80]


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _self_times(events):
    """{name: self nanoseconds} for the events of one line: an event's
    duration less that of the events nested inside it."""
    out: dict[str, float] = {}
    stack = []          # [name, end, child nanoseconds, duration]
    def close(item):
        name, _end, child, dur = item
        out[name] = out.get(name, 0.0) + max(0.0, dur - child)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] += dur
        stack.append([name, start + dur, 0.0, dur])
    while stack:
        close(stack.pop())
    return out


def _clip(events, lo, hi):
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def reduce(data: dict, annotations=ANNOTATIONS) -> dict | None:
    """None where the trace has no device operations (a CPU run)."""
    notes = []          # [name, start, end] host annotations
    device_lines = []   # the events of each device plane's operation line
    for plane in data["planes"]:
        device = plane["name"].startswith(DEVICE_PLANE_PREFIX)
        for line in plane["lines"]:
            if not device:
                notes += [[n, s, s + d] for n, s, d in line["events"]
                          if n in annotations]
            elif line["name"] == OP_LINE and line["events"]:
                device_lines.append(line["events"])
    if not device_lines:
        return None
    if notes:
        lo = min(n[1] for n in notes)
        hi = max(n[2] for n in notes)
    else:
        lo = min(s for evs in device_lines for _, s, _ in evs)
        hi = max(s + d for evs in device_lines for _, s, d in evs)
    notes.sort(key=lambda n: n[1])

    busy_ns, ops, gaps = [], {}, {}
    for events in device_lines:
        events = _clip(events, lo, hi)
        merged = _union([s, s + d] for _, s, d in events)
        busy_ns.append(sum(e - s for s, e in merged))
        for name, ns in _self_times(events).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + ns
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            label = next((n for n, s, e in notes if s <= mid < e),
                         "unannotated")
            gaps[label] = gaps.get(label, 0.0) + (g1 - g0)
    k = len(device_lines)

    def top(table):
        return [[name, ns / k / 1e9] for name, ns in
                sorted(table.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": sum(busy_ns) / k / 1e9, "span_s": (hi - lo) / 1e9,
            "devices": k, "device_ops": top(ops), "idle_gaps": top(gaps)}


def reduce_dir(trace_dir: str) -> dict | None:
    path = find_xplane(trace_dir)
    return reduce(load(path)) if path else None
