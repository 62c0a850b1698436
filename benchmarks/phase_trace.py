"""From a profiler trace to per-phase numbers: device time by the
program's named scopes, and the run loop's own spans.

The program names its tick's phases with `jax.named_scope` under the
prefix `pony/` (`ponyc_tpu/runtime/state.py`, STEP_SCOPES) and its run
loop's phases with profiler spans `pony:<phase>` carrying `window` and
`ticks` (`ponyc_tpu/runtime/runtime.py`, RUN_PHASES). Both land in the
`.xplane.pb` a `--trace 1` run writes. A program that has neither (the
parent of the PR that added them) gives a trace in which every device
operation is unscoped and there are no spans: the readers built on this
file then return None and the result line leaves their metrics out.

Two steps, as in `reduce_trace.py`, so that the arithmetic can be
checked without a chip:

  load(path)            an `.xplane.pb` -> plain data:
                        {"device": [[[name, start_ns, duration_ns,
                        op_name], ...] per device plane],
                        "host": [[name, start_ns, duration_ns, window,
                        ticks], ...]}
  reduce(data, ticks)   plain data -> the numbers (see `reduce`)

`tests/data/recorded_phases.json.gz` is load()'s output for a short run
on the v5e, cut down; `tests/test_phase_trace.py` runs reduce() on it.

Rules of the reduction:

- An operation's scope is what follows the LAST `pony` segment of its
  HLO `op_name`, up to the first segment that JAX itself wrote (`cond`,
  `branch_1_fun`, `while`, `body`, `jit(...)`, ...) and without the
  final segment, which names the primitive: `jit(multi)/while/body/
  pony/delivery/cond/branch_1_fun/pony/delivery/rebuild/gather` is
  `delivery/rebuild`. The program writes every scope absolute for this
  reason. A scope may therefore not be named like a JAX segment.
- A fusion is charged to the scope of the `op_name` the trace gives it,
  which is its root's. Time is self time, by `reduce_trace`'s nesting
  rule: a `while` is charged what its body does not cover.
- Device time under no `pony/` scope is reported as `unscoped`, never
  dropped, so the phases sum to the device-busy time.
- The traced span, busy time and idle gaps are `reduce_trace`'s: from the
  first `segment` / `between-segments` annotation to the last.
- Device idle is shared out by overlap with the run loop's spans, each
  moment to the innermost span that covers it (the gap between two
  windows crosses five spans, so the middle of a gap would name one of
  them by chance). Idle under `pony:wait` (the host blocked on the
  device) is the device's own: the gaps between its operations and the
  way back to the host. Idle under no span is outside `run()`: the
  caller's.
"""

from __future__ import annotations

import bisect
import os
import re

from benchmarks import reduce_trace
from benchmarks.reduce_trace import (ANNOTATIONS, DEVICE_PLANE_PREFIX,
                                     HOST_PLANE_PREFIX, OP_LINE, _union,
                                     short_name)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = os.path.join(ROOT, ".cache", "benchmarks", "trace")
SCOPE_ROOT = "pony"
SPAN_PREFIX = "pony:"
UNSCOPED = "unscoped"
# The stat of a device operation's event METADATA that carries its HLO
# op_name on the v5e, as `<op_name>:<op_type>` (PHASES.md, "Where the
# scope path was found"). `jax.profiler.ProfileData` gives an event's own stats only,
# so the metadata is read from the file's bytes (`_op_names`).
OP_NAME_STAT = "tf_op"
# Segments of an op_name that JAX writes itself: a scope path ends there.
_JAX_SEGMENT = re.compile(
    r"^(cond|while|body|scan|branch_\d+_fun|cond_fun|body_fun|pjit|"
    r"closed_call|core_call|checkpoint|remat|shard_map|custom_jvp_call|"
    r"custom_vjp_call|.*\(.*)$")
HOST_WORK = ("host-work", "outbox", "pollers", "gc", "checkpoint",
             "analysis")
TOP = 3


def scope_of(op_name: str | None) -> str | None:
    """`delivery/rebuild` for an op_name under `pony/delivery/rebuild`,
    None for one under no `pony` scope."""
    if not op_name:
        return None
    segments = op_name.split("/")
    if SCOPE_ROOT not in segments:
        return None
    last = len(segments) - 1 - segments[::-1].index(SCOPE_ROOT)
    path = []
    for seg in segments[last + 1:-1]:
        if _JAX_SEGMENT.match(seg):
            break
        path.append(seg)
    return "/".join(path) or None


def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) for each field of one protobuf message:
    an int for a varint, a memoryview for anything with a length."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _op_names(path: str) -> dict:
    """{device plane name: {event name: op_name}} from the XSpace's
    bytes: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
    .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name
    = 2, .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .str_value = 5. Lines are skipped whole, so this costs the number
    of distinct operations, not of events."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, metas, stat_id = None, [], None
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                metas.append(value)
            elif number == 5:
                entry = dict(_fields(value))
                stat = dict(_fields(entry[2]))
                if bytes(stat.get(2, b"")).decode() == OP_NAME_STAT:
                    stat_id = entry[1]
        if not name or not name.startswith(DEVICE_PLANE_PREFIX) \
                or stat_id is None:
            continue
        names = out.setdefault(name, {})
        for entry in metas:
            event_name = op_name = None
            for number, value in _fields(dict(_fields(entry))[2]):
                if number == 2:
                    event_name = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) == stat_id and 5 in stat:
                        op_name = bytes(stat[5]).decode()
            if event_name and op_name:
                names[event_name] = op_name.rsplit(":", 1)[0]
    return out


def load(path: str) -> dict:
    """Read an .xplane.pb into plain data (see the module docstring)."""
    from jax.profiler import ProfileData
    op_names = _op_names(path)
    device, host = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            names = op_names.get(plane.name, {})
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                events = [[e.name, float(e.start_ns), float(e.duration_ns),
                           names.get(e.name)] for e in line.events]
                if events:
                    device.append(events)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ANNOTATIONS:
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), None, None])
                    elif e.name.startswith(SPAN_PREFIX):
                        stats = dict(e.stats)
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns),
                                     _int(stats.get("window")),
                                     _int(stats.get("ticks"))])
    return {"device": device, "host": host}


def _int(value):
    try:
        return int(value)
    except (TypeError, ValueError):
        return None


def _self_events(events):
    """[(name, op_name, self nanoseconds, is a leaf)] for the events of
    one line, by `reduce_trace._self_times`' nesting rule, one entry an
    event."""
    out = []
    stack = []      # [name, end, child nanoseconds, duration, op_name, kids]

    def close(item):
        name, _end, child, dur, op_name, kids = item
        out.append((name, op_name, max(0.0, dur - child), kids == 0))
    for name, start, dur, op_name in sorted(events,
                                            key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] += dur
            stack[-1][5] += 1
        stack.append([name, start + dur, 0.0, dur, op_name, 0])
    while stack:
        close(stack.pop())
    return out


def _self_intervals(spans):
    """[(name, start, end)], disjoint: the stretches of time of which
    each (properly nested) span is the innermost one."""
    out, stack = [], []     # stack: [name, end, covered up to]

    def cover(upto):
        name, _end, since = stack[-1]
        if upto > since:
            out.append((name, since, upto))
            stack[-1][2] = upto
    for name, start, dur in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][1] <= start:
            cover(stack[-1][1])
            end = stack.pop()[1]
            if stack:
                stack[-1][2] = max(stack[-1][2], end)
        if stack:
            cover(start)
        stack.append([name, start + dur, start])
    while stack:
        cover(stack[-1][1])
        end = stack.pop()[1]
        if stack:
            stack[-1][2] = max(stack[-1][2], end)
    return out


def _busy_within(merged, starts, lo, hi):
    """Nanoseconds of the merged busy intervals (`starts`: where each
    begins) that lie in [lo, hi)."""
    total = 0.0
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(merged) and merged[i][0] < hi:
        total += max(0.0, min(merged[i][1], hi) - max(merged[i][0], lo))
        i += 1
    return total


def reduce(data: dict, ticks: int | None) -> dict:
    """Plain data -> {"ticks", "span_s", "busy_s", "phases": {scope:
    {"s", "ops", "top": [[short name, s], ...]}} (with `unscoped`),
    "leaf_ops", "plan_sorts", "scoped": any operation had a scope,
    "spans": {span name: {"s", "self_s", "n"}}, "windows": retired
    windows the spans show, "window_host_s", "idle_s": {span name or
    `outside`: s}}. Seconds and counts are totals over the traced span,
    averaged over the device planes; the readers divide."""
    notes = [h for h in data["host"] if h[0] in ANNOTATIONS]
    spans = sorted((h for h in data["host"] if h[0].startswith(SPAN_PREFIX)),
                   key=lambda h: h[1])
    lines = data["device"]
    if notes:
        lo = min(n[1] for n in notes)
        hi = max(n[1] + n[2] for n in notes)
    elif lines:
        lo = min(e[1] for evs in lines for e in evs)
        hi = max(e[1] + e[2] for evs in lines for e in evs)
    else:
        lo = hi = 0.0
    spans = [s for s in spans if s[1] + s[2] > lo and s[1] < hi] \
        if hi > lo else spans
    k = max(1, len(lines))
    owned = _self_intervals([[s[0], s[1], s[2]] for s in spans])

    phases: dict[str, dict] = {}
    busy_ns = leaf_ops = plan_sorts = 0.0
    idle: dict[str, float] = {}
    scoped = False
    for events in lines:
        clipped = []
        for name, start, dur, op_name in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                clipped.append([name, s, e - s, op_name])
        merged = _union([e[1], e[1] + e[2]] for e in clipped)
        busy_ns += sum(e - s for s, e in merged)
        for name, op_name, self_ns, leaf in _self_events(clipped):
            scope = scope_of(op_name)
            scoped = scoped or scope is not None
            short = short_name(name)
            rec = phases.setdefault(scope or UNSCOPED,
                                    {"s": 0.0, "ops": 0.0, "by_op": {}})
            rec["s"] += self_ns / 1e9
            if leaf and self_ns > 0:
                rec["ops"] += 1
                leaf_ops += 1
            rec["by_op"][short] = rec["by_op"].get(short, 0.0) + self_ns / 1e9
            if scope == "delivery/plan" and short.startswith("sort"):
                plan_sorts += 1
        outside = (hi - lo) - sum(e - s for s, e in merged)
        starts = [m[0] for m in merged]
        for name, a, b in owned:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                gap = (b - a) - _busy_within(merged, starts, a, b)
                idle[name] = idle.get(name, 0.0) + gap / 1e9
                outside -= gap
        idle["outside"] = idle.get("outside", 0.0) + max(0.0, outside) / 1e9
    for rec in phases.values():
        by_op = rec.pop("by_op")
        rec["s"] /= k
        rec["ops"] /= k
        rec["top"] = [[name, s / k] for name, s in
                      sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]]

    by_span: dict[str, dict] = {}
    for name, start, dur, _window, _ticks in spans:
        rec = by_span.setdefault(name, {"s": 0.0, "self_s": 0.0, "n": 0})
        rec["s"] += dur / 1e9
        rec["n"] += 1
    for name, a, b in owned:
        by_span[name]["self_s"] += (b - a) / 1e9
    retired = {s[3] for s in spans if s[0] == SPAN_PREFIX + "host-work"
               and s[4]}
    host_s = sum(by_span.get(SPAN_PREFIX + n, {}).get("self_s", 0.0)
                 for n in ("dispatching",) + HOST_WORK)
    return {"ticks": ticks, "span_s": (hi - lo) / 1e9,
            "busy_s": busy_ns / k / 1e9, "devices": len(lines),
            "phases": phases, "leaf_ops": leaf_ops / k,
            "plan_sorts": plan_sorts / k, "scoped": scoped,
            "spans": by_span, "windows": len(retired),
            "window_host_s": host_s,
            "idle_s": {name: s / k for name, s in idle.items()}}


def under(reduced: dict, *prefixes: str) -> float | None:
    """Device seconds in the traced span under the scopes that are, or
    lie below, one of `prefixes`; None where the trace has no scopes."""
    if not reduced or not reduced["scoped"]:
        return None
    return sum(rec["s"] for scope, rec in reduced["phases"].items()
               if any(scope == p or scope.startswith(p + "/")
                      for p in prefixes))


def table(reduced: dict) -> str:
    """For people: phase -> ms per tick, operations per tick, and its
    three largest operations, so that `fusion.20` and its phase stand
    side by side; then the run loop's spans."""
    ticks = reduced["ticks"] or 1
    lines = [f"phases over {reduced['ticks']} traced ticks "
             f"(device busy {1e3 * reduced['busy_s'] / ticks:.4f} ms a tick):",
             f"  {'phase':<20}{'ms/tick':>12}{'ops/tick':>10}  largest"]
    total = 0.0
    for scope, rec in sorted(reduced["phases"].items(),
                             key=lambda kv: -kv[1]["s"]):
        total += rec["s"]
        top = ", ".join(f"{n} {1e3 * s / ticks:.3f}" for n, s in rec["top"])
        lines.append(f"  {scope:<20}{1e3 * rec['s'] / ticks:>12.4f}"
                     f"{rec['ops'] / ticks:>10.1f}  {top}")
    lines.append(f"  {'sum':<20}{1e3 * total / ticks:>12.4f}"
                 f"{reduced['leaf_ops'] / ticks:>10.1f}")
    if reduced["spans"]:
        lines.append(f"run-loop spans ({reduced['windows']} retired windows):")
        for name, rec in sorted(reduced["spans"].items()):
            lines.append(f"  {name:<20}{1e3 * rec['self_s']:>12.4f} ms self"
                         f"{rec['n']:>8} spans")
        idle = ", ".join(f"{n} {1e3 * s:.3f}" for n, s in
                         sorted(reduced["idle_s"].items(),
                                key=lambda kv: -kv[1]))
        lines.append(f"  device idle by span, ms: {idle}")
    return "\n".join(lines)


_cache: dict = {}


def of_run(ctx: dict) -> dict | None:
    """The reduction of this run's trace, parsed once a process and
    printed once, for every reader. None where no trace was written."""
    path = reduce_trace.find_xplane(TRACE_DIR)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        ticks = ctx["trace"]["ticks"] if ctx.get("trace") else None
        _cache[key] = reduce(load(path), ticks)
        if _cache[key]["phases"] or _cache[key]["spans"]:
            print(table(_cache[key]), flush=True)
    return _cache[key]


def per_tick(ctx: dict, *prefixes: str, scale: float) -> float | None:
    """`scale` x device seconds under `prefixes` a traced tick."""
    reduced = of_run(ctx)
    if not reduced or not reduced["ticks"]:
        return None
    seconds = under(reduced, *prefixes)
    return None if seconds is None else scale * seconds / reduced["ticks"]
