"""From a profiler trace of a mesh to what only a mesh has: the time of
the collective operations, the part of it in which the device runs
nothing else, and how unevenly the devices are loaded.

Two steps, as in `phase_trace.py`, whose loader this file uses:

  phase_trace.load(path)   an `.xplane.pb` -> {"device": [[[name,
                           start_ns, duration_ns, op_name], ...] per
                           device plane], "host": [...]}
  reduce(data)             plain data -> the numbers (see `reduce`)

`tests/test_mesh_cell.py` runs reduce() on a small synthetic trace of
four planes with one collective.

Rules of the reduction:

- A collective is a device operation whose HLO opcode is one of
  COLLECTIVES (`%all_to_all.31 = s32[..] all-to-all(...)`, `%psum.12 =
  s32[19] all-reduce(...)`), with an asynchronous pair's `-start` /
  `-done` taken off (`collective_of`). A fusion that XLA built around a
  collective has the opcode `fusion` and is not found: the reader then
  under-counts, and `PERF.md` says what the trace showed.
- A synchronous collective occupies the device's operation line for its
  whole duration. An asynchronous one is in flight from its `-start`
  event's begin to its `-done` event's end (each `-start` is paired with
  the next `-done` of its kind on that plane); other operations may run
  in between.
- Collective time is the union of those intervals, clipped to the traced
  span; exposed time is the part of that union not covered by any OTHER
  leaf operation of the same plane (a `while` or a `conditional` that
  contains the collective is not another operation: only events that
  contain no event are leaves). Both are averaged over the device
  planes; the exposed share is exposed / collective.
- A device's busy time is `reduce_trace`'s union of its operation line.
  The skew is the busiest device's busy time / the mean of all - 1.
"""

from __future__ import annotations

import os
import re

from benchmarks import phase_trace, reduce_trace
from benchmarks.reduce_trace import ANNOTATIONS, _union

COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")
# An event is named by its whole HLO line, `%all_to_all.31 = s32[4,8]{..}
# all-to-all(%copy.2), channel_id=1, ...`: the instruction's NAME is the
# JAX primitive's (`all_to_all`, `psum`, `pmax`), its OPCODE the
# collective's. Layouts write their tilings in capitals (`T(2,128)`,
# `S(1)`), so the first lower-case word before a `(` is the opcode.
_OPCODE = re.compile(r"(?<![\w\-])([a-z][a-z0-9\-]*)\(")
# Where a trace gives the bare instruction name, the primitive's.
_BY_PRIMITIVE = {"all_to_all": "all-to-all", "psum": "all-reduce",
                 "pmax": "all-reduce", "pmin": "all-reduce",
                 "all_gather": "all-gather", "ppermute": "collective-permute",
                 "psum_scatter": "reduce-scatter"}


def collective_of(event_name: str):
    """(kind, part) for a collective's event — part is "start", "done"
    or None for a synchronous one — else None."""
    lhs, sep, rhs = event_name.partition(" = ")
    name = lhs.strip().lstrip("%").split(".")[0]
    found = _OPCODE.search(rhs) if sep else None
    op, part = (found.group(1) if found else name), None
    if op in ("async-start", "async-done"):     # a wrapped collective
        op, part = name, op[len("async-"):]
    for suffix in ("start", "done"):
        if op.endswith("-" + suffix):
            op, part = op[:-len(suffix) - 1], part or suffix
    op = _BY_PRIMITIVE.get(op, op)
    return (op, part) if op in COLLECTIVES else None


def _leaves(events):
    """The events of one line that contain no other event."""
    out, stack = [], []          # stack: [event, has a child]
    for ev in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0][1] + stack[-1][0][2] <= ev[1]:
            done, parent = stack.pop()
            if not parent:
                out.append(done)
        if stack:
            stack[-1][1] = True
        stack.append([ev, False])
    out += [ev for ev, parent in stack if not parent]
    return out


def _covered(intervals, by):
    """Nanoseconds of the merged `intervals` that `by` (merged) covers."""
    total, j = 0.0, 0
    for lo, hi in intervals:
        while j < len(by) and by[j][1] <= lo:
            j += 1
        k = j
        while k < len(by) and by[k][0] < hi:
            total += max(0.0, min(hi, by[k][1]) - max(lo, by[k][0]))
            k += 1
    return total


def reduce(data: dict) -> dict | None:
    """{"devices", "busy_s": [per plane], "collective_s", "exposed_s",
    "by_kind": {kind: s}} — seconds over the traced span, the last three
    averaged over the device planes. None where no device plane has
    operations."""
    lines = [evs for evs in data["device"] if evs]
    if not lines:
        return None
    notes = [h for h in data["host"] if h[0] in ANNOTATIONS]
    if notes:
        lo = min(n[1] for n in notes)
        hi = max(n[1] + n[2] for n in notes)
    else:
        lo = min(e[1] for evs in lines for e in evs)
        hi = max(e[1] + e[2] for evs in lines for e in evs)
    busy, coll_ns, exposed_ns, by_kind = [], 0.0, 0.0, {}
    for events in lines:
        clipped = []
        for ev in events:
            s, e = max(ev[1], lo), min(ev[1] + ev[2], hi)
            if e > s:
                clipped.append([ev[0], s, e - s])
        busy.append(sum(e - s for s, e in
                        _union([ev[1], ev[1] + ev[2]] for ev in clipped))
                    / 1e9)
        flights, others, open_starts = [], [], {}
        for name, start, dur in sorted(_leaves(clipped),
                                       key=lambda ev: ev[1]):
            found = collective_of(name)
            if found is None:
                others.append([start, start + dur])
                continue
            kind, part = found
            if part == "start":
                open_starts.setdefault(kind, []).append(start)
                continue
            begin = start
            if part == "done" and open_starts.get(kind):
                begin = open_starts[kind].pop(0)
            flights.append([begin, start + dur])
            by_kind[kind] = by_kind.get(kind, 0.0) + (start + dur - begin)
        for kind, starts in open_starts.items():     # cut by the span
            flights += [[s, hi] for s in starts]
        merged = _union(flights)
        coll = sum(e - s for s, e in merged)
        coll_ns += coll
        exposed_ns += coll - _covered(merged, _union(others))
    k = len(lines)
    return {"devices": k, "busy_s": busy, "collective_s": coll_ns / k / 1e9,
            "exposed_s": exposed_ns / k / 1e9,
            "by_kind": {kind: ns / k / 1e9 for kind, ns in by_kind.items()}}


_cache: dict = {}


def of_run(ctx: dict) -> dict | None:
    """The reduction of this run's trace, parsed once a process and
    printed once. None where no trace was written or no device ran."""
    if not ctx.get("trace"):
        return None
    path = reduce_trace.find_xplane(phase_trace.TRACE_DIR)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        out = _cache[key] = reduce(phase_trace.load(path))
        if out:
            ticks = ctx["trace"]["ticks"] or 1
            kinds = ", ".join(f"{kind} {1e3 * s / ticks:.3f}" for kind, s in
                              sorted(out["by_kind"].items()))
            print(f"mesh: {out['devices']} device planes, busy s "
                  f"{[round(b, 4) for b in out['busy_s']]}; collectives "
                  f"{1e3 * out['collective_s'] / ticks:.3f} ms a tick "
                  f"({kinds or 'none found'}), exposed "
                  f"{1e3 * out['exposed_s'] / ticks:.3f}", flush=True)
    return _cache[key]
