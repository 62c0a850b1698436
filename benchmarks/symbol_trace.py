"""From a profiler trace to per-phase numbers, named by the program's
own symbol table instead of by what the profiler lends an event.

`phase_trace.py` charges a device event to the `op_name` the trace
gives it. A fusion gets its ROOT's, so a fusion whose root has none (the
drain's `dynamic-update-slice`, a scatter an XLA pass re-made) and
everything the compiler made itself (copies, its moves into `S(1)`, the
prefix sum's `reduce-window`) read as `unscoped`; and the op_names are
keyed by event name, so two programs that number their fusions alike
lend each other names. Since PR 49 the program reads its own compiled
text (`Runtime.window_symbols()`, `ponyc_tpu/costs.py`): a row for every
instruction that can be a device event, with the phase it belongs to,
the rung of the ladder that found it (`own` / `inside` / `around` /
`none`) and, for a gather or a scatter, whether its table or output was
dealt `S(1)` (`s1`; `table_s1` the table's mark alone). This file joins `phase_trace.load()`'s device events to
those rows and reduces them exactly as `phase_trace.reduce` does, so
that its phases sum to the same `busy_s`. `SYMBOLS.md` has the rest.

  rows(symbols)             {program: [row]} -> {key: {program: row}}
  reduce(data, symbols, ticks)   plain data -> the numbers (see `reduce`)

The join: an event's name is its whole HLO line without the metadata,
so it holds the instruction's name AND its output shape; the key is
`reduce_trace.short_name`'s form of both (`fusion.20 s32[64,2]`). A key
that two programs have is the collector's inside a `pony:gc` span and
the window's outside. An event no row matches, and one whose row the
ladder left on `none`, is `unnamed`, never dropped.
"""

from __future__ import annotations

import os

from benchmarks import phase_trace, reduce_trace
from benchmarks.layer_metrics.setup_build_s import recorder
from benchmarks.reduce_trace import _union, short_name

UNNAMED = "unnamed"
NO_ROW = "no-row"
GC_SPAN = phase_trace.SPAN_PREFIX + "gc"
TOP = 3
LONG = 1 << 20          # indices from which a gather or a scatter is "long"


def key_of(row: dict) -> str:
    """A row's key: what `short_name` makes of the event the row's
    instruction would be."""
    shape = row["shape"]
    shape = "(tuple)" if shape.startswith("(") else shape.split("{")[0]
    return f"{row['name']} {shape}"[:80]


def rows(symbols: dict) -> dict:
    """{key: {program: row}} of a `window_symbols()` table."""
    out: dict[str, dict] = {}
    for program, table in symbols.items():
        for row in table:
            out.setdefault(key_of(row), {}).setdefault(program, row)
    return out


def symbols_of_run() -> dict | None:
    """The newest runtime's symbol table; None on a program that has
    none (the parent of PR 49) or where it cannot be made. Says what
    making it cost: its seconds, and the backend compile requests and
    cache reloads jax.monitoring saw meanwhile (after a run the
    executable is found again in memory: none of either)."""
    import time

    from jax import monitoring
    found = recorder()
    make = getattr(getattr(found, "rt", None), "window_symbols", None)
    if make is None:
        return None
    seen = []

    def listen(event, seconds, **_kw):
        seen.append((event, seconds))
    monitoring.register_event_duration_secs_listener(listen)
    t0 = time.perf_counter()
    try:
        symbols = make()
    except Exception as e:              # noqa: BLE001 — a reader never raises
        print(f"symbol_trace: no symbol table ({type(e).__name__}: {e})",
              flush=True)
        return None
    finally:
        monitoring.unregister_event_duration_listener(listen)
    compiles = [s for e, s in seen if e.endswith("backend_compile_duration")]
    reloads = [s for e, s in seen if e.endswith("cache_retrieval_time_sec")]
    print(f"symbol table: { {p: len(t) for p, t in symbols.items()} } rows "
          f"in {time.perf_counter() - t0:.3f} s; backend compile requests "
          f"{len(compiles)} ({sum(compiles):.3f} s), cache reloads "
          f"{len(reloads)} ({sum(reloads):.3f} s)", flush=True)
    return symbols


def reduce(data: dict, symbols: dict, ticks: int | None) -> dict:
    """Plain data + table -> {"ticks", "busy_s", "devices", "scopes":
    {scope or `unnamed`: {"s", "ops", "top": [[short name, s], ...],
    "how": {rung: s}}}, "indexed": [{"name", "program", "kind", "scope",
    "s1", "table_bytes", "index_count", "s", "n"}] for every gather and
    scatter that ran, largest first}. Seconds and counts are totals over
    the traced span (the annotations', as `phase_trace.reduce` clips
    it), averaged over the device planes; the readers divide."""
    notes = [h for h in data["host"] if h[0] in reduce_trace.ANNOTATIONS]
    lines = data["device"]
    if notes:
        lo = min(n[1] for n in notes)
        hi = max(n[1] + n[2] for n in notes)
    elif lines:
        lo = min(e[1] for evs in lines for e in evs)
        hi = max(e[1] + e[2] for evs in lines for e in evs)
    else:
        lo = hi = 0.0
    passes = _union([h[1], h[1] + h[2]] for h in data["host"]
                    if h[0] == GC_SPAN and h[2] > 0)
    table = rows(symbols)
    k = max(1, len(lines))

    def row_of(name: str, start: float):
        found = table.get(short_name(name))
        if not found:
            return None, None
        program = next(iter(found))
        if len(found) > 1 and "gc" in found:
            in_pass = any(a <= start < b for a, b in passes)
            program = "gc" if in_pass else \
                next(p for p in found if p != "gc")
        return program, found[program]

    scopes: dict[str, dict] = {}
    indexed: dict[tuple, dict] = {}
    busy_ns = 0.0
    for events in lines:
        clipped = []
        for name, start, dur, _op_name in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e > s:
                # `_self_events` hands its fourth field through: here it
                # carries the event's start for the join
                clipped.append([name, s, e - s, s])
        busy_ns += sum(e - s for s, e in
                       _union([e[1], e[1] + e[2]] for e in clipped))
        for name, start, self_ns, leaf in phase_trace._self_events(clipped):
            program, row = row_of(name, start)
            scope = row["scope"] if row and row["scope"] else UNNAMED
            how = row["how"] if row else NO_ROW
            rec = scopes.setdefault(scope, {"s": 0.0, "ops": 0.0,
                                            "by_op": {}, "how": {}})
            rec["s"] += self_ns / 1e9
            rec["how"][how] = rec["how"].get(how, 0.0) + self_ns / 1e9
            if leaf and self_ns > 0:
                rec["ops"] += 1
            short = short_name(name)
            rec["by_op"][short] = rec["by_op"].get(short, 0.0) + self_ns / 1e9
            if row and row["kind"] in ("gather", "scatter"):
                op = indexed.setdefault((program, short), {
                    "name": short, "program": program, "kind": row["kind"],
                    "scope": row["scope"], "s1": row["s1"],
                    "table_s1": row.get("table_s1"),
                    "table_bytes": row["table_bytes"],
                    "index_count": row["index_count"], "s": 0.0, "n": 0.0})
                op["s"] += self_ns / 1e9
                op["n"] += 1
    for rec in scopes.values():
        by_op = rec.pop("by_op")
        rec["s"] /= k
        rec["ops"] /= k
        rec["how"] = {how: s / k for how, s in rec["how"].items()}
        rec["top"] = [[name, s / k] for name, s in
                      sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]]
    for op in indexed.values():
        op["s"] /= k
        op["n"] /= k
    return {"ticks": ticks, "busy_s": busy_ns / k / 1e9, "devices": len(lines),
            "scopes": scopes,
            "indexed": sorted(indexed.values(), key=lambda op: -op["s"])}


def under(reduced: dict, *prefixes: str) -> float:
    """Device seconds in the traced span under the scopes that are, or
    lie below, one of `prefixes`."""
    return sum(rec["s"] for scope, rec in reduced["scopes"].items()
               if any(scope == p or scope.startswith(p + "/")
                      for p in prefixes))


def mark(op: dict) -> str:
    """`S(1)`: the table an operation reads or writes by index has the
    mark; `out`: only its output has (a gather whose table is plain
    memory's all the same); `plain`: neither."""
    table = op["table_s1"] if op.get("table_s1") is not None else op["s1"]
    return "S(1)" if table else "out" if op["s1"] else "plain"


def plain(reduced: dict) -> float:
    """Device seconds of the long gathers and scatters (`LONG` indices
    or more) whose TABLE is plain memory's (`mark` is not `S(1)`): the
    side that is read or written by index. The output's mark alone does
    not count: the quiet mesh's `words[:, perm]`, output marked and
    table plain, runs 72.8 ms for the 43.6 of the same gather with
    both marked (my chip run, PR 49)."""
    return sum(op["s"] for op in reduced["indexed"]
               if mark(op) != "S(1)" and (op["index_count"] or 0) >= LONG)


def table(reduced: dict, busy_s: float | None = None) -> str:
    """For people, after `phase_trace.table`: scope -> ms a tick,
    operations a tick, the three largest operations and the rungs that
    named its time; then the gathers and scatters by name."""
    ticks = reduced["ticks"] or 1
    agree = "" if not busy_s else (
        f"; phase_trace's busy differs by "
        f"{100.0 * abs(reduced['busy_s'] - busy_s) / busy_s:.4f}%")
    lines = [f"symbols over {reduced['ticks']} traced ticks (device busy "
             f"{1e3 * reduced['busy_s'] / ticks:.4f} ms a tick{agree}):",
             f"  {'scope':<28}{'ms/tick':>12}{'ops/tick':>10}  "
             "named by (ms/tick); largest"]
    total = 0.0
    for scope, rec in sorted(reduced["scopes"].items(),
                             key=lambda kv: -kv[1]["s"]):
        total += rec["s"]
        how = " ".join(f"{h} {1e3 * s / ticks:.3f}" for h, s in
                       sorted(rec["how"].items(), key=lambda kv: -kv[1]))
        top = ", ".join(f"{n} {1e3 * s / ticks:.3f}" for n, s in rec["top"])
        lines.append(f"  {scope:<28}{1e3 * rec['s'] / ticks:>12.4f}"
                     f"{rec['ops'] / ticks:>10.1f}  {how}; {top}")
    lines.append(f"  {'sum':<28}{1e3 * total / ticks:>12.4f}")
    lines.append("gathers and scatters (ms/tick, runs/tick, indices, "
                 "table bytes, table S(1) / out only / plain, scope):")
    for op in reduced["indexed"]:
        lines.append(
            f"  {op['name']:<34}{op['kind']:<8}{1e3 * op['s'] / ticks:>10.4f}"
            f"{op['n'] / ticks:>7.1f}{op['index_count'] or 0:>10}"
            f"{op['table_bytes'] or 0:>12}  "
            f"{mark(op):<6}{op['scope']}"
            + ("" if op["program"] == "window" else f" [{op['program']}]"))
    return "\n".join(lines)


_cache: dict = {}


def of_run(ctx: dict) -> dict | None:
    """The reduction of this run's trace by this run's symbol table,
    made once a process and printed once (after `phase_trace`'s table).
    None where no trace was written, the trace has no device events, or
    the program has no symbol table."""
    path = reduce_trace.find_xplane(phase_trace.TRACE_DIR)
    if path is None or not ctx.get("trace"):
        return None
    key = (path, os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        phases = phase_trace.of_run(ctx)
        symbols = symbols_of_run()
        data = phase_trace.load(path) if symbols else None
        if not data or not data["device"]:
            _cache[key] = None
        else:
            _cache[key] = reduce(data, symbols, ctx["trace"]["ticks"])
            print(table(_cache[key], phases and phases["busy_s"]), flush=True)
    return _cache[key]


def per_tick(ctx: dict, seconds_of, scale: float) -> float | None:
    """`scale` x `seconds_of(reduction)` a traced tick."""
    reduced = of_run(ctx)
    if not reduced or not reduced["ticks"]:
        return None
    return scale * seconds_of(reduced) / reduced["ticks"]
