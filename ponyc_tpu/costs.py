"""Measured, not modelled — device-cost capture (ISSUE 19; the
observability substrate ROADMAP item 2's real-silicon speed run
dispatches on).

``modelled_bytes_per_msg`` prices a ring record from the layout alone.
This module pulls the numbers XLA itself reports for the REAL
executables — the Halide
push-memory paper's discipline (PAPERS.md): HBM traffic is measured
before/after staging a pipeline, never assumed — and the
resource-consumption-preserving actors→Haskell translation's posture of
cost accounting attributed per construct rather than per opaque binary:

- ``capture(rt)`` — AOT-lower + compile the runtime's actual step and
  pipelined-window executables and record ``cost_analysis()`` (flops,
  bytes accessed) and ``memory_analysis()`` (argument/output/temp/peak
  bytes) per executable. Works on CPU and TPU: CPU's memory_analysis
  may be absent and every field degrades to None, never raises. The
  capture never touches the traced step itself, so the step jaxpr is
  bit-identical with the observatory on or off.
- ``record_move_probe(opts)`` — the measured twin of the modelled
  bytes/msg: compile the canonical one-record-per-actor ring move and
  read its bytes/message back from XLA's cost analysis.
- ``divergence(modelled, measured)`` — the loud ``model_divergence``
  flag: when the model and the measurement disagree past a threshold,
  the BENCH json, /metrics and the flight-recorder postmortem all say
  so (a silent model is how three rounds of A/B machinery rotted).

The ``measured`` block these compose (``measured_block(rt)``) rides
every BENCH json next to the modelled bytes/msg. For device wall time
by operation, wrap ``Runtime.run()`` in ``jax.profiler.trace(dir)``:
the tick's named scopes (``pony/<phase>``) and the run loop's
``pony:*`` spans come with it (``benchmarks/phase_trace.py`` reads
them).
"""

from __future__ import annotations

import collections
import re
import sys
from typing import Any, Dict, Optional

COST_VERSION = 1

# Relative disagreement past which modelled and measured bytes/msg are
# flagged as diverged: |measured - modelled| / modelled > tolerance.
# 0.5 is deliberately loose — the model prices the packed-record layout,
# XLA's accounting includes fusion/layout slop; the flag exists to catch
# the model being WRONG (2x+), not to litigate rounding.
DIVERGENCE_TOLERANCE = 0.5


# ---------------------------------------------------------------------------
# per-executable extraction (tolerant across jax versions and backends)

def _cost_dict(compiled) -> Dict[str, Optional[float]]:
    """Normalise ``compiled.cost_analysis()`` — a dict on some
    jax/backends, a one-element list of dicts on others, None where the
    backend reports nothing — into {flops, bytes_accessed,
    transcendentals}, all Optional floats."""
    out: Dict[str, Optional[float]] = {
        "flops": None, "bytes_accessed": None, "transcendentals": None}
    try:
        ca = compiled.cost_analysis()
    except Exception:                       # noqa: BLE001 — degrade
        return out
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return out
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed"),
                      ("transcendentals", "transcendentals")):
        v = ca.get(key)
        if v is not None:
            out[name] = float(v)
    return out


def _memory_dict(compiled) -> Dict[str, Optional[int]]:
    """Normalise ``compiled.memory_analysis()`` (CompiledMemoryStats;
    None on backends that don't report) into plain ints. ``peak_bytes``
    is the executable's device working set: arguments + outputs + temps
    + generated code (the HBM a window actually pins)."""
    out: Dict[str, Optional[int]] = {
        "argument_bytes": None, "output_bytes": None,
        "temp_bytes": None, "alias_bytes": None,
        "generated_code_bytes": None, "peak_bytes": None}
    try:
        ma = compiled.memory_analysis()
    except Exception:                       # noqa: BLE001
        return out
    if ma is None:
        return out
    for attr, name in (("argument_size_in_bytes", "argument_bytes"),
                       ("output_size_in_bytes", "output_bytes"),
                       ("temp_size_in_bytes", "temp_bytes"),
                       ("alias_size_in_bytes", "alias_bytes"),
                       ("generated_code_size_in_bytes",
                        "generated_code_bytes")):
        v = getattr(ma, attr, None)
        if v is not None:
            out[name] = int(v)
    known = [out[k] for k in ("argument_bytes", "output_bytes",
                              "temp_bytes", "generated_code_bytes")
             if out[k] is not None]
    # Donated (aliased) argument pages are the same physical HBM as the
    # outputs they alias — count them once.
    if known:
        out["peak_bytes"] = int(sum(known) - (out["alias_bytes"] or 0))
    return out


def capture_compiled(compiled) -> Dict[str, Any]:
    """The measured record of one compiled executable."""
    rec: Dict[str, Any] = dict(_cost_dict(compiled))
    rec.update(_memory_dict(compiled))
    return rec


# ---------------------------------------------------------------------------
# runtime capture: the REAL step/window executables

def capture(rt, force: bool = False) -> Dict[str, Any]:
    """Cost/memory analysis of the runtime's actual executables,
    memoized on ``rt._costs``. AOT ``lower().compile()`` with the
    runtime's canonical dispatch argument shapes — one extra compile
    per executable (the persistent XLA disk cache absorbs the repeat on
    warm starts); lowering never executes, so the world does not
    advance and donation does not consume ``rt.state``."""
    cached = getattr(rt, "_costs", None)
    if cached is not None and not force:
        return cached
    if rt.state is None:
        raise RuntimeError("call start() first")
    import jax
    inj_t, inj_w = rt._empty_inject
    execs: Dict[str, Any] = {}
    try:
        step_c = rt._step.lower(rt.state, inj_t, inj_w).compile()
        execs["step"] = capture_compiled(step_c)
    except Exception as e:                  # noqa: BLE001 — record, go on
        execs["step"] = {"error": f"{type(e).__name__}: {e}"}
    try:
        # the window's one repeat compile serves the symbol table too
        execs["window"] = capture_compiled(compiled_program(rt, "window"))
    except Exception as e:                  # noqa: BLE001
        execs["window"] = {"error": f"{type(e).__name__}: {e}"}
    out = {
        "version": COST_VERSION,
        "backend": jax.default_backend(),
        "delivery": rt.opts.delivery,
        "executables": execs,
    }
    rt._costs = out
    return out


# ---------------------------------------------------------------------------
# the symbol table of what the run loop launches (Runtime.window_symbols)
#
# A profiler trace names a device operation by its HLO instruction and
# gives a fusion its ROOT's op_name: a fusion whose root has none (a
# dynamic-update-slice, a scatter an XLA pass re-made) reads as under no
# scope though every instruction inside it carries one, and what the
# compiler itself made (copies, its moves into S(1), the prefix sum's
# reduce-window) has no op_name at all. The compiled text has both: the
# table below names each instruction that can be a device event by the
# program's own scopes (state.STEP_SCOPES), and says of each gather and
# scatter which memory its table was dealt.

# Programs the run loop launches, by the Runtime attribute that holds
# their jitted function.
LAUNCHED = {"window": "_multi_g", "gc": "_gc_fn"}

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$")
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMMENT = re.compile(r"/\*.*?\*/")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_INDEX_DIM = re.compile(r"index_vector_dim=(\d+)")
# Opcodes whose called computations run as device events of their own.
_CONTROL = ("while", "conditional", "call", "async-start")
# Never a device event: they name or regroup a buffer.
_NO_EVENT = ("parameter", "tuple", "get-tuple-element", "bitcast",
             "constant")
_COLLECTIVES = ("all-reduce", "all-gather", "all-to-all",
                "collective-permute", "reduce-scatter",
                "collective-broadcast")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8,
                "u64": 8, "f64": 8}
S1 = "S(1)"


def launch_specs(*args):
    """The abstract signature of one launch: every argument as a
    `ShapeDtypeStruct` with the sharding it was committed to. Lowering
    the same jitted function over these finds the executable that ran
    (in memory, else in the persistent cache) where lowering over the
    world's CURRENT arrays may not: on a mesh a program hands a
    zero-size leaf back replicated, which jit runs through the first
    executable and `lower()` compiles anew. Taken at a COLD launch only
    (runtime._dispatch_window, Runtime.gc): a millisecond beside a
    compile or a reload."""
    import jax
    import numpy as np

    def spec(x):
        sharding = x.sharding if isinstance(x, jax.Array) and x.committed \
            else None
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)
    return jax.tree.map(spec, args)


def compiled_program(rt, name: str = "window"):
    """The `Compiled` of a program the run loop launches (LAUNCHED),
    memoized on the runtime: the jitted function lowered over its first
    launch's `launch_specs` — after a run that is the executable that
    ran, found again and not compiled —, or over the canonical dispatch
    arguments where the window has not been launched yet (one compile,
    which the persistent cache absorbs on a warm start). Lowering never
    executes: the world does not advance. None for a program this
    runtime has not built (no collection pass yet)."""
    if name in rt._compiled:
        return rt._compiled[name]
    fn = getattr(rt, LAUNCHED[name])
    if fn is None:
        return None
    specs = rt._launch_specs.get(name)
    if specs is None:
        if name != "window" or rt.state is None:
            raise RuntimeError("call start() first")
        import jax.numpy as jnp
        import numpy as np
        specs = (rt.state, *rt._empty_inject, jnp.int32(1), np.bool_(True),
                 rt._zero_aux)
    rt._compiled[name] = fn.lower(*specs).compile()
    return rt._compiled[name]


def _close(text: str, start: int) -> int:
    """Index of the bracket that closes the one at `text[start]`."""
    depth = 0
    for i in range(start, len(text)):
        if text[i] in "([{":
            depth += 1
        elif text[i] in ")]}":
            depth -= 1
            if depth == 0:
                return i
    return len(text) - 1


def _computations(text: str):
    """({computation: [instruction]}, entry) of an HLO text; an
    instruction is a dict: name, opcode, shape (with its layout as
    written), operands (names), called (computations), attrs (what
    follows the operands), op_name."""
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            current = comps.setdefault(head.group(2), [])
            if head.group(1):
                entry = head.group(2)
            continue
        found = _INSTRUCTION.match(line) if current is not None else None
        if not found:
            continue
        rhs = found.group(3)
        cut = _close(rhs, 0) + 1 if rhs.startswith("(") else rhs.find(" ")
        shape, rest = rhs[:cut], rhs[cut + 1:]
        paren = rest.find("(")
        if cut < 0 or paren < 0:
            continue
        end = _close(rest, paren)
        operands = [piece.split()[-1].lstrip("%") for piece in
                    _split(_COMMENT.sub("", rest[paren + 1:end]))
                    if piece.strip()]
        attrs = rest[end + 1:]
        named = _OP_NAME.search(attrs)
        called = _CALLED.findall(attrs) + [
            c.strip().lstrip("%") for group in _BRANCHES.findall(attrs)
            for c in group.split(",")]
        current.append({"name": found.group(2), "opcode": rest[:paren],
                        "shape": shape, "operands": operands,
                        "called": called, "attrs": attrs,
                        "op_name": named.group(1) if named else None})
    return comps, entry


def _split(text: str):
    """`text` cut at its top-level commas."""
    out, depth, last = [], 0, 0
    for i, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(text[last:i])
            last = i + 1
    return out + [text[last:]]


def _merge(scopes):
    """One scope for several: theirs if they agree, else their longest
    common prefix (whole segments), else None."""
    scopes = [s for s in scopes if s]
    if not scopes:
        return None
    common = scopes[0].split("/")
    for scope in scopes[1:]:
        parts = scope.split("/")
        n = 0
        while n < min(len(common), len(parts)) and common[n] == parts[n]:
            n += 1
        common = common[:n]
    return "/".join(common) or None


def _dims(shape: str) -> list:
    """The dimensions of the first array of a shape as written."""
    found = _ARRAY.search(shape)
    return [int(d) for d in found.group(2).split(",") if d] if found else []


def _elements(shape: str) -> int:
    n = 1
    for d in _dims(shape):
        n *= d
    return n


_RENAMES = ("bitcast", "copy", "reshape", "convert", "transpose")


def _table_of(index, callers, comp, ins):
    """(layout-bearing shape of the table an indexed read or write goes
    to, count of its index vectors) for the gather or scatter `ins` of
    computation `comp`: operand 0, followed through what only renames a
    buffer to the computation's parameter and from there, while `comp`
    is a fusion's, to the producing instruction in its caller. The
    table is None where the fusion makes it itself (a scatter into
    zeros). `index`: {computation: {name: instruction}}."""
    count = 0
    indices = index[comp].get(ins["operands"][1])
    if indices is not None:
        dims = _dims(indices["shape"])
        at = _INDEX_DIM.search(ins["attrs"])
        at = int(at.group(1)) if at else len(dims)
        count = _elements(indices["shape"]) // (
            dims[at] if at < len(dims) else 1)
    name = ins["operands"][0]
    while True:
        table = index[comp].get(name)
        if table is None:
            return None, count
        if table["opcode"] in _RENAMES:
            name = table["operands"][0]
        elif table["opcode"] == "parameter" and comp in callers:
            comp, fusion = callers[comp]
            name = fusion["operands"][int(table["operands"][0])]
        else:
            return (None if comp in callers else table["shape"]), count


def hlo_symbols(text: str) -> list:
    """The symbol table of one compiled HLO text (`Compiled.as_text()`):
    a row for every instruction that can be a device event — the entry
    computation's and those of the loop bodies, loop conditions,
    branches and called computations it reaches; not the inside of a
    fused computation, not a reducer; parameters, tuples, their
    elements, bitcasts and constants left out. A row:

    - `name`, `opcode`, `shape` (the output's, with its layout as
      written) and `kind`: `gather` / `scatter` / `sort` / `collective`
      / `other`, for a fusion from the instructions of its fused
      computation;
    - `scope` (state.scope_of's form, `delivery/rebuild`) and `how`, the
      rung of the ladder that found it: `own`, the instruction's own
      op_name; `inside`, for a fusion, `while`, `conditional` or `call`
      with none, the scopes of the instructions in the computations it
      calls, transitively — one scope if they agree, else their longest
      common prefix, else (a fusion only: a loop round the whole tick
      is no phase's) the scope most of them carry; `around`, for what is
      still unnamed, the scopes of its operands' producers and of its
      users within the same computation, taken to a fixed point —
      agree, or common prefix; where the two sides have nothing in
      common, the users' (what the compiler makes — a copy, a pad, a
      move into `S(1)`, one link of a concatenation — it makes for its
      consumer), else the producers'; and what a branch or a loop's
      body still holds unnamed (the zeros a branch not taken hands
      back) is its caller's; `none`;
    - `s1`, for a gather or a scatter: **a gather's table, or its
      OUTPUT, carries the mark `S(1)`** (PERF.md §7, C11: the compiler
      dealt it the fast memory space; a list gather reads 25–44 ms
      there and 54–126 ms from plain memory). The table is operand 0 of
      the gather (the scatter's operand), followed to the fusion's
      parameter and from there to the producing instruction's layout
      (a scatter that writes into what its fusion made itself: its
      output). With `table_s1`, the table's mark alone — the side that
      is read or written BY INDEX: on the chip the short list's
      `words[:, perm]` of the quiet mesh, output marked and table
      plain, costs 72.8 ms where the same gather with both marked
      costs 43.6 (PERF.md §6, PR 49), so the output's mark does not
      make up for the table's —, `table_bytes` and `index_count`. None
      for any other row."""
    from .runtime.state import scope_of
    comps, entry = _computations(text)
    if entry is None:
        return []
    own = {name: [scope_of(i["op_name"]) for i in instrs]
           for name, instrs in comps.items()}
    index = {name: {i["name"]: i for i in instrs}
             for name, instrs in comps.items()}
    callers = {}                # fused computation -> (computation, fusion)
    for name, instrs in comps.items():
        for ins in instrs:
            if ins["opcode"] == "fusion":
                for sub in ins["called"]:
                    callers[sub] = (name, ins)

    below = {}                  # computation -> scopes inside, transitively

    def scopes_below(name, seen=()):
        if name not in below:
            found = collections.Counter(s for s in own.get(name, ()) if s)
            for ins in comps.get(name, ()):
                for sub in ins["called"]:
                    if sub not in seen:
                        found.update(scopes_below(sub, seen + (name,)))
            below[name] = found
        return below[name]

    def fused(ins):
        """(computation, instruction) of everything a fusion holds,
        through the fusions nested in it."""
        for sub in ins["called"] if ins["opcode"] == "fusion" else ():
            for inner in comps.get(sub, ()):
                yield sub, inner
                yield from fused(inner)

    def kind_of(ins):
        opcodes = {ins["opcode"]} | {i["opcode"] for _c, i in fused(ins)}
        if {op.replace("-start", "").replace("-done", "")
                for op in opcodes} & set(_COLLECTIVES):
            return "collective"
        for kind in ("sort", "scatter", "gather"):
            if kind in opcodes:
                return kind
        return "other"

    def indexed(comp, ins, kind):
        """s1, table_bytes, index_count of a gather / scatter row: its
        largest indexed operation's."""
        where = [(comp, ins)] if ins["opcode"] == kind else [
            (c, i) for c, i in fused(ins) if i["opcode"] == kind]
        table, count = max((_table_of(index, callers, c, i)
                            for c, i in where), key=lambda tc: tc[1])
        if table is None and kind == "scatter":
            table = ins["shape"]        # written where the fusion made it
        dtype = _ARRAY.search(table) if table else None
        return {"s1": S1 in ins["shape"] or bool(table and S1 in table),
                "table_s1": S1 in table if table else None,
                "index_count": count,
                "table_bytes": _elements(table) * _DTYPE_BYTES.get(
                    dtype.group(1), 4) if dtype else None}

    rows, queue = [], [entry]
    outer = {entry: None}       # computation -> its caller's scope
    while queue:
        comp = queue.pop(0)
        instrs = comps[comp]
        scope = dict(zip((i["name"] for i in instrs), own[comp]))
        how = {n: "own" for n, s in scope.items() if s}
        for ins in instrs:
            if scope[ins["name"]] or not ins["called"] or ins["opcode"] \
                    not in ("fusion",) + _CONTROL:
                continue
            inside = collections.Counter()
            for sub in ins["called"]:
                inside.update(scopes_below(sub))
            found = _merge(list(inside))
            if not found and inside and ins["opcode"] == "fusion":
                found = inside.most_common(1)[0][0]
            if found:
                scope[ins["name"]], how[ins["name"]] = found, "inside"
        users = collections.defaultdict(list)
        for ins in instrs:
            for operand in ins["operands"]:
                users[operand].append(ins["name"])
        # a parameter joins buffers that have nothing to do with each
        # other and hands no name on; a tuple, for the same reason, only
        # from the loop or branch it feeds to what it gathers; a loop
        # or a branch is the program's own and is named above or not
        carriers = [i for i in instrs
                    if i["opcode"] not in ("parameter",) + _CONTROL]
        changed = True
        while changed:
            changed = False
            found = {}
            for ins in carriers:
                if scope[ins["name"]]:
                    continue
                before = [] if ins["opcode"] == "tuple" else [
                    scope.get(n) for n in ins["operands"]]
                after = [scope.get(n) for n in users[ins["name"]]]
                merged = _merge(before + after) or _merge(after) \
                    or _merge(before)
                if merged:
                    found[ins["name"]] = merged
            for name, merged in found.items():
                scope[name], how[name] = merged, "around"
                changed = True
        for ins in carriers:
            if not scope[ins["name"]] and outer[comp]:
                scope[ins["name"]], how[ins["name"]] = outer[comp], "around"
        for ins in instrs:
            for sub in ins["called"] if ins["opcode"] in _CONTROL else ():
                if sub in comps and sub not in outer:
                    outer[sub] = scope[ins["name"]]
                    queue.append(sub)
            if ins["opcode"] in _NO_EVENT:
                continue
            kind = kind_of(ins)
            row = {"name": ins["name"], "opcode": ins["opcode"],
                   "shape": ins["shape"], "kind": kind,
                   "scope": scope[ins["name"]],
                   "how": how.get(ins["name"], "none"),
                   "s1": None, "table_s1": None, "table_bytes": None,
                   "index_count": None}
            if kind in ("gather", "scatter"):
                row.update(indexed(comp, ins, kind))
            rows.append(row)
    return rows


def window_symbols(rt) -> Dict[str, list]:
    """`hlo_symbols` of every program the run loop launches — `window`
    (the gated window `Runtime.run()` dispatches) and, once the world
    has run a collection pass, `gc` —, memoized on the runtime. The
    text is the executable's that runs (`compiled_program`), so an
    instruction's name and shape here are the profiler's event's."""
    for name in LAUNCHED:
        if name not in rt._symbols:
            compiled = compiled_program(rt, name)
            if compiled is not None:
                rt._symbols[name] = hlo_symbols(compiled.as_text())
    return dict(rt._symbols)


# ---------------------------------------------------------------------------
# the measured twin of the modelled bytes/msg

_PROBE_CACHE: Dict[tuple, Dict[str, Any]] = {}


def modelled_bytes_per_msg(opts) -> Dict[str, Any]:
    """The model the probe is judged against: a ring record moves as
    int32 words, 4 bytes each."""
    from .runtime.state import record_words
    w1 = record_words(opts)
    return {"record_words": w1, "unpacked_bytes": 4.0 * w1}


def record_move_probe(opts, n: int = 4096) -> Dict[str, Any]:
    """Measure what XLA actually charges to move one mailbox ring
    record per actor: compile ``record + 1`` over a [record_words, n]
    int32 plane (a read of every record word + a write of every record
    word — the unpacked delivery move) and divide the executable's
    reported bytes accessed by the 2n record-planes it touches. On a
    clean-payload workload this lands on the model's
    ``unpacked_bytes = 4 * record_words`` (tests assert the tolerance);
    a model/layout drift shows up as divergence."""
    import jax
    import jax.numpy as jnp

    from .runtime.state import record_words
    w1 = record_words(opts)
    # The probe depends only on (record_words, n, backend) — memoize
    # per process so repeated measured_block calls pay one compile.
    key = (w1, n, jax.default_backend())
    hit = _PROBE_CACHE.get(key)
    if hit is not None:
        return dict(hit)
    table = jnp.zeros((w1, n), jnp.int32)
    compiled = jax.jit(lambda t: t + 1).lower(table).compile()
    rec = capture_compiled(compiled)
    ba = rec.get("bytes_accessed")
    per_msg = (float(ba) / n / 2.0) if ba else None
    out = {"record_words": w1, "n": n,
           "bytes_accessed": ba, "bytes_per_msg": per_msg}
    _PROBE_CACHE[key] = out
    return dict(out)


def divergence(modelled_bytes: float, measured_bytes: Optional[float],
               tolerance: float = DIVERGENCE_TOLERANCE,
               ) -> Dict[str, Any]:
    """The model-vs-measurement verdict: relative error of the measured
    bytes/msg against the modelled one, flagged past ``tolerance``.
    Unknown measurement (backend reported nothing) is honest: ratio
    None, diverged False — absence of evidence is not divergence."""
    if not measured_bytes or not modelled_bytes:
        return {"modelled_bytes": modelled_bytes,
                "measured_bytes": measured_bytes,
                "ratio": None, "tolerance": tolerance, "diverged": False}
    ratio = float(measured_bytes) / float(modelled_bytes)
    diverged = abs(ratio - 1.0) > tolerance
    return {"modelled_bytes": float(modelled_bytes),
            "measured_bytes": float(measured_bytes),
            "ratio": round(ratio, 4), "tolerance": tolerance,
            "diverged": bool(diverged)}


def measured_block(rt, modelled: Optional[Dict[str, Any]] = None,
                   tolerance: float = DIVERGENCE_TOLERANCE,
                   quiet: bool = False) -> Dict[str, Any]:
    """The standing ``measured`` block every BENCH json carries: the
    real executables' cost/memory analysis, the record-move probe, the
    modelled bytes/msg it is judged against, and the loud
    ``model_divergence`` verdict."""
    cap = dict(capture(rt))
    if modelled is None:
        modelled = modelled_bytes_per_msg(rt.opts)
    probe = record_move_probe(rt.opts)
    div = divergence(modelled["unpacked_bytes"], probe["bytes_per_msg"],
                     tolerance)
    cap["record_probe"] = probe
    cap["modelled"] = modelled
    cap["model_divergence"] = div
    rt._costs = cap   # metrics /metrics + flight postmortem read this
    if div["diverged"] and not quiet:
        print(f"ponyc_tpu costs: MODEL DIVERGENCE — modelled "
              f"{div['modelled_bytes']:.1f} B/msg vs measured "
              f"{div['measured_bytes']:.1f} B/msg "
              f"(ratio {div['ratio']}, tolerance {tolerance}): "
              "the bytes/msg model no longer matches what XLA charges",
              file=sys.stderr)
    return cap


# ---------------------------------------------------------------------------
# perf-regression scoreboard (python -m ponyc_tpu perf [--check])
#
# bench.py appends one flattened row per run to BENCH_HISTORY.jsonl;
# the committed BENCH_r*.json round records are ingested too (their
# driver wrapper format: {"n", "cmd", "rc", "tail", "parsed"} with the
# bench stdout json under "parsed"). The scoreboard compares like with
# like — an explicit CPU run must not read as a "regression" from the
# last TPU run, and a 256-actor smoke must not be judged against a
# 1M-actor headline — so rows group by (metric, unit, platform,
# actors) and --check gates the newest row of each group against the
# best earlier row of the SAME group.

# vs_baseline at the driver-set north star: 10x message-ubench over
# the 32-core CPU estimate (bench.CPU32_BASELINE_MSGS_PER_SEC).
NORTH_STAR_VS_BASELINE = 10.0

# Run-to-run noise allowance for --check: a group's newest value may
# sit this fraction below the group's best without failing the gate.
PERF_TOLERANCE = 0.2


def flatten_result(parsed: Dict[str, Any], source: str,
                   ) -> Optional[Dict[str, Any]]:
    """One scoreboard row from a bench result json (the `parsed` body,
    not the driver wrapper); None when it carries no headline number
    (a failed round). Also accepts rows already flattened by
    bench.history_entry (they have no 'detail')."""
    if not isinstance(parsed, dict) or parsed.get("value") is None:
        return None
    detail = parsed.get("detail") or {}
    measured = parsed.get("measured") or {}
    step = (measured.get("executables") or {}).get("step") or {}
    div = measured.get("model_divergence") or {}
    return {
        "source": source,
        "time": parsed.get("time"),
        "metric": parsed.get("metric"),
        "unit": parsed.get("unit"),
        "value": float(parsed["value"]),
        "vs_baseline": parsed.get("vs_baseline"),
        "platform": detail.get("platform", parsed.get("platform")),
        "delivery": detail.get("delivery", parsed.get("delivery")),
        "actors": detail.get("actors", parsed.get("actors")),
        "measured_step_bytes": step.get(
            "bytes_accessed", parsed.get("measured_step_bytes")),
        "model_divergence": bool(div.get(
            "diverged", parsed.get("model_divergence"))),
        "divergence_ratio": div.get(
            "ratio", parsed.get("divergence_ratio")),
    }


def load_history(root: str = ".", history_path: Optional[str] = None,
                 ) -> list:
    """Every scoreboard row on disk, oldest first: the committed
    BENCH_r*.json round records (sorted by round), then the
    BENCH_HISTORY.jsonl trail in append order. Unreadable files and
    rows degrade to skipped, never raise — the scoreboard must render
    whatever survives."""
    import glob
    import json
    import os
    rows = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        try:
            with open(path) as f:
                obj = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = obj.get("parsed") if isinstance(obj, dict) else None
        if parsed is None and isinstance(obj, dict) and "value" in obj:
            parsed = obj           # a bare bench json, no wrapper
        row = flatten_result(parsed, os.path.basename(path)) \
            if parsed else None
        if row is not None:
            rows.append(row)
    if history_path is None:
        history_path = os.path.join(root, "BENCH_HISTORY.jsonl")
    try:
        with open(history_path) as f:
            lines = f.readlines()
    except OSError:
        lines = []
    for i, line in enumerate(lines):
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        row = flatten_result(obj, f"history[{i}]")
        if row is not None:
            rows.append(row)
    return rows


def group_key(row: Dict[str, Any]) -> tuple:
    return (row.get("metric"), row.get("unit"),
            row.get("platform"), row.get("actors"))


def perf_check(rows: list, tolerance: float = PERF_TOLERANCE,
               ) -> Dict[str, Any]:
    """The regression gate: per comparable group, the newest row must
    not sit more than `tolerance` below the group's best earlier row;
    any row's model_divergence flag is a failure in its own right
    (measured reality disagreeing with the model is exactly what the
    observatory exists to catch). Returns {"ok", "regressions",
    "divergent", "groups"}."""
    groups: Dict[tuple, list] = {}
    for row in rows:
        groups.setdefault(group_key(row), []).append(row)
    regressions, report = [], []
    for key, grp in groups.items():
        best = max(grp, key=lambda r: r["value"])
        latest = grp[-1]
        floor = best["value"] * (1.0 - tolerance)
        regressed = len(grp) >= 2 and latest is not best \
            and latest["value"] < floor
        rec = {"key": key, "n": len(grp),
               "best": best["value"], "best_source": best["source"],
               "latest": latest["value"],
               "latest_source": latest["source"],
               "floor": round(floor, 1), "regressed": regressed}
        report.append(rec)
        if regressed:
            regressions.append(rec)
    divergent = [r for r in rows if r.get("model_divergence")]
    return {"ok": not regressions and not divergent,
            "regressions": regressions, "divergent": divergent,
            "groups": report}


def render_perf(rows: list, check: Optional[Dict[str, Any]] = None,
                ) -> str:
    """The human scoreboard: the trajectory row by row, per-group
    best-so-far, distance to the north star, and the --check verdict
    when one ran."""
    if not rows:
        return ("perf: no history found (run bench.py — every run "
                "appends to BENCH_HISTORY.jsonl; committed "
                "BENCH_r*.json rounds are read too)")
    lines = ["=== ponyc_tpu perf scoreboard ==="]
    for row in rows:
        bits = [f"{row['value']:>14,.1f} {row.get('unit') or ''}",
                f"x{row['vs_baseline']}" if row.get("vs_baseline")
                is not None else "x?",
                f"{row.get('platform') or '?'}/"
                f"{row.get('delivery') or '?'}",
                f"actors={row.get('actors') or '?'}"]
        if row.get("model_divergence"):
            bits.append("MODEL-DIVERGED")
        lines.append(f"  {row['source']:<18} " + "  ".join(bits))
    best = max(rows, key=lambda r: r["value"])
    lines.append(f"best so far: {best['value']:,.1f} "
                 f"{best.get('unit') or ''} ({best['source']}, "
                 f"{best.get('platform')}/{best.get('delivery')})")
    vsb = best.get("vs_baseline")
    if vsb:
        lines.append(
            f"north star:  vs_baseline {NORTH_STAR_VS_BASELINE} "
            f"(10x CPU32) — best is {vsb} "
            f"({100.0 * float(vsb) / NORTH_STAR_VS_BASELINE:.1f}% "
            "of target)")
    if check is not None:
        for rec in check["regressions"]:
            key = rec["key"]
            lines.append(
                f"REGRESSION [{key[2]}/actors={key[3]}]: latest "
                f"{rec['latest']:,.1f} ({rec['latest_source']}) is "
                f"below floor {rec['floor']:,.1f} (best "
                f"{rec['best']:,.1f} from {rec['best_source']})")
        for row in check["divergent"]:
            lines.append(
                f"MODEL DIVERGENCE [{row['source']}]: measured/"
                f"modelled bytes ratio {row.get('divergence_ratio')}")
        lines.append("check: " + ("OK" if check["ok"] else "FAIL"))
    return "\n".join(lines)
