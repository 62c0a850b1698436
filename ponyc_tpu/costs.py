"""What the compiler says of the programs a runtime launches: their
static analysis and their symbol table. Nothing here is a measurement
of a run.

- ``capture(rt)`` — AOT-lower + compile the runtime's step and its
  gated window and record XLA's ``cost_analysis()`` (flops, bytes
  accessed) and ``memory_analysis()`` (argument / output / temp / peak
  bytes) per executable, under the key ``measured`` of ``/metrics``,
  the postmortem and ``doctor`` (``Runtime.measured_costs()``,
  ``RuntimeOptions(cost_capture=True)``). Works on CPU and TPU: CPU's
  memory_analysis may be absent and every field degrades to None, never
  raises. The capture never touches the traced step itself, so the step
  jaxpr is bit-identical with it on or off.
- ``window_symbols(rt)`` / ``hlo_symbols(text)`` — a row for every
  instruction of the compiled window (and of the collector's program)
  that can be a device event: its phase scope, its kind, and for a
  gather or scatter the memory its table was dealt
  (``Runtime.window_symbols()``; ``benchmarks/symbol_trace.py`` names a
  trace's events by it).

For device wall time by operation, wrap ``Runtime.run()`` in
``jax.profiler.trace(dir)``: the tick's named scopes (``pony/<phase>``)
and the run loop's ``pony:*`` spans come with it
(``benchmarks/phase_trace.py`` reads them).
"""

from __future__ import annotations

import collections
import re
from typing import Any, Dict, Optional

COST_VERSION = 1


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
