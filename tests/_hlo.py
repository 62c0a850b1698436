"""The tests' one helper for reading what a window compiles to.

- `bare_hlo(text)`: optimised HLO without what only describes it;
  `op_names(text)`: the multiset of `op_name`s it carries (the scopes
  `benchmarks/phase_trace.py` reads).
- `v5e_counts(snippet, length)`: a function compiled for a DESCRIBED
  v5e (no chip: libtpu's compiler, in a child) and what the chip would
  run under it, counted; `branch_ops(text, scope)`: the same a branch
  of one conditional.
- `python tests/_hlo.py DIR [--chip] [--only NAME ...]`: the jaxpr, the
  bare HLO and the op_names of the windows in WINDOWS, one file each,
  so that "the parent's program, line for line" is `diff -r` of two
  directories. The file imports nothing of the repo but its public
  builders: copied into a checkout of another commit
  (`git archive <commit> | tar -x -C DIR`) it dumps that commit's
  windows. `--chip` adds the one-chip ubench and fan-in windows at their
  cells' own size, compiled for the described v5e (minutes each).
"""

from __future__ import annotations

import importlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bare_hlo(text: str) -> str:
    """Optimised HLO without what only describes it: per-instruction
    metadata={...} and the module's file / function / location / stack
    frame tables that the metadata indexes."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    out, skipping = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skipping = True
        elif skipping and not line.strip():
            skipping = False
        elif not skipping:
            out.append(line)
    return "\n".join(out)


def op_names(text: str) -> list:
    """Every `op_name` of an HLO text, sorted: the multiset of scopes.
    Under `jit(cumsum)` jax 0.9 names a segment after the Python
    function that called `jnp.cumsum`, and it is the caller it lowered
    FIRST in the process, whichever op this is (the lowering is cached:
    two dumps of one commit disagree there). Not a scope; dropped."""
    return sorted(re.sub(r"(jit\(cumsum\))/[^/]+/(reduce_window_sum)",
                         r"\1/\2", name)
                  for name in re.findall(r'op_name="([^"]*)"', text))


def branch_ops(text: str, scope: str) -> list:
    """For each conditional whose `op_name` ends in `scope`, for each
    of its branches, the indexed reads, indexed writes and sorts it can
    reach (through fusions, loops and conditionals inside it), each as
    (opcode, the dimensions of its result): what a branch costs by the
    entry."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line)

    def called(line):
        return re.findall(
            r"(?:calls|to_apply|body|condition|true_computation|"
            r"false_computation)=%?([\w.\-]+)", line) + [
            c.strip().lstrip("%") for group in re.findall(
                r"branch_computations=\{([^}]*)\}", line)
            for c in group.split(",")]

    def reach(comp, seen):
        if comp in seen or comp not in comps:
            return []
        seen.add(comp)
        ops = []
        for line in comps[comp]:
            m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) "
                         r"(gather|scatter|sort)\(", line)
            if m:
                ops.append((m.group(2), tuple(
                    int(d) for d in re.findall(
                        r"\[([\d,]+)\]", m.group(1))[0].split(","))))
            for sub in called(line):
                ops += reach(sub, seen)
        return ops

    return [[sorted(reach(c, set())) for c in called(line)]
            for lines in comps.values() for line in lines
            if " conditional(" in line and re.search(
                rf'op_name="[^"]*{re.escape(scope)}"', line)]


# ------------------------------------------------ for the described v5e

# The child: `snippet` defines `fn` and `args` (`arg(*shape)` is an
# int32 array on the described device); what comes back is counted over
# the compiled text. `long` is how many arrays of `length` elements the
# program WRITES outside a fusion (a parameter is not written); a
# gather under `jit(searchsorted)` is a binary search's, not a read of
# the entries by index.
_FOR_THE_CHIP = """
import functools, json, sys
sys.path.insert(0, {root!r})
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    device = topologies.get_topology_desc("v5e:2x2", "tpu").devices[0]
except Exception as e:
    print(json.dumps({{"no_compiler": repr(e)}})); sys.exit(0)
arg = lambda *shape: jax.ShapeDtypeStruct(
    shape, jnp.int32, sharding=SingleDeviceSharding(device))
{snippet}
text = jax.jit(fn).trace(*args).lower(
    lowering_platforms=("tpu",)).compile().as_text()
if "report" in globals():       # the snippet's own reading of the text
    print(json.dumps(report(text))); sys.exit(0)
seen = dict(gathers=0, scatters=0, sorts=0, long=0)
fused = False
for line in text.splitlines():
    if not line.startswith(" "):
        fused = line.startswith("%fused_computation")
        continue
    head = line.split(" = ")[1].split("(")[0] if " = " in line else ""
    seen["gathers"] += " gather(" in line and "jit(searchsorted)" not in line
    seen["scatters"] += " scatter(" in line
    seen["sorts"] += " sort(" in line
    seen["long"] += ((not fused) and " parameter(" not in line
                     and str({length}) in head)
print(json.dumps(seen))
"""


def v5e_counts(snippet: str, length: int = 0) -> dict:
    """`snippet`'s `fn(*args)` compiled for the described v5e in a
    child: {"gathers", "scatters", "sorts", "long"} of the compiled
    program, or what the snippet's own `report(text)` makes of it.
    Skips the calling test where this machine has no TPU compiler."""
    import pytest

    import _child
    out = _child.script(
        _FOR_THE_CHIP.format(root=ROOT, snippet=snippet, length=length),
        env={"ALLOW_MULTIPLE_LIBTPU_LOAD": "1", "TPU_LOG_DIR": "disabled"})
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.strip().splitlines()[-1])
    if "no_compiler" in seen:
        pytest.skip(f"no TPU compiler here: {seen['no_compiler']}")
    return seen


# ------------------------------------------------------------ the dump

def _bench_rt(config: str, traffic: str, actors: int, cfg_over=None,
              **over):
    """The Runtime of a benchmark world (`benchmarks/worlds/*.py`) at
    `actors`; `over` replaces runtime options, `count` the spreader's
    tree depth, `cfg_over` other keys of the configuration."""
    def load(*parts):
        with open(os.path.join(ROOT, "benchmarks", *parts)) as f:
            return json.load(f)
    cfg = load("configs", config + ".json")
    cfg.update(cfg_over or {}, actors=actors)
    if "count" in over:
        cfg["count"] = over.pop("count")
    cfg["runtime_options"] = {**cfg["runtime_options"], **over}
    world = importlib.import_module("benchmarks.worlds." + cfg["world"])
    return world.build(cfg, load("traffic", traffic + ".json"), 1).rt


def _priorities_on_a_mesh():
    from test_priority import HiSender, LoSender, Rx

    from ponyc_tpu import Runtime, RuntimeOptions
    rt = Runtime(RuntimeOptions(mailbox_cap=4, batch=4, max_sends=4,
                                msg_words=2, spill_cap=64, inject_slots=8,
                                mesh_shards=2))
    rt.declare(HiSender, 4).declare(LoSender, 4).declare(Rx, 4)
    rt.start()
    return rt


def _blobs_over_a_mesh():
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import records
    return records.build(4, 2, RuntimeOptions(
        mailbox_cap=8, batch=2, max_sends=2, msg_words=2, inject_slots=8,
        blob_slots=64, blob_words=records.W, mesh_shards=2))[0]


# name -> () -> Runtime: the configurations' worlds, small, and the
# formulations no cell runs.
WINDOWS = {
    "ubench": lambda: _bench_rt("ubench-1m", "cycle", 2048),
    "ring": lambda: _bench_rt("ring-1024", "token", 64),
    "fanin": lambda: _bench_rt("fanin-zipf", "steady", 2048),
    "gups": lambda: _bench_rt("gups-hpcc", "stream", 512),
    "spreader": lambda: _bench_rt("spreader-forest", "churn", 4096, count=6),
    "bank": lambda: _bench_rt(
        "savina-bank", "transfers", 2002,
        cfg_over={"teller_batch": 12, "teller_mailbox_cap": 32}),
    "mesh4": lambda: _bench_rt("ubench-4m-mesh4", "remote", 2048),
    "fanin-mesh4": lambda: _bench_rt("fanin-zipf-mesh4", "crossing", 2048),
    "ubench-analysis1": lambda: _bench_rt("ubench-1m", "cycle", 2048,
                                          analysis=1),
    "ubench-analysis3-tracing": lambda: _bench_rt(
        "ubench-1m", "cycle", 2048, analysis=3, trace_sample=1),
    "ubench-cosort": lambda: _bench_rt("ubench-1m", "cycle", 2048,
                                       delivery="cosort"),
    "mesh4-analysis1": lambda: _bench_rt("ubench-4m-mesh4", "remote", 2048,
                                         analysis=1),
    "mesh2-priorities": _priorities_on_a_mesh,
    "mesh2-blobs": _blobs_over_a_mesh,
}
# The one-chip cells' own windows, for the described v5e (`--chip`).
CHIP_WINDOWS = {
    "chip-ubench-1m": lambda: _bench_rt("ubench-1m", "random", 1 << 20),
    "chip-fanin-1m": lambda: _bench_rt("fanin-zipf", "steady", 1 << 20),
}


def window_texts(rt, chip: bool = False):
    """(jaxpr, optimised HLO) of `rt`'s gated window, the program
    `Runtime.run()` dispatches; with `chip`, the HLO is the described
    v5e's (one-chip worlds only)."""
    import jax
    import jax.numpy as jnp

    from ponyc_tpu.runtime import engine
    args = (rt.state, *rt._empty_inject, jnp.int32(4), jnp.bool_(True),
            rt._zero_aux)
    if not chip:
        fn = engine.jit_multi_step_gated(rt.program, rt.opts, rt.mesh)
        return str(jax.make_jaxpr(fn)(*args)), \
            fn.lower(*args).compile().as_text()
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ponyc_tpu.ops import mailbox_kernel as mk
    mk.interpret_mode = lambda: False        # what the chip would see
    sh = SingleDeviceSharding(
        topologies.get_topology_desc("v5e:2x2", "tpu").devices[0])
    fn = engine.build_multi_step_gated(rt.program, rt.opts)
    specs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        jnp.shape(x), jnp.asarray(x).dtype, sharding=sh), args)
    return str(jax.make_jaxpr(fn)(*args)), \
        jax.jit(fn, donate_argnums=(0,)).trace(*specs).lower(
            lowering_platforms=("tpu",)).compile().as_text()


def dump(directory: str, names=None, chip: bool = False) -> list:
    """Write `<name>.jaxpr.txt`, `<name>.hlo.txt` (bare) and
    `<name>.ops.txt` (the op_names, sorted) for each window; returns the
    names written."""
    os.makedirs(directory, exist_ok=True)
    windows = {**WINDOWS, **CHIP_WINDOWS}
    done = []
    for name in names or [*WINDOWS, *(CHIP_WINDOWS if chip else ())]:
        rt = windows[name]()
        jaxpr, hlo = window_texts(rt, chip=name in CHIP_WINDOWS)
        rt.stop()
        for kind, text in (("jaxpr", jaxpr), ("hlo", bare_hlo(hlo)),
                           ("ops", "\n".join(op_names(hlo)))):
            with open(os.path.join(directory, f"{name}.{kind}.txt"),
                      "w") as f:
                f.write(text + "\n")
        done.append(name)
        print(f"{name}: {len(jaxpr.splitlines())} jaxpr lines", flush=True)
    return done


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("directory")
    ap.add_argument("--chip", action="store_true")
    ap.add_argument("--only", nargs="*")
    a = ap.parse_args()
    sys.path.insert(0, ROOT)
    from ponyc_tpu.platforms import force_cpu
    force_cpu(8)
    dump(a.directory, a.only, chip=a.chip)
