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
    import jax.numpy as jnp
    import numpy as np
    inj_t, inj_w = rt._empty_inject
    execs: Dict[str, Any] = {}
    try:
        step_c = rt._step.lower(rt.state, inj_t, inj_w).compile()
        execs["step"] = capture_compiled(step_c)
    except Exception as e:                  # noqa: BLE001 — record, go on
        execs["step"] = {"error": f"{type(e).__name__}: {e}"}
    try:
        win_c = rt._multi_g.lower(
            rt.state, inj_t, inj_w, jnp.int32(1), np.bool_(True),
            rt._zero_aux).compile()
        execs["window"] = capture_compiled(win_c)
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
