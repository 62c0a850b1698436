#!/usr/bin/env python3
"""The benchmark's one command: one cell, one run, one JSON line.

    python3 benchmarks/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

run from the root of a checkout, on a machine that holds the chips the
cell asks for. This file knows no names: a cell is an entry of
`workloads` in BENCHMARK.json, and everything that belongs to one
configuration, one traffic mix, one world, one way of driving it or one
per-layer metric is a file of its own, found by name:

    configs/<config>.json        sizes, options, guarantees, `world`
    traffic/<traffic>.json       the mix's parameters, `mode`
    worlds/<world>.py            build(cfg, traffic, seed) -> world
    modes/<mode>.py              warm_up / window / traced / finish
    layer_metrics/<metric>.py    read(ctx) -> float | None

One process, which touches JAX once. JAX resolving anything but the
asked platform (a TPU unless `--platform cpu` is given, for the
rehearsal and the self-tests only) or fewer chips than the cell asks for
is an error before any number: exit 2, no result line.

The last line of stdout is the contract's JSON object and nothing more;
everything else a reader might want is on earlier lines.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()      # as near to process start as Python gets

import argparse                    # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".cache", "benchmarks", "trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
RELOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class NoResult(Exception):
    """The run cannot give a number: exit 2 before any result line."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise NoResult(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


class CompileLog:
    """Backend compile requests as jax.monitoring reports them: how
    many, how long, and how much of that was a reload from the cache."""

    def __init__(self):
        from jax import monitoring
        self.requests, self.backend_s, self.reload_s = 0, 0.0, 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.requests += 1
            self.backend_s += seconds
        elif event == RELOAD_EVENT:
            self.reload_s += seconds

    def mark(self) -> dict:
        return {"requests": self.requests, "backend_s": self.backend_s,
                "reload_s": self.reload_s}


def resolve_device(platform: str, chips: int) -> tuple[dict, list]:
    """Touch JAX, here and only here, and hold it to what was asked."""
    if platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != platform:
        raise NoResult(f"JAX resolved {device}, not --platform {platform}")
    if len(devices) < chips:
        raise NoResult(f"the cell needs {chips} chip(s), JAX found {device}")
    return device, devices[:chips]


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def trace_part(mode, world, plan, units: int) -> dict:
    """A few more units of the same driving, under the profiler; the
    trace is reduced at once and only the reduction is kept."""
    import jax
    from benchmarks import reduce_trace
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # the run loop would write 1e5 frames
    jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    try:
        part = mode.traced(world, plan, units)
    finally:
        jax.profiler.stop_trace()
    part["reduced"] = reduce_trace.reduce_dir(TRACE_DIR)
    return part


def metric_lines(metrics: list, cell: str, value_of) -> dict:
    """The `metrics` object of the result line: every metric of the list
    that applies to the cell and has a value."""
    out = {}
    for m in metrics:
        value = value_of(m) if applies(m, cell) else None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(args, scale: dict | None = None) -> dict:
    """Everything but the printing. `scale` overrides keys of the
    configuration for the self-tests (a smaller world on the CPU); the
    command line cannot set it."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], args.workload, "workload")
    cfg_entry = by_name(bench["configs"], cell["config"], "config")
    cfg = {**load_json(ROOT, cfg_entry["file"]), **(scale or {})}
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if importlib.util.find_spec("ponyc_tpu") is None:
        raise NoResult(f"the program (ponyc_tpu) is not in {ROOT}")

    device, devices = resolve_device(args.platform, int(cell["chips"]))
    peaks = load_json(HERE, "peaks.json")
    if device["platform"] == "tpu" and device["kind"] not in peaks:
        raise NoResult(f"no peaks for device kind {device['kind']!r}")
    compiles = CompileLog()
    print(f"device: {device}", flush=True)

    world_mod = importlib.import_module(f"benchmarks.worlds.{cfg['world']}")
    mode = importlib.import_module(f"benchmarks.modes.{traffic['mode']}")
    t0 = time.perf_counter()
    world = world_mod.build(cfg, traffic, args.seed)
    t_built = time.perf_counter()
    opts = world.rt.opts
    print(f"world: {cfg['world']} actors={cfg['actors']} live={world.live} "
          f"built in {t_built - t0:.2f}s; formulation: delivery="
          f"{opts.delivery} pallas={opts.pallas} pallas_fused="
          f"{opts.pallas_fused} mesh_shards={opts.mesh_shards} "
          f"mailbox_cap={opts.mailbox_cap} batch={opts.batch}", flush=True)
    plan = mode.warm_up(world, traffic, args.seconds)
    at_setup = compiles.mark()
    setup_s = time.perf_counter() - T_START
    print(f"set-up: {setup_s:.2f}s (warm-up {time.perf_counter() - t_built:.2f}"
          f"s), compile log {at_setup}, plan "
          f"{ {k: v for k, v in plan.items() if k != 'codes'} }", flush=True)

    win = mode.window(world, plan, args.seconds)
    win["compiles"] = compiles.mark()["requests"] - at_setup["requests"]
    device["memory_peak_bytes"] = memory_peak(devices)
    part = trace_part(mode, world, plan, int(traffic["trace_units"])) \
        if args.trace else None
    out = mode.finish(world, plan, win, part)
    world.rt.stop()

    notes = {**out["notes"], "wall_s": win["wall_s"],
             "compile_requests": win["compiles"],
             "host_gap_us": win["host_gap_us"],
             "sync_dispatches": win["sync_dispatches"],
             "pipelined_dispatches": win["pipelined_dispatches"]}
    print(f"window: {notes}", flush=True)
    print(f"checks: {out['checks']}", flush=True)

    values = {**out["metrics"], "setup_s": setup_s}
    result = {"correct": all(out["checks"].values()) and win["compiles"] == 0,
              "attempted": int(out["attempted"]), "failed": int(out["failed"]),
              "metrics": {}, "device": device}
    if not args.trace:
        result["metrics"] = metric_lines(bench["end_to_end"], cell["name"],
                                         lambda m: values[m["name"]])
        return result

    reduced = part["reduced"]
    trace = reduced and {**reduced, "ticks": part["ticks"]}
    if trace:
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["span_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        print(f"trace: {part['ticks']} ticks in {part['segments']} units, "
              f"busy {trace['busy_s']:.4f}s of {trace['span_s']:.4f}s on "
              f"{trace['devices']} device(s)", flush=True)
    setup = {"compiled_s": max(0.0, at_setup["backend_s"]
                               - at_setup["reload_s"]), **at_setup}
    ctx = {"setup": setup, "window": win, "trace": trace, "device": device,
           "tick_shape": world.tick_shape(), "peak": peaks.get(device["kind"]),
           "cfg": cfg, "traffic": traffic}

    def read(m: dict):
        # a per-layer metric is reported only where the metric it moves is
        moved = by_name(bench["end_to_end"], m["moves"], "end-to-end metric")
        if not applies(moved, cell["name"]):
            return None
        reader = importlib.import_module(
            f"benchmarks.layer_metrics.{m['name']}")
        return reader.read(ctx)
    result["metrics"] = metric_lines(bench["per_layer"], cell["name"], read)
    return result


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                   help="cpu: rehearsal and self-tests only; the device "
                   "record then names the CPU")
    return p.parse_args(argv)


def main(argv=None, scale: dict | None = None) -> int:
    args = parse(argv)
    try:
        result = run_cell(args, scale)
    except NoResult as e:
        print(f"benchmarks/run.py: {e} — no result", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
