#!/usr/bin/env python
"""Headline benchmark: message-ubench throughput + p50 dispatch latency.

Reproduces the reference's `examples/message-ubench` metric
(actor-messages/sec; BASELINE.md) at benchmark scale: N pingers in one
shuffled cycle, `--pings` messages in flight per actor (≙ the reference's
--initial-pings, default 5 there), sustained. Each jitted tick dispatches
exactly N×pings behaviours and routes N×pings messages, so

    msgs/sec = N × pings × ticks / elapsed.

Also measures the second tracked BASELINE metric: p50 behaviour-dispatch
latency, via a single-token 1024-actor ring (≙ examples/ring/main.pony) —
each tick is one hop, timed individually with a device sync.

vs_baseline: the reference publishes no absolute numbers (BASELINE.md —
"published: {}"); the driver-set north star is ≥10× message-ubench on a
32-core CPU. We use 3.0e8 msgs/s as the 32-core CPU estimate (Pony's
ubench sustains O(10M) msgs/core/s on modern x86), so vs_baseline 10.0
== the north-star 10× target.

Platform handling: no chip, no number. The bench runs in ONE process on
the backend JAX resolves and `--platform tpu` (the default) exits
non-zero, before any number is printed, unless that backend is a TPU —
it never probes in a child process, never falls back to the CPU and
never shrinks the world. `--platform cpu` pins the CPU backend
explicitly (tests, smoke runs); every result carries `platform`,
`device_kind` and the device count, so a CPU number can never be read
as a chip number.

The formulation is what the flags say (`--delivery plan|cosort`,
`--pallas`, `--fused`; default plan, kernels off): one the program
cannot serve is refused at start(). The jax persistent compile cache is
enabled (at $JAX_COMPILATION_CACHE_DIR where set, else the checkout's
.cache/ponyc_tpu/xla), so a second identical run's warmup_s drops to
executable-reload time.

Every run also embeds a `telemetry` block: a headline-shaped pass at
analysis=1 whose per-behaviour runs, queue-wait percentiles and GC
stats (Runtime.profile(), the per-behaviour profiler of PROFILE.md §8)
attribute the ticks, so the BENCH trajectory records where the time
went, not just totals. The timed headline pass itself stays level 0.

Usage: python bench.py  [--actors N] [--ticks K] [--platform tpu|cpu]
                        [--delivery plan|cosort]
                        [--pallas on|off] [--fused on|off]
                        [--trace-smoke] [--metrics-smoke]
                        [--checkpoint-smoke] [--serve-smoke]

--trace-smoke adds a `tracing` block: one sampled causal-tracing pass
(analysis=3, trace_sample=1, PROFILE.md §10) reassembled and checked
(spans_ok/span_count_ok — attribution_ok style). --metrics-smoke adds
a `metrics` block: a scrape-under-load round-trip through the real
HTTP exporter (RuntimeOptions.metrics_port, PROFILE.md §11) whose
final counters must equal Runtime.profile(). --checkpoint-smoke adds
a `checkpoint` block: checkpoint cost per window, per-checkpoint
capture/write costs and restore-fast-start time (durable worlds,
PROFILE.md §12). --serve-smoke adds a `serving` block: the real socket
front door (serve.py) driven by loadgen.py at ~2x measured capacity —
p50/p99 end-to-end latency of admitted requests, shed rate at the
edge, goodput, and the rings-never-sticky-fail check (PROFILE.md
§13). A secondary phase that fails still records its error in the
JSON, but the process then exits non-zero.
Env:   PONY_TPU_BENCH_ACTORS / PONY_TPU_BENCH_TICKS /
       PONY_TPU_BENCH_PLATFORM / PONY_TPU_BENCH_DELIVERY /
       PONY_TPU_BENCH_FUSED / PONY_TPU_BENCH_PALLAS override;
       JAX_COMPILATION_CACHE_DIR places the compile cache.
"""

import argparse
import json
import os
import statistics
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

CPU32_BASELINE_MSGS_PER_SEC = 3.0e8

# Standing perf-regression scoreboard (ISSUE 19): every bench run
# appends one flattened line here; `python -m ponyc_tpu perf [--check]`
# renders the trajectory and gates CI on regressions.
HISTORY_PATH = os.environ.get("PONY_TPU_BENCH_HISTORY",
                              "BENCH_HISTORY.jsonl")


def history_entry(result):
    """Flatten one bench result json into a perf-trajectory row: the
    headline number, enough context to interpret it (platform,
    delivery, world size), and the measured numbers the scoreboard
    tracks alongside the modelled ones."""
    detail = result.get("detail") or {}
    measured = result.get("measured") or {}
    step = (measured.get("executables") or {}).get("step") or {}
    div = measured.get("model_divergence") or {}
    return {
        "time": round(time.time(), 1),
        "metric": result.get("metric"),
        "value": result.get("value"),
        "unit": result.get("unit"),
        "vs_baseline": result.get("vs_baseline"),
        "platform": detail.get("platform"),
        "delivery": detail.get("delivery"),
        "actors": detail.get("actors"),
        "measured_step_bytes": step.get("bytes_accessed"),
        "measured_step_flops": step.get("flops"),
        "measured_step_peak_bytes": step.get("peak_bytes"),
        "model_divergence": div.get("diverged"),
        "divergence_ratio": div.get("ratio"),
    }


def append_history(result, path=None):
    """Append the run's scoreboard row to BENCH_HISTORY.jsonl (best
    effort: a read-only checkout must not sink the bench)."""
    path = path or HISTORY_PATH
    try:
        with open(path, "a") as f:
            f.write(json.dumps(history_entry(result)) + "\n")
    except OSError as e:
        print(f"bench: history append failed ({e})", file=sys.stderr)
        return None
    return path


def resolve_device(platform: str):
    """Touch JAX — once, in this process, the only one that does — and
    return ({platform, device_kind, device_count}, init seconds): the
    device record every result carries. `platform` is "tpu" (demand a
    chip: anything else exits non-zero HERE, before any number exists)
    or "cpu" (pin the CPU backend explicitly, for tests). A backend
    that fails to initialise raises; nothing here retries, probes in a
    child or falls back."""
    if platform == "cpu":
        from ponyc_tpu.platforms import force_cpu
        force_cpu()
    import jax
    t0 = time.monotonic()
    devs = jax.devices()
    init_s = time.monotonic() - t0
    dev = {"platform": devs[0].platform,
           "device_kind": devs[0].device_kind,
           "device_count": len(devs)}
    if platform == "tpu" and dev["platform"] != "tpu":
        print(f"bench: --platform tpu, but JAX resolved {dev} — no "
              "chip, no number (use --platform cpu for an explicit "
              "CPU test run)", file=sys.stderr)
        sys.exit(3)
    return dev, init_s


def unit_for(dev):
    """The headline unit names the backend it was taken on: only a TPU
    run is msgs/sec/chip (a CPU-backend number is never filed under a
    device metric's name)."""
    return ("msgs/sec/chip" if dev["platform"] == "tpu"
            else f"msgs/sec/{dev['platform']}-backend")


def run_phase(failed, name, fn, *a, **kw):
    """Run one secondary phase. A failure is recorded in the phase's
    JSON block AND in `failed`, so main() exits non-zero after printing
    — an error never rides out under exit code 0."""
    try:
        return fn(*a, **kw)
    except Exception as e:                       # noqa: BLE001
        traceback.print_exc()
        failed.append(name)
        return {"error": f"{type(e).__name__}: {e}"}


def switch(v):
    """CLI/env spelling of an on/off runtime option (the environment's
    default does not pass through argparse's `choices`)."""
    v = str(v).lower()
    if v not in ("1", "on", "0", "off"):
        raise ValueError(f"expected on/off/1/0, not {v!r}")
    return v in ("1", "on")


def bench_ubench(args):
    import jax
    import jax.numpy as jnp
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import ubench

    # cap must hold the sustained in-flight pings per pinger (≙ the
    # reference's --initial-pings, default 5 there); the ring rebuild is
    # cap-proportional so keep it at the smallest power of two that fits.
    pings = args.pings
    cap = ubench.cap_for_pings(pings, floor=args.cap)
    opts = RuntimeOptions(mailbox_cap=cap, batch=pings, max_sends=1,
                          msg_words=1, spill_cap=1024, inject_slots=8,
                          delivery=args.delivery,
                          pallas=switch(args.pallas),
                          pallas_fused=switch(args.fused))
    t0 = time.time()
    rt, ids = ubench.build(args.actors, opts, pings=pings)
    ubench.seed_all(rt, ids, hops=1 << 30, pings=pings)  # ~infinite
    build_s = time.time() - t0

    # Drive the fused window directly (engine.build_multi_step): one
    # device dispatch advances `fuse` ticks, so the measurement sees the
    # engine's steady state, not per-dispatch overhead. ubench never
    # quiesces, so every window runs its full `fuse` ticks (asserted via
    # the processed counter below).
    K = max(1, min(args.fuse, args.ticks))   # small --ticks shrinks windows
    limit = jnp.int32(K)
    inj = rt._empty_inject
    state = rt.state
    t0 = time.time()
    warm_windows = -(-args.warmup // K)      # warmup >= 1 (main() clamps)
    for _ in range(warm_windows):
        state, aux, _k = rt._multi(state, *inj, limit)
    jax.block_until_ready(aux)
    warm_s = time.time() - t0

    windows = max(1, args.ticks // K)
    ticks = windows * K
    t0 = time.time()
    for _ in range(windows):
        state, aux, _k = rt._multi(state, *inj, limit)
    jax.block_until_ready(aux)
    elapsed = time.time() - t0
    rt.state = state

    processed = rt.counter("n_processed") & 0xFFFFFFFF
    expect = (warm_windows * K + ticks) * args.actors * pings
    # Measured, not modelled (ISSUE 19): XLA's own cost/memory analysis
    # of THIS run's compiled executables plus the record-move probe,
    # judged against the modelled bytes/msg — the `measured` block
    # every BENCH json carries. Never sinks a run.
    from ponyc_tpu import costs as _costs
    if getattr(args, "skip_measured", False):
        # --skip-measured: dev-iteration knob only — runs for the
        # record must keep the capture (the scoreboard reads it).
        measured = {"skipped": True}
    else:
        try:
            measured = _costs.measured_block(rt)
            # Per-executable wall from the headline timing itself: the
            # measured windows above ARE this executable.
            win_rec = (measured.get("executables") or {}).get("window")
            if isinstance(win_rec, dict):
                win_rec["wall_ms_per_window"] = round(
                    1e3 * elapsed / windows, 4)
                win_rec["wall_ms_per_tick"] = round(
                    1e3 * elapsed / ticks, 4)
        except Exception as e:                   # noqa: BLE001
            measured = {"error": str(e)}
    return {
        "measured": measured,
        "msgs_per_sec": args.actors * pings * ticks / elapsed,
        "pings": pings,
        "elapsed_s": elapsed,
        "tick_ms": 1e3 * elapsed / ticks,
        "ticks": ticks,
        "fuse": K,
        "processed_counter_ok": bool(processed == expect % (1 << 32)),
        "build_s": build_s,
        "warmup_s": warm_s,
        "delivery": rt.opts.delivery,
        "pallas": rt.opts.pallas,
        "pallas_fused": rt.opts.pallas_fused,
    }


def bench_telemetry(args, delivery="plan", fused=False):
    """One headline-shaped pass at analysis=1: the per-behaviour
    profiler (lanes.profile_lanes / Runtime.profile()) attributes the
    run so the BENCH json records WHERE the ticks went, not just
    totals — per-behaviour runs, queue-wait percentiles, gc passes.
    Runs after the timed pass on its own runtime (analysis is a
    trace-time constant; the headline numbers stay level-0) at a
    bounded world size so the extra jit never dominates a run."""
    import jax.numpy as jnp
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import ubench

    actors = min(args.actors, 1 << 16)
    ticks = 64
    pings = args.pings
    cap = ubench.cap_for_pings(pings, floor=args.cap)
    opts = RuntimeOptions(mailbox_cap=cap, batch=pings, max_sends=1,
                          msg_words=1, spill_cap=1024, inject_slots=8,
                          delivery=delivery, pallas_fused=fused,
                          analysis=1)
    rt, ids = ubench.build(actors, opts, pings=pings)
    ubench.seed_all(rt, ids, hops=1 << 30, pings=pings)
    state, aux, _k = rt._multi(rt.state, *rt._empty_inject,
                               jnp.int32(ticks))
    rt.state = state
    rt.steps_run += ticks
    prof = rt.profile()
    return {
        "actors": actors,
        "ticks": ticks,
        "analysis": 1,
        "behaviours": prof["behaviours"],
        "queue_wait_ticks": {
            c: {"p50": v["queue_wait_p50"], "p99": v["queue_wait_p99"]}
            for c, v in prof["cohorts"].items()},
        "mute_ticks": {c: v["mute_ticks"]
                       for c, v in prof["cohorts"].items()},
        "gc_passes": prof["gc"]["passes"],
        "attribution_ok": bool(
            sum(b["runs"] for b in prof["behaviours"].values())
            == prof["totals"]["processed"]),
    }


def bench_runloop(args, delivery="plan", fused=False):
    """Run-loop overhead study (PROFILE.md §9): drive the REAL
    Runtime.run() — not rt._multi — over a seeded ubench world twice,
    once with the forced synchronous fixed-window loop and once with
    the pipelined adaptive loop, and record each mode's host_gap_us
    (wall time the host left the device idle between windows) plus the
    window-length histogram and controller trajectory. The pipelined/
    sync ratio is THIS PR's acceptance number, re-measured by every
    bench run so a regression shows up as a recorded value, not a
    vibe. World size is bounded: the study measures loop overhead, not
    throughput (the headline pass above owns that)."""
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import ubench

    actors = min(args.actors, 1 << 12)
    steps = 1024
    pings = args.pings
    cap = ubench.cap_for_pings(pings, floor=args.cap)
    out = {"actors": actors, "max_steps": steps}
    for mode in ("sync", "pipelined"):
        opts = RuntimeOptions(
            mailbox_cap=cap, batch=pings, max_sends=1, msg_words=1,
            spill_cap=1024, inject_slots=8, delivery=delivery,
            pallas_fused=fused,
            pipeline=(mode == "pipelined"),
            quiesce_interval=("auto" if mode == "pipelined" else 64),
            # The gap study must neither inherit nor publish converged
            # windows — both modes start cold every run.
            tuning_cache="off")
        rt, ids = ubench.build(actors, opts, pings=pings)
        ubench.seed_all(rt, ids, hops=1 << 30, pings=pings)
        t0 = time.time()
        rt.run(max_steps=steps)
        elapsed = time.time() - t0
        rl = rt.run_loop_stats()
        out[mode] = {
            "elapsed_s": round(elapsed, 3),
            "steps": rt.steps_run,
            "windows": rl["windows"],
            "pipelined_dispatches": rl["pipelined_dispatches"],
            "sync_dispatches": rl["sync_dispatches"],
            "host_gap_us_mean": round(rl["host_gap_us_mean"], 1),
            "host_gap_us_total": round(rl["host_gap_us_total"], 1),
            "window_hist": rl["window_hist"],
            "controller": rl["controller"],
        }
    s = out["sync"]["host_gap_us_mean"]
    p = out["pipelined"]["host_gap_us_mean"]
    # ∞-safe: a fully-pipelined run can expose literally zero gap.
    out["host_gap_ratio"] = round(s / p, 2) if p > 0 else None
    out["host_gap_2x_ok"] = bool(p * 2 <= s)
    return out


def bench_trace_smoke(args, delivery="plan", fused=False):
    """Causal-tracing smoke (PROFILE.md §10; --trace-smoke): one
    sampled injection through a small ring at analysis=3 /
    trace_sample=1, run to quiescence and reassembled — the BENCH
    json's standing record (attribution_ok style) that trace
    propagation, span-tick consistency and reassembly hold on THIS
    platform. Bounded world, never allowed to sink a headline run
    (main() guards with try/except)."""
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import ring
    from ponyc_tpu.tracing import consistent

    hops = 24
    opts = RuntimeOptions(mailbox_cap=8, batch=1, max_sends=1,
                          msg_words=1, spill_cap=64, inject_slots=8,
                          delivery=delivery, pallas_fused=fused,
                          analysis=3, trace_sample=1,
                          analysis_path="/tmp/pony_tpu.bench_trace.csv")
    rt, ids = ring.build(64, opts)
    t0 = time.time()
    rt.send(int(ids[0]), ring.RingNode.token, hops)
    rt.run()
    elapsed = time.time() - t0
    trees = rt.traces()
    rt.stop()
    spans = sum(t["n_spans"] for t in trees.values())
    return {
        "analysis": 3,
        "trace_sample": 1,
        "traces": len(trees),
        "spans": spans,
        "max_latency_ticks": max(
            (t["latency"] for t in trees.values()), default=0),
        "elapsed_s": round(elapsed, 3),
        # The acceptance predicates: enq <= disp <= retire on every
        # span with children nested under parents, and a single-token
        # ring reassembling to exactly inject + one span per hop.
        "spans_ok": bool(trees) and all(consistent(t)
                                        for t in trees.values()),
        "span_count_ok": bool(spans == hops + 1),
    }


def bench_metrics_smoke(args, delivery="plan", fused=False):
    """Metrics-export smoke (PROFILE.md §11; --metrics-smoke): a small
    seeded world served on an ephemeral metrics port, scraped OVER HTTP
    while run() is live and again at quiescence — the standing record
    that the scrape surface round-trips under load: /healthz answers
    mid-run, the final Prometheus counters equal Runtime.profile(),
    and the text parses. Bounded world, never allowed to sink a
    headline run (main() guards with try/except)."""
    import threading
    import urllib.request

    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.metrics import parse_prometheus
    from ponyc_tpu.models import ring

    opts = RuntimeOptions(mailbox_cap=8, batch=1, max_sends=1,
                          msg_words=1, spill_cap=64, inject_slots=8,
                          delivery=delivery, pallas_fused=fused,
                          analysis=1, metrics_port=0,
                          analysis_path="/tmp/pony_tpu.bench_metrics.csv")
    rt, ids = ring.build(64, opts)
    hops = 5000
    rt.send(int(ids[0]), ring.RingNode.token, hops)
    base = f"http://127.0.0.1:{rt._metrics.port}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=5.0) as r:
            return r.read().decode()

    live_status = None
    live_scrapes = 0

    def scrape_live():
        nonlocal live_status, live_scrapes
        while not done.is_set():
            try:
                live_status = json.loads(get("/healthz"))["status"]
                parse_prometheus(get("/metrics"))
                live_scrapes += 1
            except OSError:
                pass
            time.sleep(0.02)

    done = threading.Event()
    t = threading.Thread(target=scrape_live, daemon=True)
    t.start()
    t0 = time.time()
    rt.run()
    elapsed = time.time() - t0
    done.set()
    t.join(timeout=5.0)
    final = parse_prometheus(get("/metrics"))
    hz = json.loads(get("/healthz"))
    prof = rt.profile()
    rt.stop()
    counters_match = (
        final.get(("pony_tpu_processed_total", ()))
        == prof["totals"]["processed"]
        and final.get(("pony_tpu_delivered_total", ()))
        == prof["totals"]["delivered"]
        and final.get(("pony_tpu_behaviour_runs_total",
                       (("behaviour", "RingNode.token"),)))
        == prof["behaviours"]["RingNode.token"]["runs"])
    return {
        "port": rt.opts.metrics_port,
        "hops": hops,
        "elapsed_s": round(elapsed, 3),
        "live_scrapes": live_scrapes,
        "live_status": live_status,
        "final_status": hz["status"],
        "scrape_ok": bool(live_scrapes > 0),
        "counters_match": bool(counters_match),
    }


def bench_checkpoint_smoke(args, delivery="plan", fused=False):
    """Durable-worlds smoke (PROFILE.md §12; --checkpoint-smoke): the
    standing record of what crash-safe checkpointing costs and buys on
    this platform — (a) steady-state overhead of a cadence-checkpointed
    run vs the same run with checkpointing off (µs/window), (b) the
    per-checkpoint capture (run-loop-blocking) and write (background)
    costs from Runtime.checkpoint_stats(), (c) restore-fast-start: time
    to restore the soaked terminal world into a fresh runtime, with the
    outcome asserted equal. Bounded world; never sinks a headline run
    (main() guards with try/except)."""
    import shutil
    import tempfile

    import numpy as np
    from ponyc_tpu import Runtime, RuntimeOptions, serialise
    from ponyc_tpu.models import ring

    tmp = tempfile.mkdtemp(prefix="pony_ckpt_bench_")
    hops = int(getattr(args, "checkpoint_hops", 20_000))
    base = dict(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8, delivery=delivery,
                pallas_fused=fused)
    try:
        # (a) baseline: checkpointing off
        rt, ids = ring.build(128, RuntimeOptions(**base))
        rt.send(int(ids[0]), ring.RingNode.token, hops)
        t0 = time.perf_counter()
        rt.run()
        off_s = time.perf_counter() - t0
        windows_off = max(1, rt._rl_windows)
        want = np.asarray(rt.cohort_state(ring.RingNode)["passes"])
        rt.stop()

        # (b) the same run with the cadence checkpointer armed
        prefix = tmp + "/ring"
        rt2, ids2 = ring.build(128, RuntimeOptions(
            **base, checkpoint_every_s=0.02, checkpoint_path=prefix,
            checkpoint_keep=3))
        rt2.send(int(ids2[0]), ring.RingNode.token, hops)
        t0 = time.perf_counter()
        rt2.run()
        on_s = time.perf_counter() - t0
        windows_on = max(1, rt2._rl_windows)
        stats = rt2.checkpoint_stats()
        equal_ok = bool((np.asarray(
            rt2.cohort_state(ring.RingNode)["passes"]) == want).all())
        rt2.stop()                      # final fast-start checkpoint

        # (c) restore-fast-start: soaked world into a fresh runtime
        newest = serialise.newest_intact(prefix)
        ring_files = serialise.list_checkpoints(prefix)
        intact_ok = True
        for _seq, f in ring_files:
            try:
                serialise.verify_snapshot(f)
            except Exception:            # noqa: BLE001
                intact_ok = False
        rt3, _ = ring.build(128, RuntimeOptions(**base))
        t0 = time.perf_counter()
        serialise.restore(rt3, newest)
        restore_s = time.perf_counter() - t0
        restore_equal_ok = bool((np.asarray(
            rt3.cohort_state(ring.RingNode)["passes"]) == want).all())

        n_ckpt = max(1, stats["checkpoints"])
        return {
            "hops": hops,
            "checkpoints": stats["checkpoints"],
            "ring_files": len(ring_files),
            "ring_intact_ok": intact_ok,
            "run_off_s": round(off_s, 4),
            "run_on_s": round(on_s, 4),
            # per-window tax of the armed checkpointer (wall-clock delta
            # over the baseline; noisy at smoke scale — the capture/
            # write costs below are the per-event truth)
            "ckpt_cost_us_per_window": round(
                max(0.0, on_s - off_s) / windows_on * 1e6, 1),
            "windows": windows_on,
            "windows_off": windows_off,
            "capture_ms_mean": round(
                stats["capture_ms_total"] / n_ckpt, 3),
            "write_ms_mean": round(stats["write_ms_total"]
                                   / max(1, stats["written"]), 3),
            "write_failures": stats["failures"],
            "bytes_last": stats["bytes_last"],
            "restore_fast_start_s": round(restore_s, 4),
            "equal_ok": bool(equal_ok and restore_equal_ok),
        }
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_latency(args, delivery="plan", fused=False):
    """p50 behaviour-dispatch latency: single token on a 1024-actor ring,
    one hop per tick. The headline number is the DEVICE-RESIDENT per-hop
    latency — window-of-K hops in one fused dispatch, divided by K — the
    analog of the reference's scheduler-internal dispatch latency (its
    number contains no host RPC either). The per-call host round-trip
    (which adds the launch/dispatch overhead on top) is reported
    alongside as host_roundtrip_us."""
    import jax
    import jax.numpy as jnp
    from ponyc_tpu import RuntimeOptions
    from ponyc_tpu.models import ring

    # The latency ring runs the headline run's formulation.
    opts = RuntimeOptions(mailbox_cap=8, batch=1, max_sends=1, msg_words=1,
                          spill_cap=64, inject_slots=8,
                          delivery=delivery, pallas_fused=fused)
    rt, ids = ring.build(args.lat_actors, opts)
    rt.send(int(ids[0]), ring.RingNode.token, 1 << 30)
    inj = rt._drain_inject()
    state, aux = rt._step(rt.state, *inj)     # pays the jit + injects token
    jax.block_until_ready(aux)
    inj = rt._empty_inject
    K = 32
    limit = jnp.int32(K)
    state, aux, _k = rt._multi(state, *inj, limit)   # fused-window jit
    jax.block_until_ready(aux)
    # Enough windows that the p90 over window means is a real quantile,
    # not the max of a handful of samples.
    windows = max(20, args.lat_ticks // K)
    times = []
    for _ in range(windows):
        t0 = time.perf_counter()
        state, aux, _k = rt._multi(state, *inj, limit)
        jax.block_until_ready(aux)
        times.append((time.perf_counter() - t0) / K)
    # host round-trip: one hop per individually-synced dispatch.
    rtt = []
    for _ in range(20):
        t0 = time.perf_counter()
        state, aux = rt._step(state, *inj)
        jax.block_until_ready(aux)
        rtt.append(time.perf_counter() - t0)
    rt.state = state
    hops = int(rt.cohort_state(ring.RingNode)["passes"].sum())
    # inject step delivers but doesn't dispatch (dispatch precedes
    # delivery in the step): hops = warm window + timed windows + rtt.
    expect = K + windows * K + 20
    return {
        "p50_us": 1e6 * statistics.median(times),
        "p90_us": 1e6 * sorted(times)[int(0.9 * len(times))],
        "host_roundtrip_us": 1e6 * statistics.median(rtt),
        "hops_ok": bool(hops == expect),
    }


def bench_serve_smoke(args, delivery="plan", fused=False):
    """Serving front door smoke (ISSUE 9; --serve-smoke): the standing
    `serving` BENCH block. Phase 1 measures service capacity with a
    gentle closed loop; phase 2 offers ~2x that in concurrent demand
    (conns x depth far past the worker pool) for a fixed window and
    records what the north-star claim needs a number for: p50/p99
    end-to-end latency of ADMITTED requests, shed rate at the edge,
    and goodput under overload — then drains gracefully and asserts
    the mailbox rings never hit a sticky-fail state. Bounded world;
    never sinks a headline run (main() guards with try/except)."""
    import threading

    from ponyc_tpu import loadgen, serve

    workers = 16
    opts = serve.default_options(workers, delivery=delivery,
                                 pallas_fused=fused)
    rt, server = serve.build(workers, opts)
    port = server.listen("127.0.0.1", 0)
    out = {}

    def client():
        try:
            out["calib"] = loadgen.run_load(
                "127.0.0.1", port, conns=2, depth=2, requests=30)
            out["load"] = loadgen.run_load(
                "127.0.0.1", port, conns=4, depth=4 * workers,
                requests=1 << 30, duration_s=2.0,
                busy_backoff_s=0.005)
        finally:
            server.begin_drain()

    t = threading.Thread(target=client, daemon=True)
    t.start()
    code = rt.run()
    t.join(timeout=60.0)
    stats = server.stats()
    sticky = {f"{cls}:{c}": int(n)
              for (cls, c), n in rt._error_counts.items()
              if cls in ("SpillOverflowError", "SpawnCapacityError",
                         "BlobCapacityError")}
    rt.stop()
    calib = out.get("calib") or {}
    load = out.get("load") or {}
    capacity = max(1.0, calib.get("goodput_rps", 0.0))
    return {
        "workers": workers,
        "capacity_rps_est": round(capacity, 1),
        "offered_rps": load.get("offered_rps", 0.0),
        "overload_x": round(load.get("offered_rps", 0.0) / capacity, 2),
        "sent": load.get("sent", 0),
        "ok": load.get("ok", 0),
        "busy": load.get("busy", 0),
        "unanswered": load.get("unanswered", 0),
        "bad_value": load.get("bad_value", 0),
        "p50_us": load.get("p50_us", 0),
        "p99_us": load.get("p99_us", 0),
        "goodput_rps": load.get("goodput_rps", 0.0),
        "shed_rate": load.get("shed_rate", 0.0),
        "shed_by_reason": stats["shed"],
        "admission": stats["admission"],
        "batches": stats["batches"],
        "submitted": stats["submitted"],
        "rings_sticky_fail": sticky,          # must stay empty: the
        #   edge shed BEFORE the device could wedge
        "rings_ok": bool(not sticky and code == 0),
        "drained_ok": bool(stats["drained"] and code == 0),
        "shed_ok": bool(load.get("busy", 0) > 0),
        "replies_accounted": bool(
            load.get("unanswered", 0) == 0 and calib.get(
                "unanswered", 0) == 0),
    }


def bench_perf_smoke(args):
    """--perf-smoke (ISSUE 19): the observatory end-to-end in seconds —
    a tiny headline-shaped ubench run whose json carries the `measured`
    block (XLA cost/memory analysis of the real executables, the
    record-move probe, the model_divergence verdict) and appends the
    scoreboard row to BENCH_HISTORY.jsonl. Runs on --platform like the
    full bench (tests pass cpu). Returns the process exit code (1 only
    when the measured capture itself failed)."""
    dev, _init_s = resolve_device(args.platform)
    # Smoke shape: small enough for the unit-test clock, big enough
    # that the executables are the real plan/window pair.
    args.actors = min(args.actors, 256)
    args.ticks = min(args.ticks, 32)
    args.fuse = min(args.fuse, 8)
    args.warmup = min(args.warmup, 8)
    ub = bench_ubench(args)
    msgs_per_sec = ub["msgs_per_sec"]
    result = {
        "metric": "ubench_actor_messages_per_sec",
        "value": round(msgs_per_sec, 1),
        "unit": unit_for(dev),
        "vs_baseline": round(msgs_per_sec / CPU32_BASELINE_MSGS_PER_SEC,
                             3),
        "detail": {
            "perf_smoke": True,
            "actors": args.actors,
            "ticks": ub["ticks"],
            "delivery": ub["delivery"],
            **dev,
        },
        "measured": ub["measured"],
    }
    result["history_path"] = append_history(result)
    print(json.dumps(result))
    return 1 if "error" in (ub["measured"] or {}) else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--actors", type=int,
                    default=int(os.environ.get("PONY_TPU_BENCH_ACTORS",
                                               1 << 20)))
    ap.add_argument("--ticks", type=int,
                    default=int(os.environ.get("PONY_TPU_BENCH_TICKS", 256)))
    ap.add_argument("--fuse", type=int,
                    default=int(os.environ.get("PONY_TPU_BENCH_FUSE", 64)))
    ap.add_argument("--warmup", type=int, default=64)
    ap.add_argument("--cap", type=int,
                    default=int(os.environ.get("PONY_TPU_BENCH_CAP", 4)))
    ap.add_argument("--pings", type=int,
                    default=int(os.environ.get("PONY_TPU_BENCH_PINGS", 4)))
    ap.add_argument("--delivery",
                    default=os.environ.get("PONY_TPU_BENCH_DELIVERY",
                                           "plan"),
                    choices=["plan", "cosort"],
                    help="delivery formulation (RuntimeOptions.delivery)")
    ap.add_argument("--fused", nargs="?", const="on",
                    default=os.environ.get("PONY_TPU_BENCH_FUSED", "0"),
                    choices=["on", "off", "0", "1"],
                    help="fused Pallas dispatch: on/off")
    ap.add_argument("--pallas", nargs="?", const="on",
                    default=os.environ.get("PONY_TPU_BENCH_PALLAS", "0"),
                    choices=["on", "off", "0", "1"],
                    help="Pallas drain kernel: on/off")
    ap.add_argument("--lat-actors", type=int, default=1024)
    ap.add_argument("--lat-ticks", type=int, default=200)
    ap.add_argument("--platform",
                    default=os.environ.get("PONY_TPU_BENCH_PLATFORM",
                                           "tpu"),
                    choices=["tpu", "cpu"],
                    help="tpu (default): demand a chip — exit non-zero "
                    "before any number unless JAX resolves a TPU; cpu: "
                    "pin the CPU backend explicitly (tests/smoke runs)")
    ap.add_argument("--trace-smoke", action="store_true",
                    default=os.environ.get(
                        "PONY_TPU_BENCH_TRACE_SMOKE", "0") == "1",
                    help="run one sampled causal-tracing window "
                    "(analysis=3, trace_sample=1) and embed a "
                    "`tracing` block in the JSON (PROFILE.md §10)")
    ap.add_argument("--metrics-smoke", action="store_true",
                    default=os.environ.get(
                        "PONY_TPU_BENCH_METRICS_SMOKE", "0") == "1",
                    help="scrape-under-load round-trip: serve a small "
                    "world on an ephemeral metrics_port, scrape "
                    "/metrics+/healthz over HTTP during run(), and "
                    "embed a `metrics` block asserting the final "
                    "counters equal Runtime.profile() (PROFILE.md §11)")
    ap.add_argument("--checkpoint-smoke", action="store_true",
                    default=os.environ.get(
                        "PONY_TPU_BENCH_CHECKPOINT_SMOKE", "0") == "1",
                    help="durable-worlds smoke: a cadence-checkpointed "
                    "run vs the same run with checkpointing off "
                    "(ckpt_cost_us_per_window), per-checkpoint capture/"
                    "write costs, and restore-fast-start time — "
                    "embedded as a `checkpoint` block (PROFILE.md §12)")
    ap.add_argument("--serve-smoke", action="store_true",
                    default=os.environ.get(
                        "PONY_TPU_BENCH_SERVE_SMOKE", "0") == "1",
                    help="serving front door smoke (ISSUE 9): drive "
                    "the real socket ingress tier (serve.py) with "
                    "loadgen at ~2x measured capacity and embed a "
                    "`serving` block — p50/p99 end-to-end latency of "
                    "admitted requests, shed rate, goodput, and the "
                    "rings-never-sticky-fail check")
    ap.add_argument("--skip-measured", action="store_true",
                    help="skip the measured cost capture (dev "
                    "iteration only — runs for the record keep it; "
                    "the BENCH json says `skipped` instead)")
    ap.add_argument("--perf-smoke", action="store_true",
                    default=os.environ.get(
                        "PONY_TPU_BENCH_PERF_SMOKE", "0") == "1",
                    help="device-cost observatory smoke (ISSUE 19): a "
                    "tiny headline-shaped run emitting the `measured` "
                    "block (XLA cost/memory analysis + record-move "
                    "probe + model_divergence) and appending the "
                    "scoreboard row to BENCH_HISTORY.jsonl — seconds, "
                    "not minutes; for tests and CI")
    args = ap.parse_args()
    args.warmup = max(1, args.warmup)   # the first step pays the jit
    args.lat_ticks = max(1, args.lat_ticks)
    if args.perf_smoke:
        sys.exit(bench_perf_smoke(args))

    # The ONE process that touches JAX (the chip belongs to one process
    # at a time; `python -m ponyc_tpu bench` launches this file from a
    # parent that never initialises a backend).
    dev, backend_init_s = resolve_device(args.platform)

    # Persistent compile cache (tuning.enable_compile_cache): the
    # second run of an identical bench reloads its executables instead
    # of re-lowering — the warmup_s delta is the measurement.
    from ponyc_tpu import tuning as _tuning
    compile_cache = _tuning.enable_compile_cache()

    failed = []          # secondary phases that raised (run_phase)
    ub = bench_ubench(args)
    if "error" in ub["measured"]:
        failed.append("measured")
    form = dict(delivery=ub["delivery"], fused=ub["pallas_fused"])
    lat = bench_latency(args, **form)
    # Attribution pass (analysis=1): records per-behaviour runs +
    # queue-wait percentiles so the perf trajectory carries attribution,
    # not just totals.
    telemetry = run_phase(failed, "telemetry", bench_telemetry, args,
                          **form)
    # Run-loop overhead study (PROFILE.md §9): pipelined adaptive vs
    # forced synchronous host_gap_us through the real run() loop.
    run_loop = run_phase(failed, "run_loop", bench_runloop, args, **form)
    # Opt-in smokes: causal tracing (§10), metrics export (§11),
    # durable worlds (§12), the serving front door (§13).
    tracing_block = metrics_block = checkpoint_block = None
    serving_block = None
    if args.trace_smoke:
        tracing_block = run_phase(failed, "tracing", bench_trace_smoke,
                                  args, **form)
    if args.metrics_smoke:
        metrics_block = run_phase(failed, "metrics",
                                  bench_metrics_smoke, args, **form)
    if args.checkpoint_smoke:
        checkpoint_block = run_phase(failed, "checkpoint",
                                     bench_checkpoint_smoke, args,
                                     **form)
    if args.serve_smoke:
        serving_block = run_phase(failed, "serving", bench_serve_smoke,
                                  args, **form)
    msgs_per_sec = ub["msgs_per_sec"]

    result = {
        "metric": "ubench_actor_messages_per_sec",
        "value": round(msgs_per_sec, 1),
        "unit": unit_for(dev),
        "vs_baseline": round(msgs_per_sec / CPU32_BASELINE_MSGS_PER_SEC, 3),
        "detail": {
            "actors": args.actors,
            "ticks": ub["ticks"],
            "pings": ub["pings"],
            "delivery": ub["delivery"],
            "pallas": ub["pallas"],
            "pallas_fused": ub["pallas_fused"],
            "fused_ticks_per_dispatch": ub["fuse"],
            "elapsed_s": round(ub["elapsed_s"], 4),
            "tick_ms": round(ub["tick_ms"], 3),
            "processed_counter_ok": ub["processed_counter_ok"],
            "build_s": round(ub["build_s"], 1),
            "warmup_s": round(ub["warmup_s"], 1),
            **dev,
            "backend_init_s": round(backend_init_s, 1),
            "p50_dispatch_latency_us": round(lat["p50_us"], 1),
            "p90_dispatch_latency_us": round(lat["p90_us"], 1),
            "host_roundtrip_us": round(lat["host_roundtrip_us"], 1),
            "latency_ring_actors": args.lat_actors,
            "latency_hops_ok": lat["hops_ok"],
            "compile_cache": compile_cache,
        },
        # Per-behaviour attribution of a headline-shaped pass at
        # analysis=1 (Runtime.profile(), PROFILE.md §8): the perf
        # trajectory records WHERE the ticks went, not just totals.
        "telemetry": telemetry,
        # host_gap_us: pipelined adaptive run loop vs the forced
        # synchronous loop through the real Runtime.run() (PROFILE.md
        # §9) — the standing record of this PR's win.
        "run_loop": run_loop,
        # Measured device costs (costs.py, ISSUE 19): XLA's own
        # cost/memory analysis of the headline run's compiled
        # executables, the record-move probe, and the loud
        # model_divergence verdict against the modelled bytes/msg.
        "measured": ub["measured"],
    }
    if tracing_block is not None:
        result["tracing"] = tracing_block
    if metrics_block is not None:
        result["metrics"] = metrics_block
    if checkpoint_block is not None:
        result["checkpoint"] = checkpoint_block
    if serving_block is not None:
        result["serving"] = serving_block
    if failed:
        result["failed_phases"] = failed
    append_history(result)
    print(json.dumps(result))
    if failed:
        print(f"bench: phases failed: {failed} — errors recorded in "
              "the JSON above; exiting non-zero", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
