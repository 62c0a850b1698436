"""Fast bench-wiring smoke test: the fused measurement window driven at
toy scale, so bench.py's harness (counter verification, the blocks
every run publishes) can never silently rot between chip runs.
Everything here runs on the CPU backend the suite pins; the no-chip
tests below pin that bench.py says so."""

import argparse

import pytest


def _args(**kw):
    base = dict(actors=64, ticks=8, fuse=4, warmup=1, cap=4, pings=2,
                delivery="plan", fused="off", pallas="off",
                lat_actors=64, lat_ticks=40)
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.fixture()
def bench_mod(tmp_path, monkeypatch):
    monkeypatch.setenv("PONY_TPU_TUNING_CACHE", str(tmp_path / "tuning"))
    import bench
    return bench


def test_bench_ubench_smoke(bench_mod):
    # --skip-measured: the observatory is covered below — skip the
    # capture to keep the smoke fast.
    ub = bench_mod.bench_ubench(_args(skip_measured=True))
    assert ub["measured"] == {"skipped": True}
    # The fused window really advanced the world: every tick dispatched
    # actors×pings behaviours (the headline metric's denominator).
    assert ub["processed_counter_ok"]
    assert ub["msgs_per_sec"] > 0
    assert ub["ticks"] == 8 and ub["fuse"] == 4
    # the formulation is the one the flags gave, and nothing was raced
    assert (ub["delivery"], ub["pallas"], ub["pallas_fused"]) \
        == ("plan", False, False)
    assert "tuning" not in ub


def test_bench_latency_uses_the_delivery_given(bench_mod, monkeypatch):
    from ponyc_tpu.models import ring
    seen = []
    build = ring.build
    monkeypatch.setattr(
        ring, "build",
        lambda n, opts: seen.append(opts) or build(n, opts))
    lat = bench_mod.bench_latency(_args(), delivery="cosort", fused=False)
    assert lat["hops_ok"]
    assert lat["p50_us"] > 0
    assert [(o.delivery, o.pallas_fused) for o in seen] \
        == [("cosort", False)]


def test_bench_telemetry_block(bench_mod):
    """The BENCH json's attribution block (per-behaviour profiler at
    analysis=1): runs attribute exactly, queue-wait percentiles and gc
    stats ride along."""
    t = bench_mod.bench_telemetry(_args(), delivery="plan", fused=False)
    assert t["attribution_ok"]
    # actors × pings × ticks behaviours dispatched, all attributed
    assert t["behaviours"]["Pinger.ping"]["runs"] \
        == t["actors"] * 2 * t["ticks"]
    assert t["queue_wait_ticks"]["Pinger"]["p50"] >= 1
    assert "gc_passes" in t and "mute_ticks" in t


def test_bench_ubench_emits_measured_block(bench_mod):
    """Every BENCH json carries a `measured` block (ISSUE 19): XLA's
    cost/memory analysis of the run's real executables, the record
    probe, and the model_divergence verdict against the modelled
    bytes/msg."""
    ub = bench_mod.bench_ubench(_args())
    m = ub["measured"]
    assert "error" not in m
    assert m["executables"]["step"]["bytes_accessed"] > 0
    assert m["executables"]["window"]["bytes_accessed"] > 0
    assert m["modelled"] == {"record_words": 2, "unpacked_bytes": 8.0}
    assert m["model_divergence"]["diverged"] is False


def test_bench_perf_smoke_scoreboard_row(bench_mod, tmp_path, capsys,
                                         monkeypatch):
    """--perf-smoke (ISSUE 19): the observatory end-to-end — json with
    the measured block on stdout, one flattened scoreboard row
    appended to BENCH_HISTORY.jsonl, exit code 0."""
    import json
    hist = tmp_path / "BENCH_HISTORY.jsonl"
    monkeypatch.setattr(bench_mod, "HISTORY_PATH", str(hist))
    rc = bench_mod.bench_perf_smoke(_args(platform="cpu"))
    assert rc == 0
    result = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert result["detail"]["perf_smoke"] is True
    assert result["measured"]["model_divergence"]["diverged"] is False
    assert result["history_path"] == str(hist)
    rows = [json.loads(ln) for ln in hist.read_text().splitlines()]
    assert len(rows) == 1
    assert rows[0]["value"] == result["value"]
    assert rows[0]["measured_step_bytes"] \
        == result["measured"]["executables"]["step"]["bytes_accessed"]
    # and the perf CLI ingests the row it just wrote
    from ponyc_tpu import costs
    loaded = costs.load_history(str(tmp_path))
    assert len(loaded) == 1 and loaded[0]["value"] == result["value"]
    assert costs.perf_check(loaded)["ok"]


def test_bench_trace_smoke_block(bench_mod):
    """The --trace-smoke `tracing` block (causal tracing, PROFILE.md
    §10): one sampled injection reassembles with consistent span
    ticks — attribution_ok style, recorded by every bench that opts
    in."""
    t = bench_mod.bench_trace_smoke(_args(), delivery="plan",
                                    fused=False)
    assert t["spans_ok"] and t["span_count_ok"]
    assert t["traces"] == 1
    assert t["spans"] == 25              # inject + one span per hop
    assert t["max_latency_ticks"] >= 24
    assert t["analysis"] == 3 and t["trace_sample"] == 1


def test_bench_metrics_smoke_block(bench_mod):
    """The --metrics-smoke `metrics` block (PROFILE.md §11): a real
    HTTP scrape-under-load round-trip — /healthz answers mid-run and
    the final Prometheus counters equal Runtime.profile()."""
    m = bench_mod.bench_metrics_smoke(_args(), delivery="plan",
                                      fused=False)
    assert m["scrape_ok"], m
    assert m["counters_match"], m
    assert m["live_scrapes"] >= 1
    assert m["final_status"] == "ok"
    assert m["port"] == 0                # ephemeral requested


def test_bench_checkpoint_smoke_block(bench_mod):
    """The --checkpoint-smoke `checkpoint` block (durable worlds,
    PROFILE.md §12): a cadence-checkpointed run keeps the unfaulted
    outcome, the ring stays intact+bounded, and a restore-fast-start
    reproduces the soaked world."""
    c = bench_mod.bench_checkpoint_smoke(_args(checkpoint_hops=5000),
                                         delivery="plan", fused=False)
    assert c["equal_ok"], c
    assert c["ring_intact_ok"], c
    assert c["checkpoints"] >= 1
    assert 1 <= c["ring_files"] <= 3
    assert c["write_failures"] == 0
    assert c["capture_ms_mean"] >= 0
    assert c["restore_fast_start_s"] < 30


def test_bench_serve_smoke_block(bench_mod):
    """The --serve-smoke `serving` block (ISSUE 9, PROFILE.md §13):
    the real socket front door under ~2x-capacity concurrent demand —
    requests are shed at the edge with BUSY, p50/p99 of ADMITTED
    requests recorded, every frame answered, and the mailbox rings
    never hit a sticky-fail state."""
    s = bench_mod.bench_serve_smoke(_args(), delivery="plan",
                                    fused=False)
    assert s["rings_ok"], s              # no SpillOverflow/SpawnFail
    assert s["rings_sticky_fail"] == {}
    assert s["drained_ok"], s
    assert s["shed_ok"], s               # overload really shed BUSY
    assert s["replies_accounted"], s     # zero unanswered requests
    assert s["ok"] > 0 and s["busy"] > 0
    assert s["bad_value"] == 0
    assert s["p99_us"] > s["p50_us"] > 0
    assert s["goodput_rps"] > 0
    assert s["overload_x"] >= 2.0        # sustained >= 2x overload
    assert s["admission"]["limit"] >= 1
    assert s["batches"] >= 1 and s["submitted"] >= s["ok"]


def test_no_accelerator_exits_nonzero_before_any_number(
        bench_mod, monkeypatch, capsys):
    """`python bench.py` (--platform tpu is the default) on a machine
    where JAX resolves no TPU exits non-zero and prints NO throughput:
    no probe child, no CPU fallback, no shrunken world."""
    monkeypatch.delenv("PONY_TPU_BENCH_PLATFORM", raising=False)
    monkeypatch.setattr("sys.argv", ["bench.py"])
    monkeypatch.setattr(
        bench_mod, "bench_ubench",
        lambda args: pytest.fail("measured without a chip"))
    with pytest.raises(SystemExit) as exc:
        bench_mod.main()
    assert exc.value.code not in (0, None)
    out, err = capsys.readouterr()
    assert out == ""                     # no result, no number
    assert "no chip, no number" in err
    for gone in ("probe_tpu", "cpu_fallback_allowed",
                 "tpu_init_postmortem", "tpu_env_details"):
        assert not hasattr(bench_mod, gone)


def test_results_carry_the_device_and_an_honest_unit(bench_mod):
    """Every result names the device it ran on (platform, device_kind,
    count), and only a TPU run is filed as msgs/sec/chip."""
    dev, init_s = bench_mod.resolve_device("cpu")
    assert dev["platform"] == "cpu" and dev["device_count"] >= 1
    assert isinstance(dev["device_kind"], str) and init_s >= 0
    assert bench_mod.unit_for(dev) == "msgs/sec/cpu-backend"
    assert bench_mod.unit_for({"platform": "tpu"}) == "msgs/sec/chip"


def test_switch_parsing(bench_mod):
    assert bench_mod.switch("on") is True
    assert bench_mod.switch("1") is True
    assert bench_mod.switch("off") is False
    assert bench_mod.switch("0") is False
    with pytest.raises(ValueError, match="on/off"):
        bench_mod.switch("auto")


def test_failed_phase_is_recorded_and_fails_the_process(bench_mod,
                                                        capsys):
    """A secondary phase that raises still records its error in its
    JSON block, but lands in `failed` — main() then exits non-zero
    after printing (an error never rides out under exit code 0)."""
    failed = []

    def boom(_args, delivery, fused):
        raise RuntimeError(f"no {delivery}")

    block = bench_mod.run_phase(failed, "telemetry", boom, None,
                                delivery="plan", fused=False)
    assert block == {"error": "RuntimeError: no plan"}
    assert failed == ["telemetry"]
    assert "RuntimeError: no plan" in capsys.readouterr().err
    ok = bench_mod.run_phase(failed, "run_loop", lambda: {"x": 1})
    assert ok == {"x": 1} and failed == ["telemetry"]
