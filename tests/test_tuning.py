"""The delivery/dispatch autotuner (ponyc_tpu/tuning.py).

Three properties are pinned:

- "auto" never changes semantics, only speed: a seeded ubench run under
  delivery="auto" produces exactly the totals and per-actor columns of
  the forced formulations (which the differential suite already proves
  agree with the sequential oracle);
- the decision is a deterministic pure function of the timing table
  (minimum tick_ms, ties to the earlier/safer variant, failed variants
  never win);
- the on-disk tuning cache hits on an identical (platform, layout,
  geometry) key, misses on a different one, and a corrupt cache file
  recalibrates instead of erroring the start.
"""

import json

import numpy as np
import pytest

from ponyc_tpu import Runtime, RuntimeOptions, actor, behaviour, I32
from ponyc_tpu import tuning
from ponyc_tpu.models import ubench


def _ub_opts(**kw):
    base = dict(mailbox_cap=4, batch=4, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8, compile_cache="off",
                tuning_cache="off", tuning_ticks=2, tuning_repeats=1)
    base.update(kw)
    return RuntimeOptions(**base)


def _run_ubench(delivery, n=64, pings=2, ticks=5, **kw):
    rt, ids = ubench.build(n, _ub_opts(delivery=delivery, **kw),
                           pings=pings)
    ubench.seed_all(rt, ids, hops=1 << 30, pings=pings)
    st, inj = rt.state, rt._empty_inject
    for _ in range(ticks):
        st, _aux = rt._step(st, *inj)
    rt.state = st
    cols = rt.cohort_state(ubench.Pinger)
    return rt, {"processed": rt.counter("n_processed"),
                "delivered": rt.counter("n_delivered"),
                "pings": np.asarray(cols["pings"])}


# ---------------------------------------------------------------------------
# decision function


def test_decide_picks_minimum():
    assert tuning.decide({"plan": 2.0, "cosort": 1.0}) == "cosort"
    assert tuning.decide({"plan": 0.5, "cosort": 1.0}) == "plan"


def test_decide_breaks_ties_toward_baseline():
    # Equal timings: the EARLIER entry (the safe baseline) wins, so
    # measurement noise can never flip a dead heat to the exotic path.
    assert tuning.decide({"plan": 1.0, "cosort": 1.0}) == "plan"
    assert tuning.decide({"plan": 1.0, "plan+fused": 1.0,
                          "cosort": 1.0}) == "plan"


def test_decide_never_picks_failed_variants():
    assert tuning.decide({"plan": 3.0, "cosort": None}) == "plan"
    assert tuning.decide({"plan": None, "cosort": 2.0}) == "cosort"
    assert tuning.decide({"plan": None, "cosort": None}) is None


def test_decide_is_deterministic_given_injected_timings():
    table = {"plan": 1.7, "cosort": 1.1, "plan+pallas": None,
             "cosort+pallas": 1.1000001}
    for _ in range(5):
        assert tuning.decide(table) == "cosort"


# ---------------------------------------------------------------------------
# variant enumeration


def test_variants_fixed_delivery_is_single():
    rt = Runtime(_ub_opts(delivery="plan"))
    rt.declare(ubench.Pinger, 8)
    rt.program.finalize()
    assert tuning.variants(rt.program, rt.opts) == [
        ("plan", {"delivery": "plan", "pallas": False,
                  "pallas_fused": False})]


def test_variants_auto_delivery_baseline_first():
    rt = Runtime(_ub_opts(delivery="auto"))
    rt.declare(ubench.Pinger, 8)
    rt.program.finalize()
    names = [n for n, _ in tuning.variants(rt.program, rt.opts)]
    assert names == ["plan", "cosort"]


def test_variants_auto_never_enumerates_megakernel():
    """The window megakernel does not lower on TPU (ops/megakernel.py)
    and on CPU only runs interpreted: delivery="auto" never races it,
    on any backend, under any environment."""
    rt = Runtime(_ub_opts(delivery="auto", pallas="auto",
                          pallas_fused="auto"))
    rt.declare(ubench.Pinger, 8)
    rt.program.finalize()
    vs = tuning.variants(rt.program, rt.opts)
    assert len(vs) == 8                       # 2 deliveries x 2 x 2
    assert all(ov["delivery"] in ("plan", "cosort") for _n, ov in vs)
    assert not hasattr(tuning, "mega_eligible")


def test_tuning_key_version_pinned_v3():
    """The cache-key version must be bumped whenever the variant space
    changes (v3: pallas_mega left it) — a stale v2 record naming the
    megakernel the winner would be refused at start(). Pin it so the
    bump is a conscious act."""
    rt = Runtime(_ub_opts(delivery="auto"))
    rt.declare(ubench.Pinger, 8)
    rt.program.finalize()
    assert tuning.tuning_key(rt.program, rt.opts)["v"] == 3


def test_variants_fused_auto_skips_ineligible_programs():
    # A blob-pool cohort is ineligible for the fused kernel; with every
    # cohort ineligible, pallas_fused="auto" must not enumerate (or
    # silently measure) a variant that would fall back to the baseline.
    @actor
    class BlobUser:
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def grab(self, st):
            self.blob_alloc(length=1)
            return st

    rt = Runtime(_ub_opts(delivery="plan", pallas_fused="auto",
                          msg_words=2, blob_slots=8, blob_words=4))
    rt.declare(BlobUser, 8)
    rt.program.finalize()
    names = [n for n, _ in tuning.variants(rt.program, rt.opts)]
    assert names == ["plan"]


# ---------------------------------------------------------------------------
# forced-variant equivalence (the "auto never changes semantics" oracle)


def test_auto_matches_forced_variants():
    _, plan = _run_ubench("plan")
    _, cosort = _run_ubench("cosort")
    _, auto = _run_ubench("auto")
    assert plan["processed"] == cosort["processed"] == auto["processed"]
    assert plan["delivered"] == cosort["delivered"] == auto["delivered"]
    np.testing.assert_array_equal(plan["pings"], cosort["pings"])
    np.testing.assert_array_equal(plan["pings"], auto["pings"])


def test_auto_resolves_to_concrete_opts():
    rt, _ = _run_ubench("auto")
    assert rt.opts.delivery in ("plan", "cosort")
    rec = rt.tuning_record
    assert rec["source"] == "calibrated"           # cache is off here
    assert set(rec["table"]) == {"plan", "cosort"}
    assert all(isinstance(v, float) for v in rec["table"].values())
    assert rec["winner"] == tuning.decide(rec["table"],
                                          order=rec["variants"])
    assert rec["chosen"]["delivery"] == rt.opts.delivery


def test_calibration_leaves_runtime_state_untouched():
    # Calibration runs on throwaway copies: a freshly started world must
    # still be empty (no live actors, no queued messages, zero counters).
    rt = Runtime(_ub_opts(delivery="auto"))
    rt.declare(ubench.Pinger, 32)
    rt.start()
    assert rt.counter("n_processed") == 0
    assert rt.counter("n_delivered") == 0
    assert not bool(np.asarray(rt.state.alive).any())
    assert int(np.asarray(rt.state.tail).sum()) == 0
    assert int(np.asarray(rt.state.dspill_count).sum()) == 0


# ---------------------------------------------------------------------------
# tuning cache


def test_cache_miss_then_hit_then_corrupt(tmp_path):
    cdir = str(tmp_path / "tuning")

    _, rec1 = tuning_record_for(cdir)
    assert rec1["source"] == "calibrated"
    path = rec1["cache_path"]
    with open(path) as f:
        stored = json.load(f)
    assert stored["chosen"] == rec1["chosen"]

    _, rec2 = tuning_record_for(cdir)
    assert rec2["source"] == "cache"
    assert rec2["chosen"] == rec1["chosen"]
    assert rec2["table"] == rec1["table"]

    with open(path, "w") as f:
        f.write("{corrupt json!")
    _, rec3 = tuning_record_for(cdir)
    assert rec3["source"] == "calibrated"       # corruption recalibrates
    with open(path) as f:
        assert json.load(f)["chosen"] == rec3["chosen"]   # and rewrites


def tuning_record_for(cdir):
    rt, _ = _run_ubench("auto", tuning_cache=cdir)
    return rt, rt.tuning_record


def test_cache_key_separates_layouts(tmp_path):
    cdir = str(tmp_path / "tuning")
    rt1, _ = _run_ubench("auto", n=64, tuning_cache=cdir)
    assert rt1.tuning_record["source"] == "calibrated"
    rt2, _ = _run_ubench("auto", n=128, tuning_cache=cdir)
    assert rt2.tuning_record["source"] == "calibrated"   # different key
    rt3, _ = _run_ubench("auto", n=64, tuning_cache=cdir)
    assert rt3.tuning_record["source"] == "cache"


def test_cache_off_never_writes(tmp_path):
    rt, _ = _run_ubench("auto", tuning_cache="off")
    assert rt.tuning_record["source"] == "calibrated"
    assert "cache_path" not in rt.tuning_record


# ---------------------------------------------------------------------------
# workload construction


def test_workload_is_busy_on_real_shapes():
    rt = Runtime(_ub_opts(delivery="plan"))
    rt.declare(ubench.Pinger, 32)
    rt.start()
    wl, sustain = tuning.make_workload(rt.program, rt.opts, rt.state)
    assert sustain >= 1
    assert bool(np.asarray(wl.alive).any())
    occ = np.asarray(wl.tail) - np.asarray(wl.head)
    assert (occ[np.asarray(wl.alive)] == rt.opts.mailbox_cap).all()
    assert int(np.asarray(wl.dspill_count).sum()) \
        == rt.opts.spill_cap * rt.program.shards


def test_host_only_program_skips_calibration():
    @actor
    class H:
        HOST = True
        n: I32

        @behaviour
        def tick(self, st):
            return {**st, "n": st["n"] + 1}

    rt = Runtime(_ub_opts(delivery="auto"))
    rt.declare(H, 4)
    rt.start()                      # must not raise, must resolve
    assert rt.opts.delivery in ("plan", "cosort")


# ---------------------------------------------------------------------------
# explicit kernels never give way silently


def test_explicit_kernel_that_cannot_run_raises_at_start():
    """pallas=True on a cohort the drain kernel cannot tile, and
    pallas_fused=True on a cohort the fused kernel cannot host, raise
    at start() naming the cohort and the reason — they never run the
    XLA path under the kernel's name. (delivery="pallas_mega":
    tests/test_megakernel.py.) "auto" skips the same variants without
    raising."""
    from ponyc_tpu.ops import mailbox_kernel as mk
    unaligned = mk.LANE_BLOCK + 8       # > one block, not a multiple

    for kernel in ("pallas", "pallas_fused"):
        rt = Runtime(_ub_opts(**{kernel: True}))
        rt.declare(ubench.Pinger, unaligned)
        with pytest.raises(ValueError) as exc:
            rt.start()
        msg = str(exc.value)
        assert f"{kernel}=True cannot be honoured" in msg
        assert "cohort Pinger" in msg and str(unaligned) in msg

        rt = Runtime(_ub_opts(**{kernel: "auto"}))
        rt.declare(ubench.Pinger, unaligned)
        rt.start()                      # auto: skipped, not an error
        assert getattr(rt.opts, kernel) is False

    @actor
    class BlobUser:
        n: I32
        MAX_BLOBS = 1

        @behaviour
        def grab(self, st):
            self.blob_alloc(length=1)
            return st

    rt = Runtime(_ub_opts(pallas_fused=True, msg_words=2, blob_slots=8,
                          blob_words=4))
    rt.declare(BlobUser, 8)
    with pytest.raises(ValueError, match="cohort BlobUser: uses the "
                                         "device blob pool"):
        rt.start()


def test_calibration_failure_is_said_once_on_stderr(capsys, monkeypatch):
    """A variant that fails to build is out of the race AND named on
    stderr; if none produced a timing, the unmeasured baseline default
    is said too — never swallowed."""
    from ponyc_tpu.runtime import engine

    def refuse(program, opts, mesh=None):
        raise NotImplementedError(f"no lowering for {opts.delivery}")

    monkeypatch.setattr(engine, "jit_forced_window", refuse)
    rt = Runtime(_ub_opts(delivery="auto"))
    rt.declare(ubench.Pinger, 8)
    rt.start()
    assert rt.opts.delivery == "plan"
    assert rt.tuning_record["table"] == {"plan": None, "cosort": None}
    err = capsys.readouterr().err
    assert err.count("variant 'plan' failed") == 1
    assert err.count("variant 'cosort' failed") == 1
    assert "no lowering for cosort" in err
    assert "running the baseline 'plan' unmeasured" in err


# ---------------------------------------------------------------------------
# a compile cache that can be placed from outside


@pytest.fixture()
def restore_jax_cache_config():
    import jax
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_compilation_cache_include_metadata_in_key")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_leaves_an_outside_directory_alone(
        monkeypatch, tmp_path, restore_jax_cache_config):
    """JAX_COMPILATION_CACHE_DIR set => the code sets no directory at
    all (jax reads the variable itself; here it was set after import,
    so jax.config must still hold whatever it held)."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("PONY_TPU_COMPILE_CACHE_FORCE", "1")
    assert tuning.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert tuning.enable_compile_cache("off") is None


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, restore_jax_cache_config):
    """Unset => one fixed path under the checkout (git-ignored
    .cache/), shared with the tuning-decision cache: never ~, a temp
    name, a pid or a time. Path-valued options are gone."""
    import os
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PONY_TPU_TUNING_CACHE", raising=False)
    # The CPU guard (jaxlib 0.9.0 still deadlocks a reloaded meshed
    # executable's collectives): off here unless forced.
    monkeypatch.delenv("PONY_TPU_COMPILE_CACHE_FORCE", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert tuning.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.setenv("PONY_TPU_COMPILE_CACHE_FORCE", "1")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".cache", "ponyc_tpu", "xla")
    assert tuning.enable_compile_cache() == want
    assert tuning.enable_compile_cache() == want        # idempotent
    assert jax.config.jax_compilation_cache_dir == want
    # op_names are what a profile names the tick's phases by: an entry
    # written by another build must not serve its own (ISSUE 24)
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    assert tuning.tuning_cache_dir(RuntimeOptions()) \
        == os.path.join(root, ".cache", "ponyc_tpu", "tuning")
    assert not hasattr(tuning, "compile_cache_dir")
    with pytest.raises(ValueError, match="compile_cache"):
        RuntimeOptions(compile_cache="/tmp/somewhere")
