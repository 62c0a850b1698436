"""What ponyc_tpu/tuning.py holds: the window length's memory and the
compile cache's placement. (Nothing is raced at start(): the
formulation switches are tests/test_formulations.py's.)

- the window record hits on an identical (platform, layout, geometry,
  bounds) key, misses on a different one, and a corrupt file means the
  default window instead of an error at start();
- `tuning_cache="off"` reads and writes nothing;
- an explicitly requested kernel that cannot serve the program raises
  at start();
- the compile cache goes where JAX_COMPILATION_CACHE_DIR says, else to
  one fixed path in the checkout.
"""

import json
import os

import pytest

from ponyc_tpu import Runtime, RuntimeOptions, actor, behaviour, I32
from ponyc_tpu import tuning
from ponyc_tpu.models import ubench


def _ub_opts(**kw):
    base = dict(mailbox_cap=4, batch=4, max_sends=1, msg_words=1,
                spill_cap=64, inject_slots=8, compile_cache="off",
                tuning_cache="off")
    base.update(kw)
    return RuntimeOptions(**base)


def _started(n=64, **kw):
    """A started Pinger world (nothing compiles until a tick runs) and
    its window record."""
    rt = Runtime(_ub_opts(**kw))
    rt.declare(ubench.Pinger, n)
    rt.start()
    return rt, rt.tuning_record["quiesce_interval"]


# ---------------------------------------------------------------------------
# the window record


def test_cache_miss_then_hit_then_corrupt(tmp_path):
    cdir = str(tmp_path / "tuning")

    rt, rec1 = _started(tuning_cache=cdir)
    assert rec1 == {"bounds": [4, 1024], "source": "default",
                    "initial": tuning.DEFAULT_QUIESCE_INTERVAL}
    assert not os.path.exists(cdir)             # a miss writes nothing
    path = tuning.store_quiesce_interval(rt.program, rt.opts, 256)
    with open(path) as f:
        assert json.load(f)["chosen"] == {"quiesce_interval": 256}

    rt2, rec2 = _started(tuning_cache=cdir)
    assert rec2["source"] == "cache" and rec2["initial"] == 256
    assert rec2["cache_path"] == path
    assert rt2.opts.quiesce_interval == 256

    with open(path, "w") as f:
        f.write("{corrupt json!")
    rt3, rec3 = _started(tuning_cache=cdir)     # no crash: the default
    assert rec3["source"] == "default"
    assert rt3.opts.quiesce_interval == tuning.DEFAULT_QUIESCE_INTERVAL
    assert tuning.store_quiesce_interval(rt3.program, rt3.opts,
                                         128) == path
    with open(path) as f:                       # and it is overwritten
        assert json.load(f)["chosen"] == {"quiesce_interval": 128}
    # a stored window outside the bounds is clamped into them
    _rt4, rec4 = _started(tuning_cache=cdir, quiesce_interval_max=32)
    assert rec4["source"] == "default"          # other bounds: other key
    tuning.store_quiesce_interval(_rt4.program, _rt4.opts, 4096)
    _rt5, rec5 = _started(tuning_cache=cdir, quiesce_interval_max=32)
    assert rec5["source"] == "cache" and rec5["initial"] == 32


def test_cache_key_separates_layouts(tmp_path):
    cdir = str(tmp_path / "tuning")
    rt1, rec1 = _started(n=64, tuning_cache=cdir)
    assert rec1["source"] == "default"
    tuning.store_quiesce_interval(rt1.program, rt1.opts, 512)
    _rt2, rec2 = _started(n=128, tuning_cache=cdir)
    assert rec2["source"] == "default"          # different key
    _rt3, rec3 = _started(n=64, tuning_cache=cdir)
    assert rec3["source"] == "cache" and rec3["initial"] == 512
    # the formulation is no part of the key: it changes no window
    _rt4, rec4 = _started(n=64, tuning_cache=cdir, delivery="cosort")
    assert rec4["source"] == "cache"
    key = tuning.quiesce_key(rt1.program, rt1.opts)
    assert set(key) == {"v", "field", "platform", "device_kind", "jax",
                        "geometry", "cohorts", "bounds"}


def test_cache_off_never_writes(tmp_path, monkeypatch):
    monkeypatch.setenv("PONY_TPU_TUNING_CACHE", str(tmp_path / "env"))
    rt, rec = _started(tuning_cache="off")      # the option beats the env
    assert rec["source"] == "default" and "cache_path" not in rec
    assert tuning.store_quiesce_interval(rt.program, rt.opts, 256) is None
    monkeypatch.setenv("PONY_TPU_TUNING_CACHE", "off")
    rt, rec = _started(tuning_cache="auto")
    assert rec["source"] == "default"
    assert tuning.store_quiesce_interval(rt.program, rt.opts, 256) is None
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------------
# explicit kernels never give way silently


def test_explicit_kernel_that_cannot_run_raises_at_start():
    """pallas=True on a cohort the drain kernel cannot tile, and
    pallas_fused=True on a cohort the fused kernel cannot host, raise
    at start() naming the cohort and the reason — they never run the
    XLA path under the kernel's name."""
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


# ---------------------------------------------------------------------------
# a compile cache that can be placed from outside


@pytest.fixture()
def restore_jax_cache_config():
    import jax
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
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
    # ... also a directory jax switched on by itself (the variable was
    # exported when jax was imported): with the hook it is left alone,
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    assert tuning.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_enable_compilation_cache
    # "off" leaves jax alone,
    assert tuning.enable_compile_cache("off") is None
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    # and without the hook the CPU backend switches it off (ISSUE 31:
    # returning None over a cache jax had on is how a warm
    # test_mesh_pressure aborted).
    monkeypatch.delenv("PONY_TPU_COMPILE_CACHE_FORCE")
    assert tuning.enable_compile_cache() is None
    assert not jax.config.jax_compilation_cache_dir
    assert not jax.config.jax_enable_compilation_cache


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, restore_jax_cache_config):
    """Unset => one fixed path under the checkout (git-ignored
    .cache/), shared with the window record: never ~, a temp name, a
    pid or a time. Path-valued options are gone."""
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("PONY_TPU_TUNING_CACHE", raising=False)
    # The CPU guard (jaxlib 0.9.0 still deadlocks a reloaded meshed
    # executable's collectives): off here unless forced.
    monkeypatch.delenv("PONY_TPU_COMPILE_CACHE_FORCE", raising=False)
    # conftest's force_cpu has switched it off already; a directory set
    # since (by jax itself, from the machine's variable) goes too.
    assert not jax.config.jax_compilation_cache_dir
    assert not jax.config.jax_enable_compilation_cache
    jax.config.update("jax_compilation_cache_dir", "/tmp/set-before")
    assert tuning.enable_compile_cache() is None
    assert not jax.config.jax_compilation_cache_dir
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
