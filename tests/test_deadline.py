"""The tests' own substrate (ISSUE 31): a test may wait only where
something bounds the wait and says who waited.

- conftest's deadline, shown on a scratch suite with the deadline
  shortened: a wait in Python fails by name and the run goes on; a wait
  in native code ends that process with the name and every stack in the
  stacks file and on stderr, and under xdist the rest of the file runs;
- `_child`: a timeout or the call's end kills the child's GROUP;
- `supervise.Supervisor(child_timeout_s=)`: a hung child is a failure
  with a code of its own, and is restarted;
- `platforms.force_cpu`: the persistent compile cache is off whatever
  the machine exports.
"""

import json
import os
import sys
import textwrap
import time

import pytest

import _child
from ponyc_tpu import supervise

TESTS = os.path.dirname(os.path.abspath(__file__))

# The scratch suite loads THIS suite's conftest by path and shortens its
# two constants (they are not options). It wants the deadline, not the
# backend: `force_cpu` is stubbed so that no process of it imports jax.
SCRATCH_CONFTEST = f"""\
import importlib.util, sys, types
sys.modules["ponyc_tpu"] = types.ModuleType("ponyc_tpu")
sys.modules["ponyc_tpu.platforms"] = types.SimpleNamespace(
    force_cpu=lambda n: None)
sys.path.insert(0, {TESTS!r})
spec = importlib.util.spec_from_file_location(
    "repo_conftest", {os.path.join(TESTS, "conftest.py")!r})
repo_conftest = importlib.util.module_from_spec(spec)
spec.loader.exec_module(repo_conftest)
repo_conftest.DEADLINE_S, repo_conftest.GRACE_S = 1.0, 1.0
globals().update({{k: getattr(repo_conftest, k) for k in dir(repo_conftest)
                  if k.startswith("pytest_")}})
"""

SLEEPS = """\
import time

def test_sleeps_in_python():
    time.sleep(60)
"""

BLOCKS = """\
def test_blocks_in_native_code():
    import ctypes, signal
    # every signal blocked: no handler, not SIGALRM's either, gets to run
    signal.pthread_sigmask(signal.SIG_BLOCK, range(1, signal.NSIG))
    ctypes.CDLL(None).sleep(60)
"""

SIGINT = """\


def test_gets_a_sigint_nobody_pressed():
    import os, signal
    os.kill(os.getpid(), signal.SIGINT)
    time.sleep(5)
"""

PASSES = """\
def test_passes(tmp_path):
    (tmp_path / "ran").write_text("x")
"""


def _scratch_suite(tmp_path, xdist):
    # Under xdist (the driver's own flags) the passing test comes AFTER
    # the one that ends its worker: the worker's replacement runs it.
    # Without xdist the run ends with the process, so it comes first.
    native_file = (BLOCKS, PASSES) if xdist else (PASSES, BLOCKS)
    flags = ["-p", "xdist", "-n", "2", "--dist", "loadfile"] if xdist \
        else ["-p", "no:xdist"]
    (tmp_path / "conftest.py").write_text(SCRATCH_CONFTEST)
    # (without xdist a KeyboardInterrupt is somebody at the keyboard)
    (tmp_path / "test_a_python.py").write_text(
        SLEEPS + (SIGINT if xdist else ""))
    (tmp_path / "test_b_native.py").write_text("\n\n".join(native_file))
    env = {k: None for k in os.environ if k.startswith("PYTEST_")}
    env["PYTEST_DISABLE_PLUGIN_AUTOLOAD"] = "1"     # a second less a process
    return _child.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", f"--basetemp={tmp_path / 'tmp'}",
         f"--junitxml={tmp_path / 'junit.xml'}", *flags],
        cwd=tmp_path, env=env, timeout=60)


@pytest.mark.parametrize("xdist", [True, False],
                         ids=["n2-loadfile", "no-xdist"])
def test_a_test_that_stands_still_is_named_and_the_run_ends(
        tmp_path, xdist):
    t0 = time.monotonic()
    r = _scratch_suite(tmp_path, xdist)
    elapsed = time.monotonic() - t0
    out = r.stdout + r.stderr
    soft = "test_a_python.py::test_sleeps_in_python"
    hard = "test_b_native.py::test_blocks_in_native_code"
    assert r.returncode == 1, out            # ended by itself: not 124
    assert elapsed < 30, out
    # Python wait: failed by name, its stack in the report, run went on
    # (at once on stderr, and under xdist again in the report at the end)
    assert out.count(f"DEADLINE (soft): {soft} stood still for 1 s") \
        >= (2 if xdist else 1), out
    assert "time.sleep(60)" in out and "--- thread MainThread" in out
    # native wait: named on the output with every thread's stack ...
    assert f"DEADLINE (hard): {hard} stood still" in out if xdist else \
        f"process ended: {hard}" in out, out
    assert "Timeout (0:00:02)!" in out
    assert "in test_blocks_in_native_code" in out
    # ... and in the stacks file of the process that ended
    files = list((tmp_path / "tmp").rglob("deadline-stacks.txt"))
    fired = [f.read_text() for f in files if "Timeout (" in f.read_text()]
    assert len(fired) == 1, files
    assert fired[0].startswith(hard + "\n")
    assert "in test_blocks_in_native_code" in fired[0]
    # the third test ran; the hung one was not run again
    assert list((tmp_path / "tmp").rglob("ran")), out
    assert out.count("Timeout (0:00:02)!") == (2 if xdist else 1), out
    if xdist:
        assert "[gw0] node down" in out or "[gw1] node down" in out
        # a SIGINT nobody pressed failed its test, not the session
        assert "Failed: stray SIGINT (KeyboardInterrupt) in test_a_python." \
            "py::test_gets_a_sigint_nobody_pressed" in out, out
        assert "3 failed, 1 passed, 1 skipped" in out, out
        assert "1 soft and 1 hard fired; longest: " in out, out
        # what the driver counts from: 5 cases, 1 of them a pass
        junit = (tmp_path / "junit.xml").read_text()
        assert 'errors="1" failures="2" skipped="1" tests="5"' in junit


# ----------------------------------------------------------- _child

GRANDCHILD = """\
import sys, time
from subprocess import DEVNULL, Popen
g = Popen([sys.executable, "-c", "import time; time.sleep(600)"]{keeps})
open(sys.argv[1], "w").write(str(g.pid))
print("child says hello", flush=True)
time.sleep({child_sleeps})
"""


def _gone(pid):
    """No such process, or a zombie nobody has reaped yet."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] == "Z"
    except OSError:
        return True


@pytest.mark.parametrize("case", ["timeout", "ended"])
def test_child_run_kills_a_grandchild_with_its_group(tmp_path, case):
    pidfile = tmp_path / "pid"
    if case == "timeout":
        # the grandchild holds the child's pipes: the stdlib's own run()
        # would kill the child and wait for them without a bound
        code = GRANDCHILD.format(keeps="", child_sleeps=600)
        t0 = time.monotonic()
        with pytest.raises(_child.ChildTimeout) as e:
            _child.run([sys.executable, "-c", code, str(pidfile)],
                       timeout=2.0)
        assert time.monotonic() - t0 < 8
        assert "still ran after 2 s" in str(e.value)
        assert "-c" in str(e.value)                  # names the command
        assert "child says hello" in str(e.value)    # carries the output
        assert "child says hello" in e.value.stdout
    else:
        code = GRANDCHILD.format(
            keeps=", stdout=DEVNULL, stderr=DEVNULL",
            child_sleeps=0)
        r = _child.run([sys.executable, "-c", code, str(pidfile)], timeout=30)
        assert r.returncode == 0 and "child says hello" in r.stdout
    pid = int(pidfile.read_text())
    deadline = time.monotonic() + 5
    while not _gone(pid) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert _gone(pid), f"grandchild {pid} outlived its group's kill"


def test_child_env_drops_the_compile_cache_unless_handed(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere")
    show = "import os; print(os.environ.get('JAX_COMPILATION_CACHE_DIR'))"
    assert _child.script(show, timeout=30).stdout.strip() == "None"
    handed = _child.script(show, timeout=30,
                           env={"JAX_COMPILATION_CACHE_DIR": "/here"})
    assert handed.stdout.strip() == "/here"


# ------------------------------------------------------- supervisor

HANGS_ONCE = """\
import os, sys, time
marker = sys.argv[1]
if not os.path.exists(marker):
    open(marker, "w").write("hung")
    from subprocess import Popen
    g = Popen([sys.executable, "-c", "import time; time.sleep(600)"])
    open(marker + ".pid", "w").write(str(g.pid))
    time.sleep(600)          # wedged where no watchdog of its own sees it
sys.exit(0)
"""


def test_supervisor_kills_a_hung_child_and_restarts(tmp_path, capfd):
    marker = str(tmp_path / "marker")
    sup = supervise.Supervisor(
        argv=[sys.executable, "-c", HANGS_ONCE, marker],
        prefix=str(tmp_path / "ring"), retries=2, backoff_s=0.01,
        child_timeout_s=1.0)
    t0 = time.monotonic()
    assert sup.run() == 0
    assert time.monotonic() - t0 < 8
    assert sup.restarts == 1
    assert [f["code"] for f in sup.failures] \
        == [supervise.CHILD_TIMEOUT_CODE] == [124]
    assert "child still ran after 1 s" in capfd.readouterr().err
    assert _gone(int(open(marker + ".pid").read()))   # with its group


def test_supervisor_counts_hung_children_against_retries_and_poison(
        tmp_path):
    """Every life hangs at the same ring position: the second timeout
    is the same failure twice in a row, which the poison rule refuses."""
    always = "import time; time.sleep(600)"
    sup = supervise.Supervisor(
        argv=[sys.executable, "-c", always], prefix=str(tmp_path / "ring"),
        retries=5, backoff_s=0.01, child_timeout_s=0.5)
    with pytest.raises(supervise.PoisonError):
        sup.run()
    assert [f["code"] for f in sup.failures] == [124, 124]


# ----------------------------------------- the compile cache is off

MESHED = textwrap.dedent("""\
    import json, os, sys
    sys.path.insert(0, {root!r})
    from ponyc_tpu.platforms import force_cpu
    force_cpu(4)
    import jax, jax.numpy as jnp, numpy as np
    from jax import monitoring
    from jax.sharding import Mesh, PartitionSpec as P
    events = []
    monitoring.register_event_listener(lambda name, **kw: events.append(name))
    mesh = Mesh(np.array(jax.devices()), ("actors",))
    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "actors"), mesh=mesh,
                              in_specs=P("actors"), out_specs=P()))
    total = int(f(jnp.arange(4, dtype=jnp.int32))[0])
    from ponyc_tpu import tuning
    print(json.dumps({{
        "total": total,
        "dir": jax.config.jax_compilation_cache_dir,
        "enabled": jax.config.jax_enable_compilation_cache,
        "enable_compile_cache": tuning.enable_compile_cache(),
        "hits": sum(e.endswith("/cache_hits") for e in events),
        "cache_events": sorted({{e for e in events
                                if "compilation_cache" in e}})}}))
    """).format(root=_child.ROOT)


def test_force_cpu_switches_the_exported_compile_cache_off(tmp_path):
    """JAX_COMPILATION_CACHE_DIR is read by jax at import, so each case
    is a child. The re-test hook fills the directory (the cache WOULD be
    on); without the hook a meshed program against the filled directory
    reads nothing, writes nothing and says the cache is off."""
    cache = tmp_path / "xla"
    env = {"JAX_COMPILATION_CACHE_DIR": str(cache),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"}
    forced = _child.script(
        MESHED, timeout=60, env={**env, "PONY_TPU_COMPILE_CACHE_FORCE": "1"})
    assert forced.returncode == 0, forced.stderr
    was = json.loads(forced.stdout)
    assert was["dir"] == str(cache) and was["enabled"]
    filled = sorted(os.listdir(cache))
    assert filled, was

    r = _child.script(MESHED, timeout=60, env=env)
    assert r.returncode == 0, r.stderr
    got = json.loads(r.stdout)
    assert got["total"] == 6
    assert not got["dir"] and not got["enabled"]
    assert got["enable_compile_cache"] is None
    assert got["hits"] == 0, got
    assert "/jax/compilation_cache/tasks_using_cache" \
        not in got["cache_events"], got
    assert sorted(os.listdir(cache)) == filled


@pytest.mark.parametrize("held,dropped", [(40000, True), (30000, False),
                                          (0, False)])
def test_a_process_past_half_the_map_limit_drops_jaxs_caches(
        monkeypatch, held, dropped):
    """conftest's guard after every test: compiled XLA:CPU executables
    hold memory mappings while a jit cache holds them, and past
    `vm.max_map_count` the next compile segfaults."""
    import conftest
    import jax
    calls = []
    monkeypatch.setattr(jax, "clear_caches", lambda: calls.append(1))
    monkeypatch.setattr(conftest, "_maps_held_and_limit",
                        lambda: (held, 65530 if held else 0))
    conftest._drop_jit_caches_near_the_map_limit()
    assert bool(calls) == dropped
    monkeypatch.undo()
    mine, limit = conftest._maps_held_and_limit()
    assert 0 < mine < limit or (mine, limit) == (0, 0)
