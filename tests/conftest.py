"""Test env: force an 8-device virtual CPU mesh before any JAX use, and
give every test a deadline that names it.

≙ the reference's fake-stdlib/PassTest fixture strategy (test/libponyc/
util.h:32-82): tests run against a controllable substrate rather than the
real target. Multi-chip sharding tests use these 8 virtual devices; the
real TPU is exercised by chip_smoke.py, never by this suite. The
pinning (env var + config knob + the virtual-device XLA flag + the
persistent compile cache off, all before the first device touch) lives
in ponyc_tpu.platforms.

The deadline (ISSUE 31). A run that is cut by the driver's clock names
nothing; so no test may stand still for longer than DEADLINE_S, set-up
and tear-down of its fixtures included, and the one that does is named:

- soft, at DEADLINE_S: SIGALRM in the main thread. A wait in Python
  (a child, a join, an Event, a socket, a sleep) is interrupted, the
  test FAILS by its node id with every thread's stack in the report
  (and on stderr at once: a report is printed when the run ends, which
  a run that is cut never does), and the run goes on in the same
  process.
- hard, GRACE_S later: a main thread inside native code (an XLA:CPU
  collective that never completes) runs no signal handler. faulthandler's
  watchdog thread needs no bytecode: it writes every thread's stack under
  the node id into STACKS_FILE in the run's temp directory and ends this
  process only. A reporter child that waits for exactly that copies the
  file to stderr. Under xdist the controller reports the test as the one
  its worker crashed in (with the stacks, `pytest_handlecrashitem`),
  starts another worker and hands it the rest of the file. xdist 3.8's
  `--dist loadfile` hands over the crashed test too, and would run it
  once for every worker it may restart: a test that has ended a worker
  of this run already is marked `skip` here.

And a SIGINT nobody pressed fails the test it hit instead of ending
the xdist session (`_stray_sigint_fails`). And a process that has
compiled its way past half of `vm.max_map_count` drops JAX's caches
after the test (`_drop_jit_caches_near_the_map_limit`).
"""

import faulthandler
import os
import signal
import sys
import threading
import traceback

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ponyc_tpu.platforms import force_cpu  # noqa: E402

force_cpu(8)

import _child  # noqa: E402  (tests/ is on the path: rootdir conftest)

# Four times the slowest tier-1 test (31 s alone, ISSUE 31). Constants,
# not options: a longer bound is never the repair of a test that hangs.
DEADLINE_S = 120.0
GRACE_S = 15.0
STACKS_FILE = "deadline-stacks.txt"
SOFT_MARK = "DEADLINE (soft)"
HARD_MARK = "DEADLINE (hard)"
FIRED = "Timeout ("          # faulthandler's own first word when it fires

_REPORTER = """\
import sys
sys.stdin.read()                        # until the process under watch is gone
text = open(sys.argv[1]).read()
if {fired!r} in text:
    sys.stderr.write("\\n{hard}: stood still in native code, process ended: " + text)
""".format(fired=FIRED, hard=HARD_MARK)

_stacks = None            # this process's stacks file, opened at the first test
_stderr = None            # the process's own stderr, not pytest's capture of it
_current = None           # node id the deadline is armed for


def _stacks_path(config, worker=None):
    base = config._tmp_path_factory.getbasetemp()
    return (base / f"popen-{worker}" if worker else base) / STACKS_FILE


def _all_stacks(main_frame):
    names = {t.ident: t.name for t in threading.enumerate()}
    frames = dict(sys._current_frames())
    frames[threading.main_thread().ident] = main_frame
    out = []
    for ident, frame in frames.items():
        out.append(f"--- thread {names.get(ident, '?')} ({ident:#x})\n"
                   + "".join(traceback.format_stack(frame)))
    return "\n".join(out)


def _on_alarm(signum, frame):
    if _current is None:         # raced with the test's end: nobody waits
        return
    message = (f"{SOFT_MARK}: {_current} stood still for {DEADLINE_S:g} s "
               f"(set-up and tear-down included)\n{_all_stacks(frame)}")
    os.write(_stderr, f"\n{message}\n".encode())
    pytest.fail(message, pytrace=False)


def _arm(item):
    global _stacks, _stderr, _current
    if _stacks is None:
        path = _stacks_path(item.config)
        _stacks = open(path, "w")
        _stderr = os.dup(2)       # between tests pytest captures nothing
        reporter = _child.spawn([sys.executable, "-c", _REPORTER, str(path)],
                                stdin=_child.PIPE)
        item.config.add_cleanup(
            lambda: (reporter.stdin.close(), reporter.wait()))
        signal.signal(signal.SIGALRM, _on_alarm)
    _current = item.nodeid
    _stacks.seek(0)
    _stacks.truncate()
    _stacks.write(item.nodeid + "\n")
    _stacks.flush()
    faulthandler.dump_traceback_later(DEADLINE_S + GRACE_S, exit=True,
                                      file=_stacks)
    signal.setitimer(signal.ITIMER_REAL, DEADLINE_S)


def _disarm():
    global _current
    _current = None
    signal.setitimer(signal.ITIMER_REAL, 0)
    faulthandler.cancel_dump_traceback_later()


def _drop_jit_caches_near_the_map_limit():
    """A compiled XLA:CPU executable holds memory mappings for as long
    as a jit cache holds it, and Linux gives a process `vm.max_map_count`
    of them (65,530): tests/test_rebuild.py alone leaves its worker
    44,000, the files behind it add theirs (55,000 seen), and past the
    limit the next compile's mmap fails inside LLVM: a segmentation
    fault in whichever test compiles next, another one every run (PR
    46: four of five whole runs of a tree whose new cases had moved
    which files share that worker). A process past half the limit drops
    JAX's caches; what a later test needs again is compiled again."""
    held, limit = _maps_held_and_limit()
    if held > limit // 2:
        import jax
        jax.clear_caches()


def _maps_held_and_limit():
    """(this process's memory mappings, the most it may have); (0, 0)
    where the system has no such account."""
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            limit = int(f.read())
        with open("/proc/self/maps", "rb") as f:
            return sum(1 for _ in f), limit
    except (OSError, ValueError):
        return 0, 0


@pytest.hookimpl(wrapper=True, tryfirst=True)
def pytest_runtest_protocol(item, nextitem):
    _skip_if_it_ended_a_worker(item)
    _arm(item)
    try:
        return (yield)
    finally:
        _disarm()
        _drop_jit_caches_near_the_map_limit()


def _stray_sigint_fails(item):
    """Wrapper of a test's set-up, call and tear-down in an xdist
    worker. A SIGINT nobody pressed (a flight.Watchdog whose trip()
    lands outside the run() that converts it) is a KeyboardInterrupt,
    which pytest does not hold against the test: the worker goes down
    "by keyboard-interrupt" and xdist ends the whole session, every
    test behind it unrun. Here it fails the test it hit, by name."""
    try:
        return (yield)
    except KeyboardInterrupt:
        if not hasattr(item.config, "workerinput"):
            raise                        # somebody at the keyboard
        pytest.fail(f"stray SIGINT (KeyboardInterrupt) in {item.nodeid}")


pytest_runtest_setup = pytest.hookimpl(wrapper=True)(_stray_sigint_fails)
pytest_runtest_call = pytest_runtest_teardown = pytest_runtest_setup


def _skip_if_it_ended_a_worker(item):
    if not hasattr(item.config, "workerinput"):      # xdist workers only
        return
    run_tmp = item.config._tmp_path_factory.getbasetemp().parent
    for path in run_tmp.glob(f"popen-*/{STACKS_FILE}"):
        name, _, stacks = path.read_text().partition("\n")
        if name == item.nodeid and FIRED in stacks:
            item.add_marker(pytest.mark.skip(
                reason=f"{HARD_MARK} ended a worker in this test already "
                       f"({path}); it failed there and is not run again"))


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    """xdist's controller: a worker went down in `crashitem`. If it was
    the hard deadline that ended it, its stacks go into the report."""
    path = _stacks_path(report.node.config, report.node.gateway.id)
    try:
        name, _, stacks = path.read_text().partition("\n")
    except OSError:
        return
    if name == crashitem and FIRED in stacks:
        report.longrepr = (
            f"{HARD_MARK}: {crashitem} stood still for "
            f"{DEADLINE_S + GRACE_S:g} s in native code; {report.longrepr}"
            f" ({path})\n{stacks}")


def pytest_terminal_summary(terminalreporter):
    """How near the edge the run was: the longest tests (set-up + call
    + tear-down) and how many deadlines fired."""
    seconds, fired = {}, {SOFT_MARK: 0, HARD_MARK: 0}
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if not hasattr(rep, "duration"):
                continue
            seconds[rep.nodeid] = seconds.get(rep.nodeid, 0.0) + rep.duration
            for mark in fired:
                fired[mark] += str(rep.longrepr or "").startswith(mark)
    longest = sorted(seconds.items(), key=lambda kv: -kv[1])[:5]
    terminalreporter.write_line(
        f"deadline {DEADLINE_S:g} s (+{GRACE_S:g} s hard): "
        f"{fired[SOFT_MARK]} soft and {fired[HARD_MARK]} hard fired; longest: "
        + ", ".join(f"{s:.1f} s {n}" for n, s in longest))


def pytest_configure(config):
    # Tier-1 runs with `-m 'not slow'` (ROADMAP); register the marker
    # so opting a heavyweight test out of the budget is warning-free.
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run")
