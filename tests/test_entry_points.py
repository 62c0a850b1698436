"""The entry points are what the tree says they are, and no more.

One benchmark (`benchmarks/run.py`) and one scoreboard (the driver's
ledger): nothing else in the tree is a second of either. What this file
pins is the surface that could grow one back —

- every command of `python -m ponyc_tpu` has a usage line and a
  handler, and a name that is no command exits 2;
- every key of `Runtime.run_loop_stats()` has a reader that is not a
  test (a statistic nobody reads is deleted, not kept);
- the `PONY_TPU_*` environment names the code reads are the six the
  README lists;
- `engine` builds one window, the gated one, and a started `Runtime`
  holds two jitted programs.
"""

import os
import re

import pytest

from ponyc_tpu import RuntimeOptions
from ponyc_tpu import __main__ as cli
from ponyc_tpu.models import ring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(rel):
    with open(os.path.join(ROOT, rel), encoding="utf-8") as f:
        return f.read()


# ------------------------------------------------------------ the commands

@pytest.mark.parametrize("name", sorted(cli.COMMANDS))
def test_command_has_a_usage_line_and_a_handler(name):
    assert re.search(rf"^  {name}\b", cli.__doc__, re.M), \
        f"`{name}` is missing from the module docstring's Commands"
    handler = cli.COMMANDS[name]
    assert callable(handler) and handler.__name__ == f"cmd_{name}"


@pytest.mark.parametrize("name", ["bench", "perf"])
def test_a_name_that_is_no_command_exits_2(name, capsys):
    assert name not in cli.COMMANDS
    assert not re.search(rf"^  {name}\b", cli.__doc__, re.M)
    assert cli.main([name]) == 2
    out, err = capsys.readouterr()
    assert out == "" and f"unknown command {name!r}" in err


# ---------------------------------------------------- the run loop's books

# key of run_loop_stats() -> a file that reads it and is not a test
STATS_READERS = {
    "windows": "benchmarks/modes/common.py",
    "pipelined_dispatches": "benchmarks/layer_metrics/sync_dispatch_pct.py",
    "sync_dispatches": "benchmarks/layer_metrics/sync_dispatch_pct.py",
    "host_gap_us_total": "benchmarks/layer_metrics/host_gap_pct.py",
    "windows_wall_s": "ponyc_tpu/metrics.py",
    "phase_s": "benchmarks/layer_metrics/gc_wall_pct.py",
    "phase_n": "ponyc_tpu/metrics.py",
    "cold_dispatches": "ponyc_tpu/analysis.py",
    "cold_dispatch_s": "benchmarks/layer_metrics/setup_cold_launch_s.py",
    "injects_requeued": "ponyc_tpu/metrics.py",
    "gc_runs": "benchmarks/modes/throughput_churn.py",
    "gc_iters": "benchmarks/worlds/spreader.py",
    "free_rows_low": "benchmarks/layer_metrics/free_rows_low_pct.py",
    "pool": "benchmarks/modes/throughput_payload.py",
    "controller": "ponyc_tpu/metrics.py",
}


@pytest.fixture(scope="module")
def started():
    rt, _ids = ring.build(8, RuntimeOptions(
        mailbox_cap=8, batch=1, max_sends=1, msg_words=1, spill_cap=64,
        inject_slots=8))
    yield rt
    rt.stop()


def test_every_run_loop_statistic_is_in_the_table(started):
    """A key added to run_loop_stats() without a reader named here
    fails: the table below is the whole of the dictionary."""
    assert set(started.run_loop_stats()) == set(STATS_READERS)


@pytest.mark.parametrize("key", sorted(STATS_READERS))
def test_run_loop_statistic_has_a_reader(key):
    reader = STATS_READERS[key]
    assert not os.path.basename(reader).startswith("test_")
    assert re.search(rf"""["']{key}["']""", _read(reader)), \
        f"{reader} does not read run_loop_stats()[{key!r}]"


# ------------------------------------------------- the environment's names

ENV_NAMES = {"PONY_TPU_SAFE", "PONY_TPU_DEBUG", "PONY_TPU_RESTORE",
             "PONY_TPU_CHAOS", "PONY_TPU_TUNING_CACHE",
             "PONY_TPU_COMPILE_CACHE_FORCE"}


def test_the_environment_names_are_the_six_the_readme_lists():
    """`PONY_TPU_<OPTION>` (config.options_from_env: one spelling a
    RuntimeOptions field, built from the field's name) is not a name of
    its own; every literal one is."""
    sources = ["chip_smoke.py", "tests/conftest.py"]
    for base, _dirs, files in os.walk(os.path.join(ROOT, "ponyc_tpu")):
        sources += [os.path.relpath(os.path.join(base, f), ROOT)
                    for f in files if f.endswith(".py")]
    found = set()
    for rel in sources:
        found |= set(re.findall(r"PONY_TPU_[A-Z][A-Z_]*", _read(rel)))
    assert found == ENV_NAMES
    readme = _read("README.md")
    assert {n for n in ENV_NAMES if n not in readme} == set()


# ------------------------------------------------------------- the windows

def test_engine_builds_one_window_and_a_runtime_holds_two_programs(
        started):
    from ponyc_tpu.runtime import engine
    assert {n for n in dir(engine) if "multi_step" in n} \
        == {"build_multi_step_gated", "jit_multi_step_gated"}
    jitted = {name for name, value in vars(started).items()
              if hasattr(value, "lower") and hasattr(value, "_cache_size")}
    assert jitted == {"_step", "_multi_g"}
