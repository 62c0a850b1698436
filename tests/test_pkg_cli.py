"""Safe-package capability control (stdlib/pkg.py ≙ package.c
safe-packages / allow_ffi) and the unified CLI driver (__main__.py ≙
src/ponyc/main.c)."""

import os

import pytest

import _child
from ponyc_tpu.stdlib import pkg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def teardown_function(_fn):
    pkg.set_safe_packages(None)
    os.environ.pop("PONY_TPU_SAFE", None)


def test_use_resolves_known_packages():
    js = pkg.use("json")
    assert hasattr(js, "JsonDoc")
    col = pkg.use("collections")
    assert col is pkg.use("collections")


def test_use_unknown_package_errors():
    with pytest.raises(ImportError, match="unknown package"):
        pkg.use("nonexistent")


def test_safe_list_blocks_unlisted_ffi_packages():
    pkg.set_safe_packages(["files"])
    pkg.use("files")                       # listed: ok
    pkg.use("json")                        # pure: always ok
    with pytest.raises(PermissionError, match="safe list"):
        pkg.use("net")
    with pytest.raises(PermissionError, match="safe list"):
        pkg.use("process")


def test_empty_safe_list_is_maximal_restriction():
    pkg.set_safe_packages([])
    with pytest.raises(PermissionError):
        pkg.use("term")
    pkg.use("itertools")                   # pure packages unaffected


def test_unrestricted_by_default():
    assert pkg.safe_packages() is None
    pkg.use("net")
    pkg.use("files")


def test_env_var_activates_restriction():
    os.environ["PONY_TPU_SAFE"] = "net"
    try:
        pkg.use("net")
        with pytest.raises(PermissionError):
            pkg.use("files")
    finally:
        os.environ.pop("PONY_TPU_SAFE")


def test_cli_version():
    r = _child.cli(["version"])
    assert r.returncode == 0 and "ponyc_tpu" in r.stdout


def test_cli_unknown_command():
    r = _child.cli(["frobnicate"])
    assert r.returncode == 2 and "unknown command" in r.stderr


def test_cli_run_strips_runtime_flags():
    r = _child.cli(["run", "examples/helloworld.py", "--ponybatch=4"])
    assert r.returncode == 0, r.stderr[-800:]
    assert "Hello, world!" in r.stdout
    assert "--ponybatch" not in r.stdout


def test_cli_run_safe_flag_reaches_program(tmp_path):
    script = tmp_path / "prog.py"
    script.write_text(
        "from ponyc_tpu.stdlib import pkg\n"
        "pkg.use('files')\n"
        "try:\n"
        "    pkg.use('net')\n"
        "    print('NET_ALLOWED')\n"
        "except PermissionError:\n"
        "    print('NET_BLOCKED')\n")
    r = _child.cli(["run", "--safe", "files", str(script)])
    assert r.returncode == 0, r.stderr[-800:]
    assert "NET_BLOCKED" in r.stdout


def test_cli_run_safe_equals_form(tmp_path):
    script = tmp_path / "p.py"
    script.write_text(
        "from ponyc_tpu.stdlib import pkg\n"
        "try:\n"
        "    pkg.use('net'); print('NET_ALLOWED')\n"
        "except PermissionError:\n"
        "    print('NET_BLOCKED')\n")
    r = _child.cli(["run", f"--safe=files", str(script)])
    assert r.returncode == 0, r.stderr[-500:]
    assert "NET_BLOCKED" in r.stdout


def test_cli_run_safe_missing_value_is_usage_error():
    r = _child.cli(["run", "x.py", "--safe"])
    assert r.returncode == 2 and "--safe needs a value" in r.stderr


def test_cli_run_flags_only_is_usage_error():
    r = _child.cli(["run", "--ponybatch", "4"])
    assert r.returncode == 2 and "missing script path" in r.stderr


def test_cli_doc_generates_markdown(tmp_path):
    r = _child.cli(["doc", "ponyc_tpu.models.ring", "-o", str(tmp_path)])
    assert r.returncode == 0, r.stderr[-500:]
    out = r.stdout.strip()
    assert os.path.exists(out)
    with open(out) as f:
        assert "RingNode" in f.read()
