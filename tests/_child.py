"""The one bounded way a test starts a child process.

A test may wait only where something bounds the wait and says who
waited (tests/README.md). ``subprocess.run(timeout=)`` kills the child
but not what the child started, and then waits without a bound for the
pipes a grandchild still holds. Here every child leads a process group
of its own; on a timeout, and when the caller is done with it, the
GROUP is killed and the child reaped, and a timeout's error names the
command and carries the child's last output.

The child's environment is this process's (conftest's ``force_cpu``
has put ``JAX_PLATFORMS=cpu`` there) without
``JAX_COMPILATION_CACHE_DIR``: a child must not reload what another
run compiled (ROADMAP C8) unless the test hands it the variable.
`env` holds CHANGES to that environment; a value of None removes.
"""

from __future__ import annotations

import contextlib
import os
import signal
import subprocess
import sys
from typing import Dict, Iterator, Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PIPE, STDOUT = subprocess.PIPE, subprocess.STDOUT
# What a CLI child (import jax, one small world) is allowed; like every
# bound passed here it stays under conftest's per-test deadline.
CLI_TIMEOUT_S = 90.0
TAIL = 3000          # characters of the child's output an error carries


class ChildTimeout(Exception):
    """The child outlived its bound; it and its group are dead. The
    message names the command and ends with the child's last output,
    which `stdout` and `stderr` hold whole."""

    def __init__(self, message, stdout, stderr):
        super().__init__(message)
        self.stdout, self.stderr = stdout, stderr


def _env(env: Optional[Dict[str, Optional[str]]]) -> Dict[str, str]:
    out = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    for k, v in (env or {}).items():
        if v is None:
            out.pop(k, None)
        else:
            out[k] = v
    return out


def spawn(argv: Sequence[str], *, env=None, cwd=None,
          **popen_kw) -> subprocess.Popen:
    """Start `argv` as the leader of a new process group (and session).
    The caller owes it a ``kill_group``; ``popen`` pays that by itself."""
    return subprocess.Popen(
        list(argv), env=_env(env), cwd=None if cwd is None else str(cwd),
        start_new_session=True, **popen_kw)


def kill_group(proc: subprocess.Popen, sig: int = signal.SIGKILL) -> None:
    """Signal the child's whole group (the child's pid is the group's
    id: `spawn` made it the leader) and, for SIGKILL, reap the child."""
    try:
        os.killpg(proc.pid, sig)
    except (ProcessLookupError, PermissionError):
        pass                       # nobody of the group is left
    if sig == signal.SIGKILL:
        proc.wait()


@contextlib.contextmanager
def popen(argv: Sequence[str], *, env=None, cwd=None,
          **popen_kw) -> Iterator[subprocess.Popen]:
    """``with _child.popen(argv, stdout=_child.PIPE, text=True) as p:``
    a child for as long as the block runs; whatever happens in the
    block, its group is dead and it is reaped when the block ends."""
    proc = spawn(argv, env=env, cwd=cwd, **popen_kw)
    try:
        yield proc
    finally:
        kill_group(proc)
        for pipe in (proc.stdin, proc.stdout, proc.stderr):
            if pipe is not None:
                pipe.close()


def finish(proc: subprocess.Popen, *,
           timeout: float) -> subprocess.CompletedProcess:
    """Wait at most `timeout` s for a piped child to end and return its
    output. Past the bound: kill the group, raise ChildTimeout."""
    try:
        out, err = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        kill_group(proc)
        try:                       # the group is dead: the pipes drain
            out, err = proc.communicate(timeout=5)
        except subprocess.TimeoutExpired as e:   # held outside the group
            out, err = e.stdout, e.stderr
        out, err = _text(out), _text(err)
        raise ChildTimeout(
            f"{' '.join(map(str, proc.args))!r} still ran after "
            f"{timeout:g} s; killed with its group. Last output:\n"
            f"{out[-TAIL:]}\n{err[-TAIL:]}", out, err) from None
    return subprocess.CompletedProcess(proc.args, proc.returncode, out, err)


def run(argv: Sequence[str], *, timeout: float, env=None,
        cwd=None) -> subprocess.CompletedProcess:
    """Run `argv` to its end with its output captured as text. When it
    returns or raises nothing of the child's group is left running."""
    with popen(argv, env=env, cwd=cwd, text=True, stdout=PIPE,
               stderr=PIPE) as proc:
        return finish(proc, timeout=timeout)


def cli(args: Sequence[str], *, cwd=ROOT, timeout: float = CLI_TIMEOUT_S,
        env=None) -> subprocess.CompletedProcess:
    """``python -m ponyc_tpu <args>`` from `cwd`, the checkout first on
    the child's path."""
    return run([sys.executable, "-m", "ponyc_tpu", *args], timeout=timeout,
               cwd=cwd, env={"PYTHONPATH": ROOT, **(env or {})})


def script(code: str, *, timeout: float = CLI_TIMEOUT_S,
           env=None) -> subprocess.CompletedProcess:
    """``python -c <code>``: the SIGINT / SIGTERM / watchdog children,
    which insert the checkout into ``sys.path`` themselves."""
    return run([sys.executable, "-c", code], timeout=timeout, env=env)


def _text(out) -> str:
    # TimeoutExpired hands partial output over as bytes even in text mode
    return out.decode(errors="replace") if isinstance(out, bytes) \
        else (out or "")
