"""Int-coded errors — ≙ the fork's error machinery
(pony.h:610-665 pony_try/pony_error/pony_error_int/pony_error_code/
pony_error_loc; lang/posix_except.c + except_try_catch.ll underneath).

The fork replaced Pony's bare `error` with errors that carry an int
code and a source location, caught by `try ... else` and queryable via
`__error_code()`. The TPU framework's three surfaces:

- **Host behaviours** raise PonyError(code): the dispatch loop catches
  it, records the code, and the actor continues with its next message —
  exactly a behaviour-local `try ... else` that logs (a Pony behaviour
  cannot leak errors; the unwind stops at the dispatch boundary).
- **Host driver code** uses pony_try() to get the (ok, value_or_code)
  shape of the reference's pony_try (pony.h:610).
- **Device behaviours** call ctx.error_int(code, when=...) — errors are
  values under vmap; the latest code lands in the per-actor
  `last_error` column and the n_errors counter (api.py).

Locations: PonyError captures the raise site (≙ pony_error_loc's
file/line), surfaced in logs and pony_try results.
"""

from __future__ import annotations

import os
import traceback
from typing import Any, Callable, Tuple

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))


def caller_loc(skip_pkg: bool = True) -> str:
    """file:line of the nearest stack frame OUTSIDE the ponyc_tpu
    package (≙ pony_error_loc pointing at user code). Shared by
    PonyError and Context.error_int so raise-site attribution lives
    once — helpers like stdlib Fact/Assert and error_int itself never
    claim the location."""
    for frame in reversed(traceback.extract_stack()):
        fn = os.path.abspath(frame.filename)
        if not skip_pkg or not fn.startswith(_PKG_DIR + os.sep):
            return f"{frame.filename}:{frame.lineno}"
    return "?"


# --- stable runtime error codes (≙ the fork's int-coded errors made a
# runtime-wide contract): every runtime error CLASS carries one fixed
# int, exposed on the exception (`.code`), as the metrics label
# `pony_tpu_errors_total{class=...,code=...}` (metrics.py) and in
# flight-recorder postmortems (flight.py), so operators and alert rules
# match on a number that never drifts with a message rewrite. The table
# is documented in README "Operating it" — codes are append-only. ---
ERROR_CODES = {
    "PonyError": 1,           # behaviour-level error (default user code;
    #   PonyError instances carry their own caller-chosen code)
    "SpillOverflowError": 2,     # runtime.py — bounded spill exceeded
    "SpawnCapacityError": 3,     # runtime.py — device spawn found no slot
    "BlobCapacityError": 4,      # runtime.py — blob pool/budget exhausted
    "CapabilityError": 5,        # hostmem.py — capability discipline
    "VerifyError": 6,            # verify.py — behaviour budget violation
    "PonyStallError": 7,         # this file — watchdog-declared stall
    "SnapshotCorruptError": 8,   # serialise.py — checkpoint failed its
    #   checksum/structure verification (truncated/bit-flipped file)
    "SnapshotFormatError": 9,    # serialise.py — snapshot written by an
    #   unknown FUTURE format version (loud, never a silent drop)
    "SnapshotGeometryError": 10,  # serialise.py — a geometry-changing
    #   restore found occupancy that does not fit the new layout
    "PoisonError": 11,           # supervise.py — deterministic poison:
    #   the same coded error at the same world position twice; the
    #   supervisor refuses to restart-loop on it
    "FrameError": 12,            # serve.py — malformed ingress frame
    #   (bad length prefix / non-word body); doubles as the wire
    #   BADFRAME reply status of the serving front door
    "ServeBusyError": 13,        # serve.py — admission shed at the
    #   edge (overload, drain, or a choked slow-consumer connection);
    #   doubles as the wire BUSY reply status — clients back off
    "ServeDeadlineError": 14,    # serve.py — a request's deadline
    #   expired before the device could serve it; the wire DEADLINE
    #   reply status
}


def error_code(exc) -> int:
    """Stable int code of a runtime exception: the instance's own
    `.code` when it carries one (PonyError), else the class table above
    walked up the MRO; 0 = not a coded runtime error."""
    code = getattr(exc, "code", None)
    if isinstance(code, int):
        return code
    for klass in type(exc).__mro__:
        c = ERROR_CODES.get(klass.__name__)
        if c is not None:
            return c
    return 0


class PonyStallError(RuntimeError):
    """The stall watchdog (flight.py) declared the runtime wedged: a
    run-loop phase (backend init, a dispatched window, host work)
    exceeded its deadline with no progress stamp. Carries the tripped
    phase and the postmortem path the watchdog wrote — the structured
    replacement for the silent forever-hang (ISSUE 7)."""

    code = ERROR_CODES["PonyStallError"]

    def __init__(self, message: str = "", phase: str = "?",
                 postmortem: str = ""):
        super().__init__(message or f"runtime stalled in phase {phase!r}")
        self.phase = phase
        self.postmortem = postmortem


class PonyError(Exception):
    """≙ pony_error_int: an error that is a value with an int code."""

    def __init__(self, code: int = 1, message: str = ""):
        super().__init__(message or f"error {code}")
        self.code = int(code)
        # ≙ pony_error_loc: the nearest user-code raise site (so Fact/
        # Assert and other in-package helpers attribute to their caller).
        self.loc = caller_loc()


def pony_try(fn: Callable, *args, **kw) -> Tuple[bool, Any]:
    """≙ pony_try (pony.h:610): run fn; (True, result) on success,
    (False, error_code) when it raises PonyError."""
    try:
        return True, fn(*args, **kw)
    except PonyError as e:
        return False, e.code


# --- device error-site registry (≙ the fork's __error_loc token,
# DIVERGENCE.md "Retrieve the source location where an error occurred").
# Each trace-time ctx.error_int() call site registers its Python
# file:line here once; the device carries only the i32 site id (+1; 0 =
# no error), and Runtime.last_error_loc() resolves it back to a string —
# the same "C-string table on the side" performance choice the fork
# made for __error_loc.
_device_error_sites: list = ["?"]     # id 0 = no/unknown site


def register_error_site(loc: str) -> int:
    """Intern a trace-time error site, returning its id (>= 1)."""
    try:
        return _device_error_sites.index(loc)
    except ValueError:
        _device_error_sites.append(loc)
        return len(_device_error_sites) - 1


def error_site(site_id: int) -> str:
    """Resolve a site id from the last_error_loc column."""
    if 0 <= site_id < len(_device_error_sites):
        return _device_error_sites[site_id]
    return "?"
