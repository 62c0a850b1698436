"""Device time a traced tick under the Account cohort's own share of
`dispatch` (`pony/dispatch/cohort/Account`, `state.cohort_scope`): three
behaviours evaluated on every lane of 1,024,000 rows, a batch slot at a
time. None on a program that does not name a cohort's share."""

from benchmarks import phase_trace


def cohort_ms(ctx, type_name: str):
    reduced = phase_trace.of_run(ctx)
    if not reduced or not reduced["scoped"] or not reduced["ticks"]:
        return None
    scope = f"dispatch/cohort/{type_name}"
    found = [rec["s"] for name, rec in reduced["phases"].items()
             if name == scope or name.startswith(scope + "/")]
    return 1e3 * sum(found) / reduced["ticks"] if found else None


def read(ctx):
    return cohort_ms(ctx, "Account")
