"""Share of the traced ticks in which the delivery plan was rebuilt:
executions of the sort under `pony/delivery/plan` / traced ticks. The
miss branch is the only place that sort runs."""

from benchmarks import phase_trace


def read(ctx):
    reduced = phase_trace.of_run(ctx)
    if not reduced or not reduced["scoped"] or not reduced["ticks"]:
        return None
    return 100.0 * reduced["plan_sorts"] / reduced["ticks"]
