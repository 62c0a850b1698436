"""Device time a traced tick under the Teller cohort's own share of
`dispatch` (`pony/dispatch/cohort/Teller`): a hundred batch slots one
after another on 1,024 rows."""

from benchmarks.layer_metrics.dispatch_account_ms import cohort_ms


def read(ctx):
    return cohort_ms(ctx, "Teller")
