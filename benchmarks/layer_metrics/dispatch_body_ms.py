"""Device time a traced tick under the Body cohort's own share of
`dispatch` (`pony/dispatch/cohort/Body`): one `visit` evaluated on every
lane of 1,048,575 rows, the source's force arithmetic on float state.
None on a program that does not name a cohort's share."""

from benchmarks.layer_metrics.dispatch_account_ms import cohort_ms


def read(ctx):
    return cohort_ms(ctx, "Body")
