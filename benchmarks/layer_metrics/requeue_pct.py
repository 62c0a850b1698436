"""Share of the window's dispatches that set a message aside: the
growth of the accounts' `requeued` (a credit or a debit that found its
account in reply mode and was sent to self again) / that of the
program's `n_processed`."""


def read(ctx):
    p = ctx["window"].get("protocol", {})
    if "requeued" not in p or not p.get("n_processed"):
        return None
    return 100.0 * p["requeued"] / p["n_processed"]
