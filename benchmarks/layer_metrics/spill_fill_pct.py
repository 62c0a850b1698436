"""How full the receiver spill is after the window's last tick:
`dspill_count` / `spill_cap`. At 100 the next rejection is fatal."""


def read(ctx):
    w = ctx["window"]
    if "spill_entries" not in w:
        return None
    return 100.0 * w["spill_entries"] / w["spill_cap"]
