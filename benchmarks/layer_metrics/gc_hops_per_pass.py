"""Hops a collector pass took to its fixpoint: the window's growth of
`gc_iters` / of `gc_runs` (`run_loop_stats()`, always on). The
protocol's at a given world, not a lever: the longest chain of `parent`
references from a row that holds a message up to a root, plus the hop
that finds nothing new."""


def read(ctx):
    w = ctx["window"]
    if not w.get("passes"):
        return None
    return w["hops"] / w["passes"]
