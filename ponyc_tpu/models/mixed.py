"""Mixed-behaviour ubench: the dispatch-heterogeneity stressor.

≙ the reference's mixed workload benchmark (`examples/mixed/main.pony`
runs rings + workers + mailboxes concurrently) reduced to the variable
that matters on TPU: BEHAVIOUR COUNT per type. The generated dispatch
switch costs one indirect jump regardless of how many behaviours a type
has (src/libponyc/codegen/genfun.c); the planar dispatch evaluates
every behaviour of a cohort per batch slot (engine.py scan_body), so a
B-behaviour type pays ~B× — this model is the world that shows that
cliff.

One cohort of N workers; behaviour k bumps a counter and forwards to
the next worker's behaviour (k+1) % B, so sustained traffic exercises
every behaviour every tick (the all-hot worst case). `hot=1` builds the
other extreme: traffic stays on behaviour 0 (one-hot).
"""

from __future__ import annotations

import numpy as np

from .. import F32, I32, Ref, Runtime, RuntimeOptions
from ..api import ActorTypeMeta, BehaviourDef


def make_worker_type(n_behaviours: int, hot: int | None = None,
                     work: int = 0):
    """Build a Worker actor type with `n_behaviours` behaviours
    step0..step{B-1}; each forwards to the target's next behaviour
    (or always step0 when hot=1 traffic is requested at seed time).
    `work` > 0 adds that many dependent fma rounds to each behaviour
    body — the heavy-body case where the planar O(B) evaluation term
    actually shows (trivial bodies are swamped by delivery)."""
    ns = {"__annotations__": {"next_ref": Ref, "done": I32, "acc": F32},
          "MAX_SENDS": 1}
    defs = {}                    # name → BehaviourDef (closed over below)

    def mk(k: int):
        nxt = k + 1 if k + 1 < n_behaviours else 0
        if hot == 1:
            nxt = 0

        def step(self, st, n: I32):
            # Forward to the NEXT behaviour id of the next worker —
            # round-robin over all B behaviours (all-hot), or pinned to
            # step0 (one-hot). `self` is the trace Context; the target
            # BehaviourDef comes from the enclosing defs map.
            self.send(st["next_ref"], defs[f"step{nxt}"], n - 1,
                      when=n > 0)
            acc = st["acc"]
            # Dependent NON-affine chain, distinct per behaviour (the
            # k-term): an affine chain with constant coefficients folds
            # to one fma and identical bodies CSE across branches —
            # measured flat, round 5 — so a heavy-body probe must be
            # neither.
            for _ in range(work):
                acc = acc + 1.0 / (acc * acc + 2.0 + k)
            return {**st, "done": st["done"] + 1, "acc": acc}

        step.__name__ = f"step{k}"
        return BehaviourDef(step)

    for k in range(n_behaviours):
        ns[f"step{k}"] = mk(k)
    cls = ActorTypeMeta(
        f"Worker{n_behaviours}" + ("H" if hot == 1 else ""), (), ns)
    for k in range(n_behaviours):
        defs[f"step{k}"] = getattr(cls, f"step{k}")
    return cls


def build(n_workers: int, n_behaviours: int,
          opts: RuntimeOptions | None = None, pings: int = 1,
          hot: int | None = None, seed: int = 0, work: int = 0):
    opts = opts or RuntimeOptions(mailbox_cap=max(4, pings), batch=pings,
                                  max_sends=1, msg_words=1)
    wt = make_worker_type(n_behaviours, hot=hot, work=work)
    rt = Runtime(opts)
    rt.declare(wt, n_workers)
    rt.start()
    ids = rt.spawn_many(wt, n_workers)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n_workers)
    nxt = np.empty(n_workers, np.int64)
    nxt[order] = ids[np.roll(order, -1)]
    rt.set_fields(wt, ids, next_ref=nxt)
    return rt, ids, wt


def seed_all(rt: Runtime, ids, wt, hops: int, pings: int = 1,
             mix: bool = False):
    """Default seeding puts every token on step0 → the round-robin wave
    stays PHASE-SYNCHRONIZED (each tick all lanes carry one behaviour
    id). mix=True spreads lanes across all B behaviours → every tick
    carries every id."""
    steps = [getattr(wt, f"step{k}")
             for k in range(len(wt.behaviour_defs))]
    for _ in range(pings):
        if not mix:
            rt.bulk_send(ids, wt.step0, np.full(len(ids), hops, np.int64))
            continue
        ids_a = np.asarray(ids)
        for k, bd in enumerate(steps):
            sel = ids_a[k::len(steps)]
            if len(sel):
                rt.bulk_send(sel, bd, np.full(len(sel), hops, np.int64))
