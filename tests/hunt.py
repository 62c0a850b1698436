#!/usr/bin/env python
"""Extended differential bug hunt — the long-running version of
tests/test_differential.py, run as a one-off (not under pytest):

    python tests/hunt.py [n_seeds] [first_seed] [--fifo|--blob]

--fifo runs the order-sensitive per-edge FIFO marathon (test_fifo.py
scenarios) instead of the commutative-outcome differential; --blob
runs randomized blob-chain worlds (device payload pool: alloc/free
churn per hop, iso moves, cross-shard migration) against the
sequential oracle.

Random world sizes and traffic per seed, rotating configurations
(tiny-cap single chip, cosort, fused kernel, 4/8-shard meshes with tiny
route buckets). Any mismatch against the sequential oracle or failure to
quiesce prints FAIL lines and exits nonzero. The round-3 campaign ran
30 single-chip + 12 mesh seeds clean after fixing the mute-cycle
deadlock this harness found."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ponyc_tpu.platforms import force_cpu  # noqa: E402

force_cpu(8)

import numpy as np  # noqa: E402

from ponyc_tpu import RuntimeOptions  # noqa: E402
import test_differential as td  # noqa: E402

CONFIGS = {
    "tiny": dict(mailbox_cap=2, batch=1, msg_words=1, max_sends=2,
                 spill_cap=2048, inject_slots=16),
    "cosort": dict(mailbox_cap=4, batch=2, msg_words=1, max_sends=2,
                   spill_cap=2048, inject_slots=16, delivery="cosort"),
    "fused": dict(mailbox_cap=4, batch=2, msg_words=1, max_sends=2,
                  spill_cap=2048, inject_slots=16, pallas_fused=True),
    "mesh4": dict(mailbox_cap=2, batch=1, msg_words=1, max_sends=2,
                  spill_cap=4096, inject_slots=64, mesh_shards=4,
                  quiesce_interval=2),
    "mesh8-bucket": dict(mailbox_cap=4, batch=2, msg_words=1,
                         max_sends=2, spill_cap=4096, inject_slots=64,
                         mesh_shards=8, route_bucket=8,
                         quiesce_interval=1),
}


BLOB_CONFIGS = {
    "tiny": dict(mailbox_cap=2, batch=1, max_sends=1, spill_cap=1024,
                 inject_slots=16),
    "cosort": dict(mailbox_cap=4, batch=2, max_sends=1, spill_cap=1024,
                   inject_slots=16, delivery="cosort"),
    "mesh2": dict(mailbox_cap=2, batch=1, max_sends=1, spill_cap=2048,
                  inject_slots=16, mesh_shards=2, quiesce_interval=2),
    "mesh4-bucket": dict(mailbox_cap=2, batch=1, max_sends=1,
                         spill_cap=4096, inject_slots=32, mesh_shards=4,
                         route_bucket=4, quiesce_interval=1),
    "aged": dict(mailbox_cap=2, batch=1, max_sends=1, spill_cap=1024,
                 inject_slots=16, mute_age_limit=2),
}


def _marathon(n_seeds, first, configs, run_seed, label):
    """Shared per-seed driver for the call-one-function marathons
    (fifo/blob): rotate configs, record failures, summarise."""
    fails = []
    t0 = time.time()
    names = list(configs)
    for n, seed in enumerate(range(first, first + n_seeds)):
        cfg = names[n % len(names)]
        try:
            detail = run_seed(seed, cfg, configs[cfg])
        except Exception as e:                  # noqa: BLE001
            fails.append((seed, cfg, repr(e)[:200]))
            detail = ""
        print(f"{label} seed {seed} ({cfg}{detail}): "
              f"{'FAIL' if fails and fails[-1][0] == seed else 'ok'}",
              flush=True)
    print(f"\n{n_seeds - len(fails)}/{n_seeds} {label} ok "
          f"in {time.time() - t0:.0f}s")
    for f in fails:
        print("FAIL:", f)
    return 1 if fails else 0


def main_blob(n_seeds, first):
    """Blob-chain marathon: randomized worlds through td.run_blob_chain
    (alloc/free churn every hop, generation recycling, migration under
    tiny route buckets); any oracle mismatch, leak, or dead arrival
    fails the seed."""
    def run_seed(seed, _cfg, kw):
        td.run_blob_chain(seed, kw)
        return ""
    return _marathon(n_seeds, first, BLOB_CONFIGS, run_seed, "blob")


FIFO_CONFIGS = {
    "tiny": dict(mailbox_cap=2, batch=1, max_sends=3, spill_cap=4096,
                 inject_slots=16),
    "cosort": dict(mailbox_cap=4, batch=2, max_sends=3, spill_cap=4096,
                   inject_slots=16, delivery="cosort"),
    "aged": dict(mailbox_cap=2, batch=1, max_sends=3, spill_cap=4096,
                 inject_slots=16, mute_age_limit=2),
    "fused": dict(mailbox_cap=4, batch=2, max_sends=3, spill_cap=4096,
                  inject_slots=16, pallas_fused=True),
    "mesh4-bucket": dict(mailbox_cap=2, batch=1, max_sends=3,
                         spill_cap=8192, inject_slots=32, mesh_shards=4,
                         route_bucket=8, quiesce_interval=2),
    # blob-bind:* rows run the payload<->message BINDING fifo variant
    # (run_blob_fifo): stamps ride both a word and the blob.
    "blob-bind:tiny": dict(mailbox_cap=2, batch=1, max_sends=2,
                           spill_cap=4096, inject_slots=16),
    "blob-bind:mesh4": dict(mailbox_cap=2, batch=1, max_sends=2,
                            spill_cap=8192, inject_slots=32,
                            mesh_shards=4, route_bucket=4,
                            quiesce_interval=2),
}


def main_fifo(n_seeds, first):
    """Order-sensitive marathon: random fan-in wiring + stream lengths,
    per-edge sequence stamps verified on device (test_fifo.run_fifo) —
    a single FIFO inversion anywhere in delivery/spill/route/aged-unmute
    fails the seed."""
    import test_fifo as tf

    def run_seed(seed, cfg, kw):
        rng = np.random.default_rng(seed)
        n_cons = int(rng.integers(3, 12))
        items = int(rng.integers(20, 90))
        if cfg.startswith("blob-bind:"):
            tf.run_blob_fifo(seed, kw, n_cons=n_cons, items=items)
        else:
            tf.run_fifo(seed, kw, n_cons=n_cons, items=items)
        return f", n_cons={n_cons}, items={items}"
    return _marathon(n_seeds, first, FIFO_CONFIGS, run_seed, "fifo")


def main():
    argv = [a for a in sys.argv[1:] if a not in ("--fifo", "--blob")]
    fifo = "--fifo" in sys.argv[1:]
    blob = "--blob" in sys.argv[1:]
    n_seeds = int(argv[0]) if len(argv) > 0 else 10
    first = int(argv[1]) if len(argv) > 1 else 1000
    if fifo:
        return main_fifo(n_seeds, first)
    if blob:
        return main_blob(n_seeds, first)
    fails = []
    t0 = time.time()
    names = list(CONFIGS)
    for n, seed in enumerate(range(first, first + n_seeds)):
        rng = np.random.default_rng(seed)
        n_w = int(rng.integers(12, 80))
        n_s = int(rng.integers(4, 24))
        w_nxt, s_w, s_s, seeds = td._case(seed, n_w, n_s,
                                          n_seeds=12, vmax=16)
        want = td.oracle(n_w, n_s, w_nxt, s_w, s_s, seeds)
        cfg = names[n % len(names)]
        try:
            got = td.run_device(n_w, n_s, w_nxt, s_w, s_s, seeds,
                                RuntimeOptions(**CONFIGS[cfg]))
            if not all((g == w).all() for g, w in zip(got, want)):
                fails.append((seed, cfg, "MISMATCH"))
        except Exception as e:                  # noqa: BLE001
            fails.append((seed, cfg, repr(e)[:160]))
        print(f"seed {seed} ({cfg}, n_w={n_w}, n_s={n_s}): "
              f"{'FAIL' if fails and fails[-1][0] == seed else 'ok'}",
              flush=True)
    print(f"\n{n_seeds - len(fails)}/{n_seeds} ok "
          f"in {time.time() - t0:.0f}s")
    for f in fails:
        print("FAIL:", f)
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
