"""Spreader: device-side actor tree fan-out, promise-joined completion.

  python examples/spreader.py [depth]

≙ the reference's examples/spreader (each actor spawns two children
until the countdown ends; leaves report back) — here the tree spawns
ON DEVICE (`ctx.spawn`, reservation windows), the leaf count funnels
into a HOST collector actor, and the host waits on a stdlib Promise it
fulfils — the promises package doing the reference's env.out "done"
signalling.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions,  # noqa: E402
                       actor, behaviour)
from ponyc_tpu.stdlib.promises import Promise  # noqa: E402


def main(depth: int = 6) -> int:
    expect = 1 << depth
    done = Promise()            # fulfilled by the HOST actor below

    # Host behaviours run real Python, so the collector can close over
    # the promise and fulfil it from inside the actor world — the
    # promises idiom: the ACTOR resolves, the host blocks on value(),
    # which drives the runtime while waiting (stdlib/promises.py).
    @actor
    class Collect:
        HOST = True
        got: I32

        @behaviour
        def leaf(self, st, n: I32):
            total = st["got"] + n
            if total >= expect:
                done.fulfil(total)
            return {**st, "got": total}

    @actor
    class Spread:
        col: Ref["Collect"]

        SPAWNS = {"Spread": 2}
        SPAWN_DISPATCHES = 1   # go() arrives once per actor: one
        #   spawning dispatch per tick keeps each frontier actor's
        #   reservation window at 2 slots (program._resolve_spawns on
        #   the static worst-case price)
        MAX_SENDS = 5       # 2 constructor sends + 2 go + 1 leaf report

        @behaviour
        def go(self, st, level: I32):
            leaf = level <= 0
            # Children get the collector ref through their constructor
            # message (FIFO per sender pair: init lands before go).
            a = self.spawn(Spread.init, st["col"], when=~leaf)
            b = self.spawn(Spread.init, st["col"], when=~leaf)
            self.send(a, Spread.go, level - 1, when=~leaf)
            self.send(b, Spread.go, level - 1, when=~leaf)
            self.send(st["col"], Collect.leaf, 1, when=leaf)
            return st

        @behaviour
        def init(self, st, c: Ref["Collect"]):
            return {**st, "col": c}

    rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=2, max_sends=5,
                                msg_words=2, spill_cap=4096,
                                inject_slots=8))
    done.rt = rt
    rt.declare(Spread, 4 * expect).declare(Collect, 1).start()
    col = rt.spawn(Collect, got=0)
    root = rt.spawn(Spread, col=int(col))
    rt.send(root, Spread.go, depth)
    got = done.value(timeout=120)   # drives rt.run() until fulfilled
    print(f"depth {depth}: {got} leaves (expected {expect})")
    assert got == expect, (got, expect)
    return 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 6))
