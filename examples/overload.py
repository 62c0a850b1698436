"""Automatic backpressure under fan-in overload (≙ reference
examples/overload: many Senders flood one Receiver; the runtime mutes
senders instead of letting the mailbox grow without bound).

    python examples/overload.py
"""
import sys

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor,  # noqa
                       behaviour)

N_SENDERS, ITEMS = 64, 50


@actor
class Receiver:
    msgs: I32

    BATCH = 2          # deliberately slower than the senders' aggregate

    @behaviour
    def rush(self, st, v: I32):
        return {**st, "msgs": st["msgs"] + 1}


@actor
class Sender:
    out: Ref[Receiver]
    left: I32

    MAX_SENDS = 2

    @behaviour
    def go(self, st, _: I32):
        self.send(st["out"], Receiver.rush, 1, when=st["left"] > 0)
        self.send(self.actor_id, Sender.go, 0, when=st["left"] > 1)
        return {**st, "left": st["left"] - 1}


def main():
    rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=2, msg_words=1,
                                max_sends=2, spill_cap=4096,
                                inject_slots=64))
    rt.declare(Sender, N_SENDERS).declare(Receiver, 1).start()
    recv = rt.spawn(Receiver)
    senders = rt.spawn_many(Sender, N_SENDERS, out=recv, left=ITEMS)
    rt.bulk_send(senders, Sender.go, np.zeros(N_SENDERS, np.int64))

    peak_muted = 0
    st, inj = rt.state, rt._empty_inject
    st, _ = rt._step(st, *rt._drain_inject())
    steps = 0
    while True:
        st, aux = rt._step(st, *inj)
        steps += 1
        peak_muted = max(peak_muted, int(np.asarray(st.muted).sum()))
        rt.state = st
        if (rt.state_of(recv)["msgs"] == N_SENDERS * ITEMS
                or steps > 20000):
            break
    got = rt.state_of(recv)["msgs"]
    assert got == N_SENDERS * ITEMS, (got, N_SENDERS * ITEMS)
    print(f"receiver got all {got} messages in {steps} ticks; "
          f"peak concurrently-muted senders: {peak_muted}/{N_SENDERS} "
          "(mailbox stayed bounded — no runaway growth)")


if __name__ == "__main__":
    main()
