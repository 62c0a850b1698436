"""Programmatic backpressure (≙ reference examples/under_pressure):
a producer floods a slow TCP-like sink; the sink declares pressure via
the backpressure package when its internal buffer backs up, muting the
producer until it drains and releases.

    python examples/under_pressure.py
"""
import sys

sys.path.insert(0, ".")

import numpy as np  # noqa: E402

from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor,  # noqa
                       behaviour)
from ponyc_tpu.stdlib import backpressure as bp  # noqa: E402


@actor
class SlowSink:
    """Stands in for the reference's TCPConnection whose socket stalls:
    the runtime can't see its external buffer, so the HOST applies
    pressure on its behalf (≙ Backpressure.apply in TCPConnectionNotify
    throttled callback)."""
    got: I32

    BATCH = 1

    @behaviour
    def data(self, st, v: I32):
        return {**st, "got": st["got"] + 1}


@actor
class Send:
    """≙ the Send TimerNotify: keeps sending chunks until told to stop."""
    out: Ref[SlowSink]
    sent: I32

    MAX_SENDS = 2

    @behaviour
    def tick(self, st, n: I32):
        self.send(st["out"], SlowSink.data, n)
        self.send(self.actor_id, Send.tick, n + 1)
        return {**st, "sent": st["sent"] + 1}


def main():
    rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=1, msg_words=1,
                                max_sends=2, spill_cap=256,
                                inject_slots=8))
    rt.declare(Send, 1).declare(SlowSink, 1).start()
    sink = rt.spawn(SlowSink)
    sender = rt.spawn(Send, out=sink)
    rt.send(sender, Send.tick, 0)

    auth = bp.ApplyReleaseBackpressureAuth(rt.ambient_auth())
    st, inj = rt.state, rt._empty_inject
    st, _ = rt._step(st, *rt._drain_inject())
    phase = []
    for step in range(40):
        st, aux = rt._step(st, *inj)
        rt.state = st
        muted = bool(np.asarray(st.muted)[sender])
        if step == 9:
            bp.apply(auth, sink)    # the "socket stalled" moment
            st = rt.state           # pick up the pressured column
            phase.append(f"step {step}: pressure APPLIED")
        if step == 29:
            bp.release(auth, sink)  # drained: release
            st = rt.state
            phase.append(f"step {step}: pressure RELEASED")
        if step in (8, 15, 35):
            phase.append(f"step {step}: sender muted={muted}, "
                         f"sink got={rt.state_of(sink)['got']}")
    for line in phase:
        print(line)
    assert bool(np.asarray(rt.state.muted)[sender]) is False
    g1 = rt.state_of(sink)["got"]
    print(f"done: sink received {g1} chunks; sender muted while "
          "pressured, released after")


if __name__ == "__main__":
    main()
