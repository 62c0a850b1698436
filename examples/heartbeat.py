"""Heartbeat: OS timers driving a device actor (≙ examples/timers).

  python examples/heartbeat.py

The stdlib timer hub (≙ packages/time Timers) arms a native timerfd in
the C++ event loop; each firing becomes an ordinary behaviour message
on a device actor, which accumulates beats and exits the program after
the fifth — the reference's Timer/TimerNotify cancel-after-N pattern.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import (I32, Runtime, RuntimeOptions,  # noqa: E402
                       actor, behaviour)
from ponyc_tpu.stdlib.timers import Timers  # noqa: E402

BEATS = 5


@actor
class Heart:
    beats: I32

    @behaviour
    def beat(self, st, kind: I32, n: I32, flags: I32):
        # Uniform asio event signature: n = coalesced firings.
        total = st["beats"] + n
        self.exit(0, when=total >= BEATS)
        return {**st, "beats": total}


def main() -> int:
    rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=2, msg_words=3,
                                inject_slots=8))
    rt.declare(Heart, 1).start()
    h = rt.spawn(Heart, beats=0)
    timers = Timers(rt)
    timers.timer(int(h), Heart.beat, interval_s=0.05, count=BEATS)
    code = rt.run()                 # exits from the device on beat #5
    beats = rt.state_of(h)["beats"]
    print(f"exit {code} after {beats} heartbeats")
    assert code == 0 and beats >= BEATS, (code, beats)
    # (no dispose needed: a count=N timer self-cancels on its last fire)
    return code


if __name__ == "__main__":
    sys.exit(main())
