"""Producer/consumer with backpressure (≙ examples/producer-consumer +
examples/overload): fast producers flood one consumer; the runtime's
overload → mute → unmute machinery throttles them, nothing is lost."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour


@actor
class Producer:
    sink: Ref
    left: I32

    @behaviour
    def produce(self, st, _: I32):
        go = st["left"] > 0
        self.send(st["sink"], Consumer.consume, st["left"], when=go)
        self.send(self.actor_id, Producer.produce, 0, when=go)
        return {**st, "left": st["left"] - 1}


@actor
class Consumer:
    BATCH = 2                     # deliberately slow drain
    seen: I32

    @behaviour
    def consume(self, st, item: I32):
        return {**st, "seen": st["seen"] + 1}


def main():
    n_prod, items = 8, 200
    rt = Runtime(RuntimeOptions(mailbox_cap=16, batch=8, max_sends=2,
                                msg_words=2, spill_cap=512,
                                inject_slots=64))
    rt.declare(Producer, n_prod).declare(Consumer, 1).start()
    sink = rt.spawn(Consumer)
    prods = rt.spawn_many(Producer, n_prod, sink=int(sink),
                          left=items)
    for p in prods:
        rt.send(int(p), Producer.produce, 0)
    rt.run()
    seen = rt.state_of(sink)["seen"]
    mutes = rt.counter("n_mutes")
    print(f"consumed {seen}/{n_prod * items} "
          f"(mute transitions: {mutes}, rejected→spill: "
          f"{rt.counter('n_rejected')})")
    assert seen == n_prod * items
    sys.exit(0)


if __name__ == "__main__":
    main()
