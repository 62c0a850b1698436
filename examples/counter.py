"""Counter (≙ examples/counter): N device actors accumulate increments;
a final query behaviour reports the total via a host actor."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import (I32, Ref, Runtime, RuntimeOptions, actor,  # noqa
                       behaviour, options_from_env)


@actor
class Counter:
    count: I32

    @behaviour
    def increment(self, st, by: I32):
        return {**st, "count": st["count"] + by}

    @behaviour
    def report(self, st, to: Ref):
        self.send(to, Reporter.result, st["count"])
        return st


@actor
class Reporter:
    HOST = True
    seen: I32
    expected: I32

    @behaviour
    def result(self, st, count: I32):
        total = st["seen"] + count
        print(f"partial={count} running_total={total}")
        self.exit(0, when=total >= st["expected"])
        return {**st, "seen": total}


def main():
    n, incs = 8, 100
    # options_from_env so `python -m ponyc_tpu run examples/counter.py
    # --ponyanalysis=2` (or any --pony* flag) reaches this runtime —
    # the profiler smoke test drives the example exactly that way.
    rt = Runtime(options_from_env(RuntimeOptions(
        msg_words=2, inject_slots=256, batch=16)))
    rt.declare(Counter, n).declare(Reporter, 1).start()
    counters = rt.spawn_many(Counter, n)
    rep = rt.spawn(Reporter, expected=n * incs)
    for c in counters:
        for _ in range(incs // 4):
            rt.send(int(c), Counter.increment, 4)
    rt.run()                      # drain increments
    for c in counters:
        rt.send(int(c), Counter.report, rep)
    code = rt.run()
    rt.stop()     # analysis summary + writer-thread flush (≙ pony_stop)
    print("exit:", code)
    sys.exit(code)


if __name__ == "__main__":
    main()
