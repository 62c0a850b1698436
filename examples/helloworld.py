"""Hello world (≙ examples/helloworld): one actor, one message."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour


@actor
class Main:
    HOST = True          # prints → host actor (≙ env.out)

    @behaviour
    def create(self, st, _: I32):
        print("Hello, world!")
        self.exit(0)
        return st


def main():
    rt = Runtime(RuntimeOptions(msg_words=1)).declare(Main, 1).start()
    rt.send(rt.spawn(Main), Main.create, 0)
    sys.exit(rt.run())


if __name__ == "__main__":
    main()
