"""Interactive line editor — ≙ the reference's term package demo
(packages/term: ANSITerm + Readline over stdin).

Type lines with full editing (arrows, home/end, ctrl-a/e/k/u,
history via up/down, tab completion over a few commands); each line is
echoed back by a HOST actor. Ctrl-D or `quit` exits.

Run without a terminal (CI, pipes) and it feeds itself a scripted
session instead, exercising the same code path.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.stdlib.term import (ANSITerm, Readline,  # noqa: E402
                                   ReadlineNotify, attach_stdin)

COMMANDS = ["help", "history", "quit"]


@actor
class Echo:
    HOST = True
    lines: I32

    @behaviour
    def line(self, st, n: I32):
        print(f"echo #{n}: {LINES[n]}")
        return {**st, "lines": st["lines"] + 1}


LINES = {}          # line number → text (host-side payload table)


class Shell(ReadlineNotify):
    def __init__(self, rt, echo_id, term_holder):
        self.rt = rt
        self.echo_id = echo_id
        self.term_holder = term_holder
        self.n = 0

    def apply(self, line, prompt):
        if line == "quit":
            prompt.reject("bye")
            self.rt.request_exit(0)
            return
        LINES[self.n] = line
        self.rt.send(self.echo_id, Echo.line, self.n)
        self.n += 1
        prompt.fulfil("edit> ")

    def tab(self, line):
        return [c for c in COMMANDS if c.startswith(line)]


def main():
    rt = Runtime(RuntimeOptions(msg_words=1)).declare(Echo, 1).start()
    echo = rt.spawn(Echo)
    holder = {}
    shell = Shell(rt, echo, holder)
    rl = Readline(shell, sys.stdout)
    term = ANSITerm(rl, sys.stdout)
    holder["term"] = term

    if sys.stdin.isatty():
        attach_stdin(rt, term)
        term.prompt("edit> ")
        rt.run()
    else:
        # Scripted session: same byte path as a real tty.
        term.prompt("edit> ")
        term.apply(b"helo\x1b[Dl\x01X\x7f\x05!\n")   # edits -> "hello!"
        term.apply(b"h\t")                           # completes "help"? no:
        term.apply(b"\x15")                          # ambiguous; kill line
        term.apply(b"his\tory extra\n")              # no unique completion
        term.apply(b"\x1b[A\n")                      # history repeat
        term.apply(b"quit\n")
        rt.run(max_steps=2000)
    print(f"\nsession over: {rt.state_of(echo)['lines']} lines echoed")


if __name__ == "__main__":
    main()
