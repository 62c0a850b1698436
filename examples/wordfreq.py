"""Word-frequency tool: the stdlib packages working together.

  python examples/wordfreq.py count --top=3 "the quick the lazy the dog"
  python examples/wordfreq.py help

cli parses the command line (≙ packages/cli), a fan-out of Counter
actors tallies shards of the word list on device, and json renders the
result (≙ packages/json). The aggregation itself is the fan-in pattern
(≙ examples/fan-in) running on the actor runtime.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import I32, Runtime, RuntimeOptions, actor, behaviour
from ponyc_tpu.stdlib.cli import (ArgSpec, CliSyntaxError, CommandHelp,
                                  CommandParser, CommandSpec, OptionSpec)
from ponyc_tpu.stdlib.itertools import Iter
from ponyc_tpu.stdlib.json import JsonArray, JsonDoc, JsonObject


@actor
class Tally:
    """One actor per distinct word; counts arrivals (device-side)."""
    hits: I32

    @behaviour
    def hit(self, st, _: I32):
        return {**st, "hits": st["hits"] + 1}


def build_spec() -> CommandSpec:
    spec = CommandSpec.parent("wordfreq", "Count word frequencies")
    spec.add_command(CommandSpec.leaf("count", "Count words", options=[
        OptionSpec.i64("top", "How many top words to print", short="t",
                       default=10),
        OptionSpec.bool("pretty", "Pretty-print the JSON", short="p",
                        default=False),
    ], args=[ArgSpec.string("text", "Text to analyse")]))
    spec.add_help()
    return spec


def main(argv):
    cmd = CommandParser(build_spec()).parse(argv)
    if isinstance(cmd, CliSyntaxError):
        print(cmd.string(), file=sys.stderr)
        return 1
    if isinstance(cmd, CommandHelp):
        print(cmd.help_string())
        return 0

    words = cmd.arg("text").split()
    vocab = sorted(set(words))
    index = {w: i for i, w in enumerate(vocab)}

    rt = Runtime(RuntimeOptions(mailbox_cap=64, batch=16, max_sends=1,
                                msg_words=1, spill_cap=1024,
                                inject_slots=256))
    rt.declare(Tally, max(1, len(vocab))).start()
    ids = rt.spawn_many(Tally, len(vocab))
    for w in words:
        rt.send(int(ids[index[w]]), Tally.hit, 0)
    rt.run()

    hits = rt.cohort_state(Tally)["hits"]
    ranked = (Iter(vocab).enum()
              .map(lambda iw: (iw[1], int(hits[iw[0]])))
              .collect())
    ranked.sort(key=lambda p: (-p[1], p[0]))
    doc = JsonDoc()
    doc.data = JsonObject({
        "total": len(words),
        "distinct": len(vocab),
        "top": JsonArray([
            JsonObject({"word": w, "count": c})
            for w, c in ranked[:cmd.option("top")]]),
    })
    print(doc.string(indent="  ", pretty_print=cmd.option("pretty")))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
