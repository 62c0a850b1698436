"""Rich message payloads on-device: the blob pool end to end.

  python examples/blob_pipeline.py

≙ the reference idiom of shipping `String iso` / `Array[U32] val`
payloads between actors (pony_alloc_msg object graphs): here payloads
live in the DEVICE blob pool and ride messages as capability-checked
handles — no host round-trip per message.

Three stages:
  1. the host stores UTF-8 lines as blobs (`rt.blob_store_str`) and
     sends each to a Tokenizer — an ISO move: the host loses the handle;
  2. each Tokenizer computes a checksum + length from the words, frees
     its input, and publishes ONE frozen summary blob (`blob_freeze`)
     broadcast to BOTH reviewers — a VAL alias, legal for frozen blobs;
  3. Reviewers accumulate from the shared summaries; nobody frees them
     (val has no owner) — `rt.gc()` reclaims the replicas at the end.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import (Blob, BlobVal, I32, Ref, Runtime,  # noqa: E402
                       RuntimeOptions, actor, behaviour)

W = 16          # pool width: up to 64 UTF-8 bytes per line


@actor
class Tokenizer:
    a: Ref["Reviewer"]
    b: Ref["Reviewer"]
    MAX_BLOBS = 1       # one alloc per dispatch...
    BATCH = 2           # ...and up to 2 dispatches per tick reserve
    #   2×1 pool slots per runnable tokenizer (BLOB_DISPATCHES defaults
    #   to BATCH; see docs/MIGRATION.md on sizing)
    MAX_SENDS = 2

    @behaviour
    def take(self, st, line: Blob):
        import jax.numpy as jnp
        ln = self.blob_length(line)
        s = jnp.int32(0)
        for i in range(W):
            s = s + jnp.where(i < ln, self.blob_get(line, i), 0)
        self.blob_free(line)                     # consumed the input
        out = self.blob_alloc(length=2)
        self.blob_set(out, 0, s)                 # checksum
        self.blob_set(out, 1, ln)                # word count
        summary = self.blob_freeze(out)          # shared-immutable now
        self.send(st["a"], Reviewer.review, summary)
        self.send(st["b"], Reviewer.review, summary)   # alias: val
        return st


@actor
class Reviewer:
    checks: I32
    words: I32
    n: I32

    @behaviour
    def review(self, st, summary: BlobVal):
        return {"checks": st["checks"] + self.blob_get(summary, 0),
                "words": st["words"] + self.blob_get(summary, 1),
                "n": st["n"] + 1}


def main():
    lines = ["hello pony", "actors all the way down",
             "payloads live on the device now"]
    rt = Runtime(RuntimeOptions(blob_slots=32, blob_words=W, msg_words=2,
                                max_sends=2))
    rt.declare(Tokenizer, 4).declare(Reviewer, 4).start()
    r1 = rt.spawn(Reviewer, checks=0, words=0, n=0)
    r2 = rt.spawn(Reviewer, checks=0, words=0, n=0)
    tok = rt.spawn(Tokenizer, a=r1, b=r2)
    for text in lines:
        rt.send(tok, Tokenizer.take, rt.blob_store_str(text))
    rt.run()
    s1, s2 = rt.state_of(r1), rt.state_of(r2)
    assert s1 == s2, (s1, s2)            # both saw every shared summary
    print(f"{s1['n']} lines: checksum {s1['checks'] & 0xFFFFFFFF:#x}, "
          f"{s1['words']} payload words")
    print("blobs in use before gc:", rt.blobs_in_use)   # frozen summaries
    rt.gc()
    print("blobs in use after gc: ", rt.blobs_in_use)   # reclaimed
    assert rt.blobs_in_use == 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
