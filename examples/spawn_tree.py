"""Dynamic spawn tree (≙ the reference's pervasive actor-creates-actor
pattern, e.g. examples/circle): each node spawns two children down to a
depth, then counts leaves back up through parent refs."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour


@actor
class Node:
    parent: Ref
    acc: I32
    pending: I32

    SPAWNS = {"Node": 2}
    SPAWN_DISPATCHES = 1      # grow arrives once per node: tight windows
    MAX_SENDS = 3

    @behaviour
    def grow(self, st, depth: I32, parent: Ref):
        leaf = depth <= 0
        a = self.spawn(Node.grow, depth - 1, self.actor_id, when=~leaf)
        b = self.spawn(Node.grow, depth - 1, self.actor_id, when=~leaf)
        self.send(parent, Node.leaf_up, 1, when=leaf)
        return {**st, "parent": parent, "pending": 2}

    @behaviour
    def leaf_up(self, st, n: I32):
        import jax.numpy as jnp
        acc = st["acc"] + n
        pending = st["pending"] - 1
        done = pending == 0
        root = st["parent"] < 0
        self.send(st["parent"], Node.leaf_up, acc, when=done & ~root)
        self.exit(acc, when=done & root)
        return {**st, "acc": jnp.where(done, 0, acc), "pending": pending}


# The host only ever injects grow (main() below); declaring the inject
# site lets `python -m ponyc_tpu lint examples.spawn_tree` run the
# ROOTED rules too — R1 reachability from grow, R2's nothing-spawns-it
# check (Node spawns itself on device, so the program is clean).
LINT_ROOTS = (Node.grow,)


def main():
    depth = 6                     # 2^6 = 64 leaves, 127 nodes
    rt = Runtime(RuntimeOptions(mailbox_cap=8, batch=4, max_sends=3,
                                msg_words=2, inject_slots=8,
                                spill_cap=512))
    rt.declare(Node, 256).start()
    root = rt.spawn(Node)
    rt.send(root, Node.grow, depth, -1)
    code = rt.run(max_steps=10000)
    print(f"leaves counted: {code} (expected {2**depth}); "
          f"spawned {rt.counter('n_spawned')} actors on device")
    assert code == 2 ** depth
    sys.exit(0)


if __name__ == "__main__":
    main()
