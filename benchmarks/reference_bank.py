"""Plain reference for the bank world: NumPy only, no engine, no JAX.

Savina's Bank Transaction (Imam & Sarkar, AGERE! 2014;
`benchmarks/banking`, the `ManualStash` account), as the configuration
file states it. Savina's names are kept although they read backwards: a
*credit* takes money out of the account that receives it.

  Teller (one a bank)
    issue            generateWork()                      [set-up's seeding]
    reply            completed += 1; generateWork()      [one for one]
    generateWork()   three draws of the teller's xorshift32:
                     src    = draw % sources             (the first 80%)
                     loop   = draw % (A - src); 0 -> 1
                     amount = 1 + draw % amount_max
                     send account[src].credit(amount, account[src + loop])
  Account (balance; waiting = in reply mode)
    credit(amount, recipient)   balance -= amount; recipient.debit(self,
                                amount); waiting = true
    debit(sender, amount)       balance += amount; sender.reply()
    reply()                     teller.reply(); waiting = false
    while waiting               every credit and debit is set aside: sent
                                to self again at once, unchanged

Two references decide `correct`, and neither imports the program:

  Ticks       the protocol tick by tick on every actor, for the first
              ticks of a run and for the tier-1 tests;
  invariant   what must hold after ANY number of ticks, order-free,
              from the system's own state (and `stranded`, from two
              states a tick apart: nobody stays muted behind a teller
              that has recovered).

One tick, as the engine states it for one shard (Pony's rules):

  1. unmute    a muted account is released when its teller's mailbox
               held at most `unmute_occ` messages at the start of the
               tick;
  2. dispatch  every actor that is not muted handles `min(queued,
               BATCH)` messages from the front of its mailbox, one
               after another (an account that handles a credit in slot
               0 sets aside the debit in slot 1);
  3. deliver   the tick's sends join their targets' mailboxes in
               emission order: the sender's cohort (tellers first), then
               the batch slot the send was made in, then the send site
               (0: on, 1: to self again), then the sender's row. No
               mailbox may fill: a teller's ring holds everything its
               bank has out and an account's a quarter of that, and
               `Ticks` raises if one would overflow;
  4. mute      the sender of every arrival at a mailbox that now holds
               more than its overload line is muted (an account that
               replied to a swamped teller). A muted account does not
               run. Only a teller may be that full: `Ticks` raises on
               an account that is.

A mailbox's depth and its two lines are its cohort's own: `teller_ring`
and `account_ring` are (capacity, overload line, unmute line).
"""

from __future__ import annotations

import numpy as np

MASK32 = 0xFFFFFFFF
ISSUE, T_REPLY, CREDIT, DEBIT, A_REPLY = range(5)
ACCOUNT_FIELDS = ("balance", "waiting", "n_out", "out_sum", "n_in", "in_sum",
                  "requeued", "forwarded", "stray", "violations")
TELLER_FIELDS = ("issued", "completed", "rng")


def xorshift32(x: np.ndarray) -> np.ndarray:
    """Marsaglia's 13 / 17 / 5 generator on uint32 lanes."""
    x = x ^ (x << np.uint32(13))
    x = x ^ (x >> np.uint32(17))
    x = x ^ (x << np.uint32(5))
    return x


def signed_mod(x: np.ndarray, n) -> np.ndarray:
    """`x % n` as the device computes it: x read as a signed 32-bit
    word, floor modulo."""
    return np.mod(x.astype(np.uint32).view(np.int32).astype(np.int64), n)


def generate_work(rng: np.ndarray, accounts: int, sources: int,
                  amount_max: int):
    """Savina's generateWork() on every lane: (rng after three draws,
    src, dest, amount); dest > src always."""
    a = xorshift32(np.asarray(rng, np.uint32))
    b = xorshift32(a)
    c = xorshift32(b)
    src = signed_mod(a, sources)
    loop = np.maximum(signed_mod(b, accounts - src), 1)
    return c, src, src + loop, 1 + signed_mod(c, amount_max)


def sources_of(accounts: int, source_share: float) -> int:
    """Savina's `(A / 10) * 8` for a share of 0.8, in integers."""
    sources = (accounts // 10) * round(10 * source_share)
    if not 1 <= sources < accounts:
        raise ValueError(f"{accounts} accounts a bank leave no source with "
                         "a destination above it")
    return sources


def _rank(sorted_keys: np.ndarray) -> np.ndarray:
    """Each element's place within its run of equal keys."""
    idx = np.arange(len(sorted_keys))
    first = np.ones(len(sorted_keys), bool)
    first[1:] = sorted_keys[1:] != sorted_keys[:-1]
    return idx - np.maximum.accumulate(np.where(first, idx, 0))


class Ticks:
    """The protocol of the module docstring, one tick at a time. Actors
    are numbered tellers first (bank b is actor b), then accounts (bank
    b's account i is `banks + b * accounts + i`). Everything observable
    is an attribute: the `ACCOUNT_FIELDS` per account, the
    `TELLER_FIELDS` per teller, `muted` per account, the counter
    `n_mutes`; the queue is the flat list `q_*`, sorted by target and
    then by age.

    `script`, for a case worked by hand: [banks, n, 3] transactions
    (src, dest, amount) a teller issues in order in place of its draws.
    """

    def __init__(self, banks: int, accounts: int, *, sources: int,
                 amount_max: int, in_flight: int, teller_batch: int,
                 account_batch: int, teller_ring, account_ring,
                 balance0: int, rng0, script=None,
                 lax_credit: bool = False):
        self.banks, self.accounts = banks, accounts
        self.sources, self.amount_max = sources, amount_max
        self.in_flight = in_flight
        self.lax_credit = lax_credit
        self.n_acc = banks * accounts
        # capacity and overload line per actor; the unmute line that
        # releases an account is its teller's
        self.cap, self.overload_occ = (
            np.concatenate([np.full(banks, t, np.int64),
                            np.full(self.n_acc, a, np.int64)])
            for t, a in zip(teller_ring[:2], account_ring[:2]))
        self.unmute_occ = teller_ring[2]
        self.batch = np.concatenate([np.full(banks, teller_batch, np.int64),
                                     np.full(self.n_acc, account_batch,
                                             np.int64)])
        self.balance = np.full(self.n_acc, balance0, np.int64)
        for name in ACCOUNT_FIELDS[1:]:
            setattr(self, name, np.zeros(self.n_acc, np.int64))
        self.issued = np.zeros(banks, np.int64)
        self.completed = np.zeros(banks, np.int64)
        self.rng = np.asarray(rng0, np.uint32).copy()
        self.script = None if script is None else np.asarray(script, np.int64)
        self.muted = np.zeros(self.n_acc, bool)
        self.n_mutes = self.ticks = 0
        # set-up's seeding: `in_flight` issues in every teller's mailbox
        self.q_tgt = np.repeat(np.arange(banks, dtype=np.int64), in_flight)
        self.q_kind = np.full(len(self.q_tgt), ISSUE, np.int64)
        self.q_w = np.zeros((len(self.q_tgt), 2), np.int64)

    # ---- what one dispatch does, on every actor that has one in slot j
    def _work(self, tellers: np.ndarray):
        """(targets, payloads) of the credits `tellers` send now."""
        if self.script is not None:
            tx = self.script[tellers, self.issued[tellers]]
            src, dest, amount = tx[:, 0], tx[:, 1], tx[:, 2]
        else:
            self.rng[tellers], src, dest, amount = generate_work(
                self.rng[tellers], self.accounts, self.sources,
                self.amount_max)
        self.issued[tellers] += 1
        first = self.banks + tellers * self.accounts
        return first + src, np.stack([amount, first + dest], axis=1)

    def _dispatch(self, tgt, kind, w, slot):
        """Handle one message an actor (`tgt` distinct); returns the
        sends as (target, kind, payload, emission key)."""
        out = []

        def send(rows, to, what, payload, site):
            # emission order: cohort, batch slot, send site, sender's row
            cohort = (rows >= self.banks).astype(np.int64)
            key = ((cohort * 4096 + slot) * 2 + site) * (1 << 32) + rows
            out.append((to, np.full(len(rows), what, np.int64), payload, key))

        teller = tgt < self.banks
        if teller.any():
            rows = tgt[teller]
            self.completed[rows] += kind[teller] == T_REPLY
            to, payload = self._work(rows)
            send(rows, to, CREDIT, payload, 0)
        rows = tgt[~teller]
        if len(rows):
            a = rows - self.banks
            kind_a, w_a = kind[~teller], w[~teller]
            busy = self.waiting[a] != 0
            moves = (kind_a == CREDIT) | (kind_a == DEBIT)
            go = moves & (~busy | (self.lax_credit & (kind_a == CREDIT)))
            self.violations[a] += go & busy
            aside = moves & ~go
            self.requeued[a] += aside
            send(rows[aside], rows[aside], kind_a[aside], w_a[aside], 1)
            cr, db = go & (kind_a == CREDIT), go & (kind_a == DEBIT)
            amount = np.where(cr, w_a[:, 0], w_a[:, 1])
            self.balance[a] += np.where(db, amount, 0) - np.where(cr, amount, 0)
            self.n_out[a] += cr
            self.out_sum[a] += np.where(cr, amount, 0)
            self.n_in[a] += db
            self.in_sum[a] += np.where(db, amount, 0)
            self.waiting[a] = np.where(cr, 1, self.waiting[a])
            send(rows[cr], w_a[cr, 1], DEBIT,
                 np.stack([rows[cr], w_a[cr, 0]], axis=1), 0)
            send(rows[db], w_a[db, 0], A_REPLY,
                 np.zeros((int(db.sum()), 2), np.int64), 0)
            rp = kind_a == A_REPLY
            fwd = rp & busy
            self.forwarded[a] += fwd
            self.stray[a] += rp & ~busy
            self.waiting[a] = np.where(rp, 0, self.waiting[a])
            send(rows[fwd], a[fwd] // self.accounts, T_REPLY,
                 np.zeros((int(fwd.sum()), 2), np.int64), 0)
        return out

    def queued(self) -> np.ndarray:
        """Messages waiting in every actor's mailbox."""
        return np.bincount(self.q_tgt, minlength=self.banks + self.n_acc)

    def tick(self) -> None:
        banks = self.banks
        occ0 = self.queued()
        # 1. unmute, on what the tick starts with
        bank_of = np.arange(self.n_acc) // self.accounts
        self.muted &= occ0[bank_of] > self.unmute_occ
        # 2. dispatch: the front `batch` of every mailbox that may run
        rank = _rank(self.q_tgt)
        runs = np.ones(banks + self.n_acc, bool)
        runs[banks:] = ~self.muted
        taken = (rank < self.batch[self.q_tgt]) & runs[self.q_tgt]
        sent = []
        for j in range(int(rank[taken].max(initial=-1)) + 1):
            now = taken & (rank == j)
            sent += self._dispatch(self.q_tgt[now], self.q_kind[now],
                                   self.q_w[now], j)
        keep = ~taken
        # 3. deliver, in emission order behind what was waiting
        if sent:
            tgt, kind, w, key = (np.concatenate(x) for x in zip(*sent))
            order = np.argsort(key, kind="stable")
            tgt, kind, w = tgt[order], kind[order], w[order]
            senders = key[order] & MASK32
        else:
            tgt = kind = senders = np.zeros(0, np.int64)
            w = np.zeros((0, 2), np.int64)
        q_tgt = np.concatenate([self.q_tgt[keep], tgt])
        q_kind = np.concatenate([self.q_kind[keep], kind])
        q_w = np.concatenate([self.q_w[keep], w])
        order = np.argsort(q_tgt, kind="stable")
        self.q_tgt, self.q_kind, self.q_w = q_tgt[order], q_kind[order], \
            q_w[order]
        occ = self.queued()
        if (occ > self.cap).any():
            raise RuntimeError(
                f"tick {self.ticks + 1}: a mailbox would hold "
                f"{int((occ - self.cap).max())} messages more than its "
                "ring: this deployment never rejects, and the reference "
                "does not model it")
        # 4. mute: who sent to a mailbox that is now over the line
        hot = occ > self.overload_occ
        if hot[banks:].any():
            raise RuntimeError("an account is over the overload line: the "
                               "reference models a swamped teller only")
        now_muted = np.zeros(self.n_acc, bool)
        now_muted[senders[hot[tgt]] - banks] = True
        self.n_mutes += int((now_muted & ~self.muted).sum())
        self.muted |= now_muted
        self.ticks += 1

    def advance(self, ticks: int) -> "Ticks":
        for _ in range(ticks):
            self.tick()
        return self

    def observed(self) -> dict:
        """The same keys, in the same form, as the world reads from the
        system (`worlds/bank.py` `observed()`)."""
        occ = self.queued()
        seen = {name: getattr(self, name).copy() for name in ACCOUNT_FIELDS}
        seen.update(issued=self.issued.copy(), completed=self.completed.copy(),
                    rng=self.rng.astype(np.int64),
                    teller_queued=occ[:self.banks],
                    account_queued=occ[self.banks:],
                    muted=self.muted.copy(), n_mutes=self.n_mutes)
        return seen


def invariant(seen: dict, *, banks: int, accounts: int, in_flight: int,
              amount_max: int, balance0: int) -> dict:
    """The deployment's guarantees, order-free, from any tick's state
    (`seen`: `observed()`'s keys, the reference's or the system's).
    Returns the checks and `deficit`: transactions unaccounted for,
    either way.

    Every transaction is ONE message at any moment — an issue waiting at
    its teller, a credit, a debit or a reply waiting at an account, a
    reply waiting at its teller — so a bank's mailboxes hold `in_flight`
    messages between ticks, no more and no fewer."""
    per_bank = lambda x: np.asarray(x, np.int64).reshape(  # noqa: E731
        banks, accounts).sum(axis=1)
    issued = np.asarray(seen["issued"], np.int64)
    completed = np.asarray(seen["completed"], np.int64)
    balance = np.asarray(seen["balance"], np.int64)
    out_sum, in_sum = (np.asarray(seen[k], np.int64)
                       for k in ("out_sum", "in_sum"))
    waiting = np.asarray(seen["waiting"], np.int64)
    issues_left = in_flight - (issued - completed)
    replies_at_teller = per_bank(seen["forwarded"]) - completed
    held = per_bank(seen["account_queued"]) + seen["teller_queued"]
    on_wire = per_bank(seen["n_out"]) - per_bank(seen["n_in"])   # debits out
    owed = per_bank(out_sum) - per_bank(in_sum)
    checks = {
        "money_conserved_every_account":
        bool(np.array_equal(balance, balance0 - out_sum + in_sum)),
        "money_on_the_wire_every_bank":
        bool(np.all((0 <= owed) & (owed <= amount_max * on_wire)
                    & (on_wire <= owed))),
        "one_transfer_at_a_time":
        not np.any(seen["violations"]) and not np.any(seen["stray"])
        and bool(np.all((waiting == 0) | (waiting == 1)))
        # a waiting account has a debit or a reply on its way
        and bool(np.all(per_bank(waiting) >= on_wire))
        and bool(np.all(on_wire >= 0)),
        "exactly_once_every_bank":
        bool(np.all(issues_left >= 0)) and bool(np.all(replies_at_teller >= 0))
        and bool(np.array_equal(seen["teller_queued"],
                                issues_left + replies_at_teller))
        and bool(np.array_equal(held, np.full(banks, in_flight)))
        and bool(np.all(per_bank(seen["account_queued"])
                        >= per_bank(waiting))),
    }
    deficit = int(np.abs(held - in_flight).sum()
                  + np.abs(balance - (balance0 - out_sum + in_sum)).sum()
                  + np.sum(seen["violations"]) + np.sum(seen["stray"]))
    return {"checks": checks, "deficit": deficit}


def stranded(before: dict, after: dict, *, accounts: int,
             unmute_occ: int) -> int:
    """Accounts muted behind a teller that had recovered, from two reads
    one tick apart. A muted account is released at the START of the tick
    after its teller is down to the unmute line, so one read between
    ticks cannot tell a release that is due from one that never comes;
    an account muted in both reads, its teller at or under the line in
    the first, was passed over."""
    recovered = np.repeat(np.asarray(before["teller_queued"]) <= unmute_occ,
                          accounts)
    return int(np.sum(np.asarray(before["muted"], bool) & recovered
                      & np.asarray(after["muted"], bool)))
