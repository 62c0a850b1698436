"""Bank world: Savina's Bank Transaction, many banks in one world.

Two actor types written against the public API (`@actor`, `@behaviour`,
`Ref`, `Runtime.declare / start / spawn_many / set_fields / bulk_send /
run`); the protocol is `reference_bank.py`'s docstring. Savina's names
are kept although they read backwards: a *credit* takes money OUT of
the account that receives it.

  Teller   one a bank; `MAILBOX_CAP` its own (`teller_mailbox_cap`): it
           takes back everything its bank has out, where an account
           sees two or three messages and keeps `RuntimeOptions`' ring.
           `issue()` is set-up's seeding: Savina's `start`
           loop, one `generateWork()` a message. `reply()`: a transfer
           is done, `completed += 1`, and the next is issued in the
           same dispatch, so a bank always has `in_flight` out.
           `generateWork()` draws source, destination and amount from
           the xorshift32 in the teller's state (the generator
           `worlds/ubench.py`'s RandomPinger runs) and sends
           `account[src].credit(amount, account[dest])`, dest > src.
  Account  the request -> reply round trip, without blocking: `credit`
           takes the amount out, sends `recipient.debit(self, amount)`
           and goes into reply mode (`waiting`); `debit` puts the
           amount in and answers `sender.reply()`; `reply` forwards
           `teller.reply()` and leaves reply mode. While it waits an
           account handles nothing but that `reply`: a credit or a
           debit is SET ASIDE, sent to its own mailbox again at once
           (Savina's ManualStash keeps a list; here the mailbox is the
           list). Two send sites a behaviour (on, or to self again), of
           which one fires: `MAX_SENDS = 2`.

Every size follows from `cfg["actors"]` and `cfg["accounts_per_bank"]`:
`actors // (A + 1)` banks of one teller and A accounts. A self-test's
`scale={"actors": 2048}` cuts the banks and never A; one that also cuts
`teller_mailbox_cap` cuts what a teller has out with it (`in_flight` is
the mix's, at most the teller's ring). At the size the file states, the
derived sizes must be the ones it states.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ponyc_tpu import I32, Ref, Runtime, RuntimeOptions, actor, behaviour

from benchmarks import reference_bank as ref

def _xorshift(x):
    """xorshift32 on int32 lanes (a logical right shift by masking)."""
    x = x ^ (x << 13)
    x = x ^ ((x >> 17) & 0x7FFF)
    return x ^ (x << 5)


def actors(*, accounts: int, sources: int, amount_max: int,
           teller_batch: int, teller_mailbox_cap: int, account_batch: int,
           lax_credit: bool = False):
    """The two actor types of one deployment: (Teller, Account).
    `lax_credit` builds the account the tier-1 tests break the protocol
    with: its `credit` does not honour reply mode."""

    @actor
    class Account:
        teller: Ref
        balance: I32
        waiting: I32        # in reply mode: one transfer at a time
        n_out: I32          # credits handled, and the amounts sent on
        out_sum: I32
        n_in: I32           # debits handled, and the amounts taken in
        in_sum: I32
        requeued: I32       # messages set aside: sent to self again
        forwarded: I32      # replies passed on to the teller
        stray: I32          # a reply that found nobody waiting
        violations: I32     # a credit or debit handled while waiting

        BATCH = account_batch
        MAX_SENDS = 2

        @behaviour
        def credit(self, st, amount: I32, recipient: Ref):
            busy = st["waiting"] != 0
            go = ~busy | lax_credit
            self.send(recipient, Account.debit, self.actor_id, amount,
                      when=go)
            self.send(self.actor_id, Account.credit, amount, recipient,
                      when=~go)
            return {**st, "balance": st["balance"] - jnp.where(go, amount, 0),
                    "waiting": jnp.where(go, 1, st["waiting"]),
                    "n_out": st["n_out"] + go,
                    "out_sum": st["out_sum"] + jnp.where(go, amount, 0),
                    "requeued": st["requeued"] + ~go,
                    "violations": st["violations"] + (go & busy)}

        @behaviour
        def debit(self, st, sender: Ref, amount: I32):
            busy = st["waiting"] != 0
            self.send(sender, Account.reply, when=~busy)
            self.send(self.actor_id, Account.debit, sender, amount,
                      when=busy)
            return {**st, "balance": st["balance"] + jnp.where(busy, 0, amount),
                    "n_in": st["n_in"] + ~busy,
                    "in_sum": st["in_sum"] + jnp.where(busy, 0, amount),
                    "requeued": st["requeued"] + busy}

        @behaviour
        def reply(self, st):
            busy = st["waiting"] != 0
            self.send(st["teller"], Teller.reply, when=busy)
            return {**st, "waiting": 0, "forwarded": st["forwarded"] + busy,
                    "stray": st["stray"] + ~busy}

    def generate_work(ctx, st):
        """Savina's generateWork(): three draws, one credit."""
        a = _xorshift(st["rng"])
        b = _xorshift(a)
        c = _xorshift(b)
        src = a % sources
        dest = src + jnp.maximum(b % (accounts - src), 1)
        ctx.send(st["first"] + src, Account.credit, 1 + c % amount_max,
                 st["first"] + dest)
        return {**st, "rng": c, "issued": st["issued"] + 1}

    @actor
    class Teller:
        first: I32          # global id of the bank's first account
        rng: I32
        issued: I32
        completed: I32

        BATCH = teller_batch
        MAILBOX_CAP = teller_mailbox_cap
        MAX_SENDS = 1

        @behaviour
        def issue(self, st):
            return generate_work(self, st)

        @behaviour
        def reply(self, st):
            return generate_work(
                self, {**st, "completed": st["completed"] + 1})

    return Teller, Account


def sizes(actors_: int, accounts: int, in_flight: int) -> dict:
    """The world's sizes from its free ones."""
    banks = actors_ // (accounts + 1)
    if banks < 1:
        raise ValueError(f"{actors_} actors hold no bank of {accounts}")
    return {"actors": banks * (accounts + 1), "banks": banks,
            "tellers": banks, "accounts": banks * accounts,
            "in_flight": in_flight, "live": banks * in_flight}


class World:
    """One seeded world of banks and what `throughput_bank` asks of it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 lax_credit: bool = False):
        options = dict(cfg["runtime_options"])
        self.a = int(cfg["accounts_per_bank"])
        self.teller_cap = int(cfg["teller_mailbox_cap"])
        self.in_flight = min(int(traffic["in_flight"]), self.teller_cap)
        size = sizes(int(cfg["actors"]), self.a, self.in_flight)
        stated = cfg["sizes"]
        if int(cfg["actors"]) == stated["actors"] and size != stated:
            raise ValueError(f"the configuration states {stated}, its "
                             f"rules give {size}")
        if int(traffic["seeded_every"]) != 1:
            raise ValueError("every teller is seeded: a bank without "
                             "transactions is no bank")
        self.banks, self.n_acc = size["banks"], size["accounts"]
        self.live = size["live"]
        self.amount_max = int(traffic["amount_max"])
        self.balance0 = int(cfg["initial_balance"])
        self.sources = ref.sources_of(self.a, float(traffic["source_share"]))
        self.teller_batch = int(cfg["teller_batch"])
        self.account_batch = int(cfg["account_batch"])
        self.Teller, self.Account = actors(
            accounts=self.a, sources=self.sources,
            amount_max=self.amount_max, teller_batch=self.teller_batch,
            teller_mailbox_cap=self.teller_cap,
            account_batch=self.account_batch, lax_credit=lax_credit)
        self.lax_credit = lax_credit
        self.rng0 = np.random.default_rng(seed).integers(
            1, 2**31 - 1, self.banks, dtype=np.int64)

        rt = Runtime(RuntimeOptions(**options))
        rt.declare(self.Teller, self.banks)     # tellers first: the order
        rt.declare(self.Account, self.n_acc)    # the reference delivers in
        rt.start()
        self.rt = rt
        if getattr(rt.program.by_type[self.Teller], "mailbox_cap",
                   None) != self.teller_cap:
            raise RuntimeError(
                "this program gives a cohort no MAILBOX_CAP of its own (the "
                "parent of PR 44): a teller's ring could not hold what its "
                "bank has out")
        self.teller_ids = rt.spawn_many(self.Teller, self.banks)
        self.account_ids = rt.spawn_many(self.Account, self.n_acc,
                                         balance=self.balance0)
        for ids in (self.teller_ids, self.account_ids):
            if not np.array_equal(ids, ids[0] + np.arange(len(ids))):
                raise RuntimeError("cohort ids are not contiguous: a "
                                   "teller's `first + src` needs them so")
        rt.set_fields(self.Teller, self.teller_ids, rng=self.rng0,
                      first=self.account_ids[0]
                      + self.a * np.arange(self.banks))
        rt.set_fields(self.Account, self.account_ids,
                      teller=np.repeat(self.teller_ids, self.a))
        # Savina's `start`: every transaction a bank will have out, as
        # one `issue` each in its teller's own mailbox
        for _ in range(self.in_flight):
            rt.bulk_send(self.teller_ids, self.Teller.issue)
        self._ticks = self._fresh_reference()

    # ---- what the system holds now, read from its state
    def tellers(self) -> dict:
        """`issued` and `completed` per teller: the one read a segment
        ends in (two columns of `banks` words)."""
        cols = self.rt.state.type_state[self.Teller.__name__]
        return {k: np.asarray(cols[k]).astype(np.int64)
                for k in ("issued", "completed")}

    def observed(self) -> dict:
        """`reference_bank.Ticks.observed()`'s keys, from the system."""
        rt, st = self.rt, self.rt.state
        acc = rt.cohort_state(self.Account)
        tel = rt.cohort_state(self.Teller)
        seen = {k: acc[k].astype(np.int64) for k in ref.ACCOUNT_FIELDS}
        depth = np.asarray(st.tail, np.int64) - np.asarray(st.head, np.int64)
        seen.update(issued=tel["issued"].astype(np.int64),
                    completed=tel["completed"].astype(np.int64),
                    rng=tel["rng"].astype(np.int64) & ref.MASK32,
                    teller_queued=depth[self.teller_ids],
                    account_queued=depth[self.account_ids],
                    muted=np.asarray(st.muted)[self.account_ids].astype(bool),
                    n_mutes=rt.counter("n_mutes"))
        return seen

    def dispatches(self, seen: dict) -> int:
        """Behaviours the actors themselves counted, for `n_processed`:
        a teller issues one credit a dispatch; an account counts every
        message it handled, set aside or found stray."""
        return int(seen["issued"].sum() + sum(
            seen[k].sum() for k in ("n_out", "n_in", "requeued", "forwarded",
                                    "stray")))

    def invariant(self, seen: dict) -> dict:
        return ref.invariant(
            seen, banks=self.banks, accounts=self.a,
            in_flight=self.in_flight, amount_max=self.amount_max,
            balance0=self.balance0)

    # ---- the reference
    def ring(self, atype) -> tuple:
        """A cohort's (capacity, overload line, unmute line)."""
        cohort = self.rt.program.by_type[atype]
        return cohort.mailbox_cap, cohort.overload_occ, cohort.unmute_occ

    def _fresh_reference(self) -> ref.Ticks:
        return ref.Ticks(
            self.banks, self.a, sources=self.sources,
            amount_max=self.amount_max, in_flight=self.in_flight,
            teller_batch=self.teller_batch, account_batch=self.account_batch,
            teller_ring=self.ring(self.Teller),
            account_ring=self.ring(self.Account), balance0=self.balance0,
            rng0=self.rng0, lax_credit=self.lax_credit)

    def reference(self, ticks: int) -> dict:
        """The protocol's state after `ticks` ticks, tick by tick (the
        reference is advanced, never rewound)."""
        if ticks < self._ticks.ticks:
            self._ticks = self._fresh_reference()
        return self._ticks.advance(ticks - self._ticks.ticks).observed()

    def tick_shape(self) -> dict:
        """What one steady tick must touch, for min_bytes: every message
        a bank has out is dispatched once a tick (handled or set aside;
        the few waiting at a teller beyond its batch aside), each a
        record in and a record out. Nearly all of them sit at accounts;
        the accounts that dispatch are the expected share of non-empty
        mailboxes under Poisson arrivals (an estimate: a waiting account
        gathers what is set aside), and every teller."""
        return {"messages": self.live,
                "dispatching_actors": float(
                    self.banks - self.n_acc * np.expm1(-self.live
                                                       / self.n_acc)),
                "record_words": 1 + int(self.rt.opts.msg_words),
                "state_words": len(self.Account.field_specs),
                "mailbox_cap": {t.__name__: self.ring(t)[0]
                                for t in (self.Teller, self.Account)}}


def build(cfg: dict, traffic: dict, seed: int) -> World:
    return World(cfg, traffic, seed)
