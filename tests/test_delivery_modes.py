"""Delivery formulation equivalence: the cached-plan path and the
co-sort path (RuntimeOptions.delivery) must produce identical behaviour —
same totals under sustained traffic and under backpressure/spill
(delivery.py's two formulations of the same sort+segment semantics)."""

import pytest

from ponyc_tpu import RuntimeOptions


@pytest.mark.parametrize("mode", ["plan", "cosort"])
def test_ubench_sustained(mode):
    from ponyc_tpu.models import ubench
    opts = RuntimeOptions(mailbox_cap=4, batch=4, max_sends=1, msg_words=1,
                          spill_cap=256, inject_slots=8, delivery=mode)
    rt, ids = ubench.build(256, opts, pings=4)
    ubench.seed_all(rt, ids, hops=1 << 30, pings=4)
    st, inj = rt.state, rt._empty_inject
    for _ in range(6):
        st, aux = rt._step(st, *inj)
    rt.state = st
    assert rt.counter("n_processed") == 6 * 256 * 4
    assert not bool(aux.spill_overflow)


# deep-cap: the ring is deeper than one rebuild block, and the 24
# producers' first items reach the empty aggregator in one tick — three
# rank blocks in that tick, one or none in the others (delivery.py, 4).
@pytest.mark.parametrize("cap", [8, 32], ids=["cap8", "deep-cap"])
@pytest.mark.parametrize("mode", ["plan", "cosort"])
def test_fanin_pressure(mode, cap):
    from ponyc_tpu.models import fanin
    rt = fanin.run(n_producers=24, items_each=30, opts=RuntimeOptions(
        mailbox_cap=cap, batch=2, msg_words=1, max_sends=2, spill_cap=512,
        inject_slots=16, delivery=mode))
    assert int(rt.cohort_state(fanin.Aggregator)["total"].sum()) == 24 * 30


def test_bad_delivery_mode_rejected():
    with pytest.raises(ValueError):
        RuntimeOptions(delivery="nope")
