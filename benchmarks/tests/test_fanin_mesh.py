"""The meshed fan-in's configuration, its readers and its bytes by hand.
The cell itself runs in `test_harness.py` (four virtual CPU devices,
`benchmarks/conftest.py`); the protocol against the engine, tick by
tick, is tier-1's (`tests/test_fanin_mesh.py`)."""

import json

import numpy as np
import pytest

from benchmarks import reference_fanin_mesh as ref_mesh
from benchmarks import route_spill_bytes
from benchmarks.layer_metrics import (remote_mutes_per_tick,
                                      route_pressure_pct,
                                      route_spill_roofline, short_list_pct)
from benchmarks.tests.conftest import ROOT
from benchmarks.worlds import fanin_mesh


def _files():
    with open(f"{ROOT}/benchmarks/configs/fanin-zipf-mesh4.json") as f:
        cfg = json.load(f)
    with open(f"{ROOT}/benchmarks/traffic/crossing.json") as f:
        return cfg, json.load(f)


def test_the_configuration_is_fanin_zipf_on_four_chips():
    cfg, traffic = _files()
    with open(f"{ROOT}/benchmarks/configs/fanin-zipf.json") as f:
        one = json.load(f)
    mine, its = cfg["runtime_options"], one["runtime_options"]
    # fanin-zipf's options letter for letter, + the mesh; the spill is a
    # shard's; delivery and the bucket are the program's defaults
    assert {k: v for k, v in mine.items()
            if k not in ("spill_cap", "mesh_shards")} \
        == {k: v for k, v in its.items() if k not in ("spill_cap", "delivery")}
    assert mine["mesh_shards"] == cfg["chips"] == 4
    assert "delivery" not in mine and "route_bucket" not in mine
    assert cfg["producers_per_aggregator"] == one["producers_per_aggregator"]
    assert cfg["actors"] == 4 * one["actors"] and cfg["reduced"] == {}
    assert traffic["zipf_s"] == 0.99
    sizes, bound = cfg["sizes"], cfg["spill_bound_items"]
    assert sizes["aggregators"] == 4 * one["sizes"]["aggregators"]
    assert sizes["producers"] == 4 * one["sizes"]["producers"]
    assert bound == 2 and mine["spill_cap"] == sizes["spill_cap"] \
        == ref_mesh.spill_capacity(bound, sizes["producers"], 4, 0)
    assert set(one["guarantees"]) < set(cfg["guarantees"])
    assert {"mute_crosses_chips", "layout_free_outcome"} \
        <= set(cfg["guarantees"])


def test_the_stated_size_with_another_ratio_is_refused():
    cfg, traffic = _files()
    with pytest.raises(ValueError, match="states"):
        fanin_mesh.build({**cfg, "producers_per_aggregator": 3}, traffic, 0)
    with pytest.raises(ValueError, match="one item a dispatch"):
        fanin_mesh.build({**cfg, "actors": 2048},
                         {**traffic, "items_per_dispatch": 2}, 0)


def test_a_program_without_the_counter_gives_no_result(monkeypatch, capsys):
    """The parent of the PR that made the mute cross shards: exit 2 at
    once, before anything compiles."""
    from ponyc_tpu import Runtime
    real = Runtime.counter

    def older(self, name):
        if name == "n_remote_mutes":
            raise AttributeError(name)
        return real(self, name)
    monkeypatch.setattr(Runtime, "counter", older)
    cfg, traffic = _files()
    with pytest.raises(SystemExit) as stop:
        fanin_mesh.build({**cfg, "actors": 2048}, traffic, 0)
    assert stop.value.code == 2
    assert "no result" in capsys.readouterr().err


def test_counter_readers_by_hand():
    route = {"shards": 4, "bucket": 100, "routed": 2400, "remote": 900,
             "ticks": 3, "lookups": 8, "unpacked": 12, "remote_mutes": 45}
    ctx = {"window": {"route": route}}
    assert route_pressure_pct.read(ctx) == pytest.approx(100.0 * 8 / 12)
    assert short_list_pct.read(ctx) == pytest.approx(100.0)
    assert remote_mutes_per_tick.read(ctx) == pytest.approx(15.0)
    # the other mesh mode's record has no such keys; one chip has none
    older = {"window": {"route": {"shards": 4, "bucket": 100, "routed": 9,
                                  "remote": 3, "ticks": 3}}}
    for reader in (route_pressure_pct, short_list_pct,
                   remote_mutes_per_tick):
        assert reader.read(older) is None
        assert reader.read({"window": {}}) is None


def test_route_spill_bytes_by_hand(monkeypatch):
    # an entry: target + sender + the target's hot byte; a mute: the
    # ref and the flag
    assert route_spill_bytes.entry_bytes() == 9
    assert route_spill_bytes.mute_bytes() == 5
    # 8 entries and 4 mutes a tick over 4 shards: 2 x 9 + 1 x 5 a shard
    assert route_spill_bytes.tick_bytes_a_shard(8, 4, 4) == 23.0
    assert route_spill_bytes.tick_min_seconds(
        8, 4, 4, {"hbm_bytes_per_s": 23.0}) == pytest.approx(1.0)
    from benchmarks.layer_metrics import route_spill_ms
    monkeypatch.setattr(route_spill_ms, "read", lambda ctx: 500.0)
    ctx = {"window": {"route": {"shards": 4, "routed": 24, "ticks": 3,
                                "remote_mutes": 12}},
           "peak": {"hbm_bytes_per_s": 230.0}}
    # 0.1 s at the least against 0.5 s measured
    assert route_spill_roofline.read(ctx) == pytest.approx(20.0)
    monkeypatch.setattr(route_spill_ms, "read", lambda ctx: None)
    assert route_spill_roofline.read(ctx) is None


def test_the_reference_knows_the_layout():
    """The same wiring on one shard and on four: the same items arrive
    (conservation is layout-free), the trace differs by the remote
    mute's one tick, and only a mesh counts crossings."""
    from benchmarks import reference_fanin as ref
    out = ref.zipf_wiring(11, 448, 64, 0.99)
    protocol = dict(mailbox_cap=64, batch=8, overload_occ=48, unmute_occ=16)
    # four shards of 128 ids: a cohort's rows dealt round-robin
    prod = (np.arange(448) % 4) * 128 + np.arange(448) // 4
    agg = (np.arange(64) % 4) * 128 + 112 + np.arange(64) // 4
    four = ref_mesh.Ticks(out, prod, agg, 128, **protocol).advance(40)
    flat = ref_mesh.Ticks(out, np.arange(448), 448 + np.arange(64), 512,
                          **protocol).advance(40)
    one = ref.Ticks(out, 64, **protocol).advance(40)
    for key, want in one.observed().items():
        assert np.array_equal(flat.observed()[key], want), key
    assert (flat.n_remote_mutes, flat.n_routed_remote) == (0, 0)
    assert four.n_remote_mutes > 0 and four.n_routed_remote > 0
    assert not np.array_equal(four.sent, one.sent)
    for t in (four, one):
        held = (t.total + (t.tail - t.head)
                + np.bincount(t.spill_tgt, minlength=64))
        assert np.array_equal(held, np.bincount(out, weights=t.sent,
                                                minlength=64))
