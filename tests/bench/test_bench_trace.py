"""The trace reduction (bench/trace.py) on hand-made events and on a slice
of a trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace as T

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def test_union_clip_subtract():
    assert T.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert T.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert T.subtract([(0, 10)], [(2, 3), (2.5, 4), (8, 12)]) == [(0, 2), (4, 8)]
    assert T.length([(0, 2), (4, 8)]) == 6


def test_self_time_takes_nested_ops_out_of_their_loop():
    events = [("while.1", 0, 10), ("fusion.a", 1, 3), ("fusion.b", 5, 2), ("fusion.a", 12, 1)]
    got = T.self_times(events, 0, 20)
    assert got == {"while.1": 5, "fusion.a": 4, "fusion.b": 2}
    assert sum(got.values()) == T.length(T.union([(s, s + d) for _, s, d in events]))


def test_reduce_busy_gaps_and_collectives():
    host = [("bench.window", 0, 100), ("feeder.next", 20, 15), ("loss.wait", 60, 30)]
    dev0 = [("fusion.1", 0, 20), ("all-reduce.3", 40, 20), ("fusion.2", 45, 5), ("fusion.4", 90, 10)]
    dev1 = [("fusion.1", 0, 50)]
    r = T.reduce({"devices": {"/device:TPU:0": dev0, "/device:TPU:1": dev1}, "host": host})
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    assert r["busy_s"] == pytest.approx((50 + 50) / 2 * ns)  # device 0: 20 + 20 + 10
    # device 0 idles 20–40 (feeder.next overlaps 15 of it) and 60–90 (loss.wait)
    assert dict(r["idle_gaps"]) == pytest.approx({"feeder.next": 20 * ns, "loss.wait": 30 * ns})
    assert r["collective_s"] == pytest.approx(20 * ns)
    assert r["collective_exposed_s"] == pytest.approx(15 * ns)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(35 * ns)]


def test_reduce_recorded_v5e_slice():
    events = json.load(open(FIXTURES / "trace_lm_v5e.json"))
    r = T.reduce(events)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(2.007257e-3)
    assert r["busy_s"] == pytest.approx(1.999991e-3)
    # the one gap is the host waiting on the previous step's loss
    assert r["idle_gaps"] == [["loss.wait", pytest.approx(7.266e-6)]]
    assert sum(t for _, t in r["device_ops"]) == pytest.approx(r["busy_s"])
    assert r["collective_s"] == 0.0


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        T.reduce({"devices": {"/device:TPU:0": [("f", 0, 1)]}, "host": []})


def test_op_name():
    assert T.op_name("%fusion.570.remat = (f32[4096]{0}) fusion(f32[2]{0} %x), kind=kOutput") == \
        "fusion.570.remat"
