"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e chip by ``record_trace.py``."""

from pathlib import Path

import pytest

from bench import reduce as red

E = red.Event
DATA = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"


def _trace():
    # window 0..100 ns; device 0 busy 10..30 and 20..40 (overlap) and
    # 90..110 (clipped); device 1 busy 0..50.  Host: a tick 0..60, an idle
    # sleep 60..100.
    return red.Trace(
        devices={"/device:TPU:0": [E(10, 30, "fusion.1"),
                                   E(20, 40, "all-to-all.2", "",
                                     "Async XLA Ops"),
                                   E(90, 110, "fusion.1", "a2a_round[data]")],
                 "/device:TPU:1": [E(0, 50, "fusion.1")]},
        host=[E(0, 100, "bench.window"), E(0, 60, "bench.tick"),
              E(60, 100, "bench.idle")])


def test_union_and_busy_time():
    assert red.union([(3, 5), (1, 2), (2, 4)]) == [(1, 5)]
    r = red.reduce(_trace())
    assert r.window_s == pytest.approx(100e-9)
    # device 0: 10..40 and 90..100 = 40; device 1: 50; mean 45
    assert r.busy_s == pytest.approx(45e-9)
    assert r.n_devices == 2


def test_op_time_and_scopes():
    r = red.reduce(_trace())
    # fusion.1: 20 + 10 (clipped) on device 0, 50 on device 1; mean 40
    assert r.op_s["fusion.1"] == pytest.approx(40e-9)
    assert r.op_s["all-to-all.2"] == pytest.approx(10e-9)
    # all-to-all.2: 20 on device 0; fusion.1 under a2a_round: 10 (clipped)
    assert r.time_where(red.is_exchange_op) == pytest.approx(15e-9)
    # a TPU trace names the op with underscores
    assert red.is_exchange_op("all_to_all.77", "")
    assert not red.is_exchange_op("pad_maximum_fusion.4", "")
    bd = r.breakdown()
    assert bd["device_ops"][0][0] == "fusion.1"


def test_idle_gaps_are_named_by_the_host_span():
    r = red.reduce(_trace())
    # device 0 idle 0..10 and 40..60 under the tick, 60..90 under idle;
    # device 1 idle 50..60 tick, 60..100 idle; means: tick 20, idle 35
    assert r.idle_by_host["bench.tick"] == pytest.approx(20e-9)
    assert r.idle_by_host["bench.idle"] == pytest.approx(35e-9)
    assert r.busy_s + sum(r.idle_by_host.values()) == \
        pytest.approx(r.window_s)


def test_nested_ops_count_their_own_time():
    ops = [E(0, 100, "while.1"), E(10, 30, "fusion.2"), E(40, 50, "copy.3"),
           E(120, 130, "fusion.2")]
    own = {e.name + str(e.start_ns): t for e, t in red.self_times(ops, 0, 200)}
    assert own == {"while.10": 70, "fusion.210": 20, "copy.340": 10,
                   "fusion.2120": 10}


def test_a_trace_without_its_window_is_refused():
    tr = _trace()
    tr.host = tr.host[1:]
    with pytest.raises(ValueError):
        red.reduce(tr)



def test_recorded_chip_trace():
    tr = red.load(str(DATA))
    assert list(tr.devices) == ["/device:TPU:0"]
    r = red.reduce(tr)
    assert 0 < r.busy_s < r.window_s
    # three calls of one fused program, a 10 ms host pause after each
    assert list(r.op_s) == ["convolution_reduce_fusion"]
    assert r.idle_by_host["bench.pause"] > 0.03
    assert "bench.call" in r.idle_by_host
    assert r.busy_s + sum(r.idle_by_host.values()) == \
        pytest.approx(r.window_s, rel=1e-6)
