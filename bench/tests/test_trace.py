"""The trace reduction, on a small trace recorded on the CPU
(``record_cpu_trace.py``) and on hand-made intervals."""
import os

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "cpu_trace.xplane.pb")


def test_interval_arithmetic():
    u = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert u == [(0, 3), (5, 9)]
    assert trace.length(u) == 7
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert trace.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]
    assert trace.clip([(0, 4), (6, 8)], 1, 7) == [(1, 4), (6, 7)]


def test_reduction_of_a_made_trace():
    us = 1e3                                # trace times are in ns
    tr = trace.Trace(
        devices={"d0": [("fusion.1", "", 0, 40 * us),
                        ("collective-permute-done.2", "collective-permute-done",
                         30 * us, 60 * us),
                        ("all-reduce.4", "all-reduce", 60 * us, 70 * us),
                        ("fusion.3", "", 80 * us, 100 * us)]},
        host=[("bench.round", 0, 50 * us), ("bench.round", 50 * us, 100 * us),
              ("bench.feed", 50 * us, 55 * us),
              ("PjitFunction(step)", 55 * us, 90 * us)])
    r = trace.reduce(tr, "bench.round")
    assert abs(r["window_s"] - 100e-6) < 1e-15 and r["units"] == 2
    assert abs(r["busy_s"] - 90e-6) < 1e-15
    assert abs(r["idle_share"] - 0.1) < 1e-12
    # 40..60 us: the permute alone; the all-reduce after it is not counted
    assert abs(r["collective_exposed_share"] - 0.2) < 1e-12
    assert r["device_ops"][0][0] == "fusion.1"
    # the gap 70..80 us lies under bench.round and PjitFunction(step)
    assert len(r["idle_gaps"]) == 1
    label, secs = r["idle_gaps"][0]
    assert label == "bench.round / PjitFunction(step)"
    assert abs(secs - 10e-6) < 1e-15


def test_reduction_of_the_recorded_cpu_trace():
    tr = trace.load(DATA, device_plane="/host:CPU", ops_line="tf_XLA")
    ops = {e[0] for e in tr.devices["/host:CPU"]}
    assert any(o.startswith("dot_general") for o in ops)
    assert any(o.startswith("ppermute") for o in ops)
    assert not any("::" in o or o.startswith("end:") for o in ops)
    r = trace.reduce(tr, "bench.round")
    assert r["units"] == 4
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    assert r["collective_exposed_share"] is not None
    assert 0 <= r["collective_exposed_share"] < 1
    secs = [v for _, v in r["device_ops"]]
    assert secs == sorted(secs, reverse=True) and len(secs) <= 10
    labels = [n for n, _ in r["idle_gaps"]]
    assert "bench.feed" in labels and len(labels) <= 10


def test_a_trace_with_no_unit_span_is_an_error():
    tr = trace.Trace(devices={"d0": [("fusion", "", 0, 1)]}, host=[])
    try:
        trace.reduce(tr, "bench.round")
    except ValueError:
        return
    raise AssertionError("no unit span must not reduce")
