import pytest

import trace_reduce as tr

# one device; the window is [0, 10) on thread 0. The device runs
# [1,2) [1.5,3) (overlapping: busy 1..3), [5,6) and [8,8.5).
OPS = {"/device:TPU:0": [("fusion.1", 1.0, 2.0), ("fusion.2", 1.5, 3.0),
                         ("fusion.1", 5.0, 6.0), ("copy", 8.0, 8.5),
                         ("outside", 11.0, 12.0)]}
LAUNCHES = {"/device:TPU:0": [("jit_a", 1.0, 3.0), ("jit_b", 5.0, 6.0),
                              ("jit_a", 8.0, 8.5), ("jit_a", 11.0, 12.0)]}
SPANS = [
    ("bench.window", 0, 0.0, 10.0),
    ("Query", 0, 0.5, 9.0),
    ("scan.prefetch.stall", 0, 3.0, 4.9),    # gap [3,5): the main thread
    ("scan.decode", 1, 0.0, 10.0),           # a pool thread, all along
    ("sync.collect", 0, 8.5, 9.0),           # gap [8.5,10): a third of it
]
WINDOW = (0.0, 10.0, 0)


def test_merge_clip_and_gaps():
    assert tr.merge([(1, 2), (1.5, 3), (5, 6), (6, 6)]) == [(1, 3), (5, 6)]
    assert tr.clip([(0, 4), (8, 12)], 2, 10) == [(2, 4), (8, 10)]
    assert tr.gaps([(1, 3), (5, 6)], 0, 10) == [(0, 1), (3, 5), (6, 10)]


def test_busy_idle_launches_and_top_ops():
    r = tr.reduce(OPS, LAUNCHES, SPANS, WINDOW)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(3.5)        # 2 + 1 + 0.5
    assert r["idle_share"] == pytest.approx(0.65)
    assert r["launches"] == 3                        # the fourth is outside
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2.0)]
    assert dict(map(tuple, r["device_ops"]))["fusion.2"] \
        == pytest.approx(1.5)
    assert "outside" not in dict(map(tuple, r["device_ops"]))


def test_gaps_are_named_by_what_the_host_was_doing():
    r = tr.reduce(OPS, LAUNCHES, SPANS, WINDOW)
    idle = dict(map(tuple, r["idle_gaps"]))
    # [3,5): the query's own thread sat in scan.prefetch.stall for 1.9 of 2
    assert idle["scan.prefetch.stall"] == pytest.approx(2.0)
    # [0,1), [6,8) and [8.5,10): no span of the main thread covers half,
    # the pool's scan.decode covers all
    assert idle["scan.decode"] == pytest.approx(1.0 + 2.0 + 1.5)
    assert sum(idle.values()) == pytest.approx(6.5)
    assert r["idle_gap_detail"]["scan.decode"]["gaps"] == 3


def test_nested_spans_name_a_gap_by_the_innermost():
    spans = SPANS + [("TpuHashAggregateExec", 0, 0.6, 8.9)]
    r = tr.reduce(OPS, LAUNCHES, spans, WINDOW)
    idle = dict(map(tuple, r["idle_gaps"]))
    # the operator's span covers the stall's: the stall still names [3,5);
    # [6,8) is the operator's own host work, which beats the pool's span
    assert idle["scan.prefetch.stall"] == pytest.approx(2.0)
    assert idle["TpuHashAggregateExec"] == pytest.approx(2.0)
    assert idle["scan.decode"] == pytest.approx(1.0 + 1.5)


def test_unspanned_query_time_and_time_between_queries():
    spans = [("bench.window", 0, 0.0, 10.0), ("Query", 0, 0.0, 7.0)]
    r = tr.reduce(OPS, LAUNCHES, spans, WINDOW)
    idle = dict(map(tuple, r["idle_gaps"]))
    # [0,1) [3,5) and [6,8) (7 of 8 under Query... half or more) are the
    # query's own; [8.5,10) is the harness between two queries
    assert idle["Query.unspanned"] == pytest.approx(1.0 + 2.0 + 2.0)
    assert idle["between.queries"] == pytest.approx(1.5)


def test_busy_is_averaged_over_devices_and_launches_summed():
    ops = dict(OPS, **{"/device:TPU:1": [("fusion.1", 0.0, 1.5)]})
    launches = dict(LAUNCHES, **{"/device:TPU:1": [("jit_a", 0.0, 1.5)]})
    r = tr.reduce(ops, launches, SPANS, WINDOW)
    assert r["busy_s"] == pytest.approx((3.5 + 1.5) / 2)
    assert r["launches"] == 4


def test_a_trace_directory_without_a_trace_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        tr.find_xplane(str(tmp_path))
