"""``counter_delta`` over a counter that carries labels: the per-layer
metrics of ``tpch-sf10-state.q18`` read ``join.stream.rows``, which the
engine keeps one counter a join type (``type=inner``, ``type=leftsemi``)."""

from types import SimpleNamespace

import counter_delta

INNER = ("join.stream.rows", (("type", "inner"),))
SEMI = ("join.stream.rows", (("type", "leftsemi"),))


def run_with(before, after):
    return SimpleNamespace(counters_before=before, counters_after=after)


def test_a_labelled_counter_sums_its_labels():
    run = run_with({INNER: 100, SEMI: 1000},
                   {INNER: 160, SEMI: 16000,
                    ("agg.merge.inputRows", ()): 5})
    assert counter_delta.read("join.stream.rows", run) == 60 + 15000
    assert counter_delta.read("agg.merge.inputRows", run) == 5


def test_a_label_first_seen_in_the_window_counts_from_zero():
    run = run_with({INNER: 100}, {INNER: 100, SEMI: 7})
    assert counter_delta.read("join.stream.rows", run) == 7


def test_a_program_without_the_counter_leaves_the_metric_out():
    run = run_with({}, {("scan.upload.stringColumns", ()): 3})
    assert counter_delta.read("join.stream.rows", run) is None
