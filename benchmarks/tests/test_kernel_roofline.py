"""``readers/kernel_roofline.py``: a family's counted bytes over the peak,
as a share of the seconds its programs' operations ran."""

from types import SimpleNamespace

import pytest

import kernel_roofline
from test_module_busy import MODULES, OPS, WINDOW

ARG = {"bytes_counter": "agg.merge.inputBytes",
       "prefixes": ["jit_srt_aggmrg"], "peak": "hbm_bytes_per_s"}
BYTES = ("agg.merge.inputBytes", ())


def run_with(before, after, intervals=(OPS, MODULES, WINDOW)):
    return SimpleNamespace(counters_before=before, counters_after=after,
                           trace={"busy_s": 1.0}, module_intervals=intervals,
                           peaks={"hbm_bytes_per_s": 800.0})


def test_share_is_least_seconds_over_the_familys_busy_seconds():
    # jit_srt_aggmrg's operations are busy 0.5 s of the window; 200 bytes
    # at 800 bytes/s are 0.25 s at the least: half of its roofline
    run = run_with({BYTES: 100}, {BYTES: 300})
    assert kernel_roofline.read(ARG, run) == pytest.approx(50.0)
    assert kernel_roofline.least_seconds(200, 800.0) == 0.25


def test_nothing_counted_or_nothing_run_leaves_the_metric_out():
    assert kernel_roofline.read(ARG, run_with({}, {})) is None
    assert kernel_roofline.read(ARG, run_with({BYTES: 5}, {BYTES: 5})) is None
    other = dict(ARG, prefixes=["jit_srt_filter"])   # ran outside the window
    assert kernel_roofline.read(other, run_with({}, {BYTES: 9})) is None
    no_trace = run_with({}, {BYTES: 9})
    no_trace.trace = None
    assert kernel_roofline.read(ARG, no_trace) is None
