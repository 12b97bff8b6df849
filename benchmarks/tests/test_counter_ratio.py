"""``counter_ratio`` on hand-made ``counters_before`` / ``counters_after``:
``prefetch_inflight_avg`` reads seconds of queued and running decodes over
the seconds a scan was active."""

from types import SimpleNamespace

import pytest

import counter_ratio

QUEUE = ("scan.prefetch.queueTime", ())
DECODE = ("scan.prefetch.decodeTime", ())
ACTIVE = ("scan.prefetch.activeTime", ())
ARG = {"num": {"plus": [QUEUE[0], DECODE[0]]}, "den": ACTIVE[0]}


def run_with(before, after):
    return SimpleNamespace(counters_before=before, counters_after=after)


@pytest.mark.parametrize("before, after, want", [
    # 0.5 s queued and 4.5 s decoding while scans were active for 2 s
    ({QUEUE: 1.0, DECODE: 10.0, ACTIVE: 4.0},
     {QUEUE: 1.5, DECODE: 14.5, ACTIVE: 6.0}, 2.5),
    # no scan was active in the window
    ({QUEUE: 1.0, DECODE: 10.0, ACTIVE: 4.0},
     {QUEUE: 1.0, DECODE: 10.0, ACTIVE: 4.0}, None),
    # a program without the life counters: decodeTime alone is there
    ({DECODE: 10.0}, {DECODE: 14.5}, None),
    # ... and one without any of them
    ({}, {("scan.upload.stringColumns", ()): 3}, None),
    # one side of the numerator unseen reads 0, as counter_delta has it
    ({DECODE: 10.0, ACTIVE: 4.0}, {DECODE: 13.0, ACTIVE: 6.0}, 1.5),
], ids=["ratio", "zero-denominator", "unseen-denominator", "unseen-all",
        "unseen-numerator-part"])
def test_counter_ratio(before, after, want):
    assert counter_ratio.read(ARG, run_with(before, after)) == want
