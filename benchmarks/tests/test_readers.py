from types import SimpleNamespace

import pytest

import counter_delta
import device_busy
import device_idle
import device_launches
import ledger_delta
import roofline
import run_fact
import span_count
import span_sum


def make_run(**kw):
    base = dict(
        spans=[("scan.decode", 0.5), ("scan.decode", 0.25),
               ("sync.collect", 0.125), ("sync.fetch", 0.125),
               ("Query", 2.0)],
        counters_before={("compileCache.backendCompiles", ()): 10,
                         ("compileCache.persistentHits", ()): 7},
        counters_after={("compileCache.backendCompiles", ()): 14,
                        ("compileCache.persistentHits", ()): 10,
                        ("host_sync.bytes", (("site", "a"),)): 5,
                        ("host_sync.bytes", (("site", "b"),)): 6},
        ledger_before=100, ledger_after=133,
        trace={"busy_s": 0.5, "idle_share": 0.75, "launches": 75,
               "window_s": 2.0},
        facts={"first_query_s": 1.5, "bytes_read": 819e9 * 0.05},
        peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return SimpleNamespace(**base)


def test_span_readers_match_a_name_or_a_prefix():
    run = make_run()
    assert span_sum.read("scan.decode", run) == 0.75
    assert span_sum.read("sync.", run) == 0.25
    assert span_sum.read("sync.*", run) == 0.25
    assert span_sum.read("scan.upload", run) == 0
    assert span_count.read("scan.decode", run) == 2


def test_counter_delta_sums_labels_and_subtracts():
    run = make_run()
    assert counter_delta.read("host_sync.bytes", run) == 11
    assert counter_delta.read(
        {"plus": ["compileCache.backendCompiles"],
         "minus": ["compileCache.persistentHits"]}, run) == 1
    assert counter_delta.read("never.seen", run) is None


def test_ledger_and_facts():
    run = make_run()
    assert ledger_delta.read(None, run) == 33
    assert run_fact.read("first_query_s", run) == 1.5
    assert run_fact.read("not_taken", run) is None


def test_trace_readers_and_roofline():
    run = make_run()
    assert device_busy.read(None, run) == 0.5
    assert device_idle.read(None, run) == 75.0
    assert device_launches.read(None, run) == 75
    # 0.05 s of HBM time at the peak, over 0.5 s busy
    assert roofline.read("hbm_bytes_per_s", run) == pytest.approx(10.0)


def test_a_reader_with_nothing_to_read_returns_nothing():
    run = make_run(trace=None, spans=None, ledger_before=None)
    for reader, arg in ((device_busy, None), (device_idle, None),
                        (device_launches, None),
                        (roofline, "hbm_bytes_per_s"),
                        (span_sum, "scan.decode"), (ledger_delta, None)):
        assert reader.read(arg, run) is None
