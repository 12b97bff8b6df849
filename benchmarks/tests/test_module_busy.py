"""``readers/module_busy.py`` on hand-made module and operation intervals,
and the six device metrics that split ``device_busy_s`` between them."""

from types import SimpleNamespace

import pytest

import device_busy
import module_busy
import run as harness
import trace_reduce

DEV = "/device:TPU:0"
WINDOW = (10.0, 20.0)
# name, start, end: one program before the window, one straddling its
# start, three inside, one straddling its end, one after it
MODULES = {DEV: [
    ("jit_srt_aggupd(1)", 8.0, 9.0),
    ("jit_srt_join(2)", 9.5, 11.0),
    ("jit_srt_aggmrg(3)", 12.0, 13.0),
    ("jit_srt_concatmask(4)", 14.0, 15.0),
    ("jit__lambda_(5)", 16.0, 17.0),
    ("jit_srt_sort(6)", 19.0, 21.0),
    ("jit_srt_filter(7)", 22.0, 23.0),
]}
OPS = {DEV: [
    ("%fusion.1", 8.25, 8.75),       # outside: before the window
    ("%fusion.2", 9.5, 10.5),        # straddles the window's start: 0.5 in
    ("%fusion.3", 10.5, 11.0),
    ("%fusion.4", 12.0, 12.25),
    ("%fusion.4b", 12.125, 12.5),    # overlaps the one before: union 0.5
    ("%gather.5", 14.25, 14.75),
    ("%custom-call.6", 16.0, 16.5),  # a program the engine has not named
    ("%copy.7", 18.0, 18.25),        # inside no module at all
    ("%sort.8", 19.5, 20.5),         # straddles the window's end: 0.5 in
    ("%fusion.9", 22.0, 22.5),       # outside: after the window
]}


def spec(name):
    return harness.load_json(harness.HERE, "layer_metrics", f"{name}.json")


DEVICE_METRICS = ("agg_device_s", "join_device_s", "sort_device_s",
                  "rowwise_device_s", "exchange_device_s", "other_device_s")


@pytest.mark.parametrize("arg,want", [
    ({"prefixes": ["jit_srt_agg"]}, 0.5),
    ({"prefixes": ["jit_srt_join"]}, 1.0),
    ({"prefixes": ["jit_srt_sort", "jit_srt_limitstep"]}, 0.5),
    ({"prefixes": ["jit_srt_concat", "jit_srt_exch"]}, 0.5),
    ({"prefixes": ["jit_srt_filter"]}, 0.0),
    ({"prefixes": ["jit_srt_join", "jit_srt_agg"]}, 1.5),
    ({"not": ["jit_srt_"]}, 0.75),
    ({"not": ["jit_srt_agg", "jit_srt_join"]}, 1.75),
])
def test_busy_inside_outside_and_straddling(arg, want):
    assert module_busy.busy(OPS, MODULES, WINDOW, arg) == pytest.approx(want)


def test_selects_by_prefix_or_by_none_of_them():
    assert module_busy.selects("jit_srt_aggupd(9)", {"prefixes": ["jit_srt_agg"]})
    assert not module_busy.selects(None, {"prefixes": ["jit_srt_agg"]})
    assert module_busy.selects(None, {"not": ["jit_srt_agg"]})
    assert module_busy.selects("jit__lambda_", {"not": ["jit_srt_agg"]})
    assert not module_busy.selects("jit_srt_aggfin", {"not": ["jit_srt_agg"]})


def test_busy_averages_over_the_chips_and_takes_no_chip():
    two = {DEV: OPS[DEV], "/device:TPU:1": []}
    arg = {"prefixes": ["jit_srt_join"]}
    assert module_busy.busy(two, MODULES, WINDOW, arg) == pytest.approx(0.5)
    assert module_busy.busy({}, {}, WINDOW, arg) == 0.0


def test_the_six_device_metrics_add_up_to_device_busy():
    reduced = trace_reduce.reduce(
        OPS, MODULES, [], (WINDOW[0], WINDOW[1], 0))
    run = SimpleNamespace(trace=reduced,
                          module_intervals=(OPS, MODULES, WINDOW))
    parts = {m: module_busy.read(spec(m)["arg"], run)
             for m in DEVICE_METRICS}
    assert parts["agg_device_s"] == pytest.approx(0.5)
    assert parts["join_device_s"] == pytest.approx(1.0)
    assert parts["sort_device_s"] == pytest.approx(0.5)
    assert parts["exchange_device_s"] == pytest.approx(0.5)
    assert parts["rowwise_device_s"] == 0.0
    assert parts["other_device_s"] == pytest.approx(0.75)
    assert sum(parts.values()) == pytest.approx(device_busy.read(None, run))


def test_every_family_prefix_is_in_one_metric_and_other_excludes_them_all():
    named = [p for m in DEVICE_METRICS[:-1] for p in spec(m)["arg"]["prefixes"]]
    assert len(named) == len(set(named))
    assert sorted(named) == sorted(spec("other_device_s")["arg"]["not"])
    assert all(p.startswith("jit_srt_") for p in named)
    for m in DEVICE_METRICS:
        assert spec(m)["reader"] == "module_busy"


def test_nothing_to_read_returns_nothing(tmp_path, monkeypatch):
    assert module_busy.read({"prefixes": ["x"]},
                            SimpleNamespace(trace=None)) is None
    monkeypatch.setattr(module_busy, "TRACE_DIR", str(tmp_path))
    run = SimpleNamespace(trace={"busy_s": 1.0},
                          devices=[SimpleNamespace(platform="tpu")])
    assert module_busy.read({"prefixes": ["x"]}, run) is None
    run.module_intervals = ({}, {}, None)  # a trace with no traced window
    assert module_busy.read({"prefixes": ["x"]}, run) is None


@pytest.mark.parametrize("name,arg", [
    ("plan_s", "plan."), ("dispatch_s", "dispatch."),
    ("scan_read_s", "scan.decode.read"),
    ("scan_convert_s", "scan.decode.convert"),
    ("scan_chunk_s", "scan.chunk"), ("upload_build_s", "upload.build"),
    ("upload_put_s", "upload.put")])
def test_span_metrics_read_their_spans_and_not_their_neighbours(name, arg):
    import span_sum
    s = spec(name)
    assert (s["reader"], s["arg"], s["per"]) == ("span_sum", arg, "execution")
    spans = [("plan.logical", 1.0), ("plan.rewrite", 2.0),
             ("plan.partitions", 4.0), ("dispatch.aggupd", 8.0),
             ("dispatch.concat", 16.0), ("scan.decode", 32.0),
             ("scan.decode.read", 64.0), ("scan.decode.convert", 128.0),
             ("scan.chunk", 256.0), ("scan.upload", 512.0),
             ("upload.build", 1024.0), ("upload.put", 2048.0)]
    want = {"plan_s": 7.0, "dispatch_s": 24.0, "scan_read_s": 64.0,
            "scan_convert_s": 128.0, "scan_chunk_s": 256.0,
            "upload_build_s": 1024.0, "upload_put_s": 2048.0}[name]
    assert span_sum.read(s["arg"], SimpleNamespace(spans=spans)) == want
    # the accepted metric one level up still reads its own span alone
    assert span_sum.read(spec("scan_decode_s")["arg"],
                         SimpleNamespace(spans=spans)) == 32.0
