"""Span tracer + Chrome trace export + profile report (obs/).

Validates span nesting, that the exported Chrome trace JSON is well-formed
and loadable, and that a real query under tracing produces spans for exec
operators, a shuffle fetch, and a kernel-cache event (the ISSUE 1
acceptance cross-section)."""

import json

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.obs.trace import TRACER, Tracer
from spark_rapids_tpu.sql import functions as F

pytestmark = pytest.mark.smoke  # fast cross-section (see pyproject)


@pytest.fixture(autouse=True)
def _tracer_off_after():
    yield
    TRACER.configure(False)
    TRACER.clear()


class TestTracer:
    def test_span_nesting(self):
        tr = Tracer()
        tr.configure(True)
        with tr.span("outer", kind="test"):
            with tr.span("inner") as sp:
                sp.set(rows=5)
            tr.instant("marker", n=1)
        events = tr.events()
        names = [e["name"] for e in events]
        # inner exits (and records) before outer
        assert names == ["inner", "marker", "outer"]
        inner = events[0]
        assert inner["args"]["depth"] == 1
        assert inner["args"]["parent"] == "outer"
        assert inner["args"]["rows"] == 5
        marker = events[1]
        assert marker["ph"] == "i"
        assert marker["args"]["parent"] == "outer"
        outer = events[2]
        assert outer["args"]["depth"] == 0
        assert outer["ph"] == "X"
        # the parent span covers the child
        assert outer["ts"] <= inner["ts"]
        assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]

    def test_disabled_is_free(self):
        tr = Tracer()
        assert not tr.enabled
        cm1 = tr.span("a", x=1)
        cm2 = tr.span("b")
        # shared null context: no allocation per span when disabled
        assert cm1 is cm2
        with cm1 as sp:
            assert sp is None
        tr.instant("nothing")
        assert tr.events() == []

    def test_error_span_recorded(self):
        tr = Tracer()
        tr.configure(True)
        with pytest.raises(ValueError):
            with tr.span("boom"):
                raise ValueError("x")
        (ev,) = tr.events()
        assert ev["args"]["error"] == "ValueError"

    def test_chrome_export_wellformed(self, tmp_path):
        tr = Tracer()
        tr.configure(True)
        with tr.span("parent"):
            with tr.span("child", bytes=10):
                pass
        path = str(tmp_path / "t.trace.json")
        doc = tr.export_chrome(path)
        with open(path) as f:
            loaded = json.load(f)
        assert loaded == json.loads(json.dumps(doc))
        assert loaded["displayTimeUnit"] == "ms"
        for ev in loaded["traceEvents"]:
            assert ev["ph"] in ("X", "i")
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["pid"], int)
            assert isinstance(ev["tid"], int)
            if ev["ph"] == "X":
                assert ev["dur"] >= 0

    def test_event_cap(self):
        tr = Tracer()
        tr.configure(True)
        tr.max_events = 10
        for i in range(20):
            tr.instant("e", i=i)
        assert len(tr.events()) == 10
        assert tr.export_chrome()["otherData"]["droppedEvents"] == 10


def _query_df(s, pdf_l, pdf_r):
    return (s.create_dataframe(pdf_l, 4)
            .join(s.create_dataframe(pdf_r, 2), on="k", how="inner")
            .group_by("tag")
            .agg(F.sum("v").alias("sv"), F.count("*").alias("n")))


def test_query_trace_has_exec_shuffle_and_kernel_spans(session, rng,
                                                       tmp_path):
    """TPC-H-shaped query (scan -> join -> aggregate) with the accelerated
    shuffle manager striped over 2 executors, traced end to end: the
    export must json.load and contain exec-operator, shuffle-fetch and
    kernel-cache spans."""
    n = 4000
    left = pd.DataFrame({"k": rng.integers(0, 40, n).astype(np.int64),
                         "v": rng.random(n) * 100.0})
    right = pd.DataFrame({"k": np.arange(40, dtype=np.int64),
                          "tag": np.array(["t%d" % (i % 7)
                                           for i in range(40)])})
    path = str(tmp_path / "query.trace.json")
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.tpu.trace.path", path)
    session.set_conf("spark.rapids.shuffle.transport.enabled", True)
    session.set_conf("spark.rapids.shuffle.executors", 2)
    session.set_conf("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
    try:
        out = _query_df(session, left, right).collect()
        assert len(out) > 0
    finally:
        # the striped 2-executor pool must not leak into later tests
        if session._shuffle_env is not None:
            for env in session._shuffle_env:
                env.close()
            session._shuffle_env = None
    with open(path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert any(nm.startswith("Tpu") for nm in names), names
    assert "shuffle.fetch" in names, names
    assert any(nm.startswith("kernelcache.") for nm in names), names
    assert "Query" in names
    # tracer window is per query: a second query overwrites the file
    session.create_dataframe(left.head(10), 1).collect()
    with open(path) as f:
        doc2 = json.load(f)
    assert not any(e["name"] == "shuffle.fetch"
                   for e in doc2["traceEvents"])


def test_profile_report(session, rng):
    n = 2000
    pdf = pd.DataFrame({"k": rng.integers(0, 10, n).astype(np.int64),
                        "v": rng.random(n)})
    session.set_conf("spark.rapids.sql.enabled", True)
    df = (session.create_dataframe(pdf, 2).filter(F.col("v") > 0.1)
          .group_by("k").agg(F.sum("v").alias("sv")))
    df.collect()
    text = session.profile_report()
    assert "incl" in text and "excl" in text
    assert "Tpu" in text
    doc = session.profile_json()
    json.dumps(doc)  # machine shape is JSON-serializable
    assert doc["version"] == 1
    assert doc["wall_s"] > 0

    def walk(node):
        yield node
        for c in node["children"]:
            yield from walk(c)
    nodes = list(walk(doc["plan"]))
    assert any(n["op"].startswith("Tpu") for n in nodes)
    for nd in nodes:
        assert nd["exclusive_s"] <= nd["inclusive_s"] + 1e-9
    # root inclusive covers the whole tree's exclusive time
    root = doc["plan"]
    assert root["inclusive_s"] <= doc["wall_s"] + 1e-6


def test_profile_disabled_with_metrics(session, rng):
    session.set_conf("spark.rapids.sql.metrics.enabled", False)
    try:
        pdf = pd.DataFrame({"x": np.arange(10, dtype=np.int64)})
        session.create_dataframe(pdf, 1).filter(F.col("x") > 3).collect()
        assert session.profile_json() is None
        assert session.profile_report() == ""
    finally:
        session.set_conf("spark.rapids.sql.metrics.enabled", True)


def test_trace_summary_tool(tmp_path, capsys, session, rng):
    """tools/trace_summary.py import+run smoke on both artifact kinds."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "trace_summary",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "trace_summary.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    tr = Tracer()
    tr.configure(True)
    with tr.span("TpuProjectExec", op="p"):
        with tr.span("TpuScanExec", op="s"):
            pass
    tr.instant("shuffle.fetch.retry", peer="x")
    tpath = str(tmp_path / "t.trace.json")
    tr.export_chrome(tpath)
    assert mod.main([tpath, "-n", "5"]) == 0
    out = capsys.readouterr().out
    assert "TpuProjectExec" in out
    assert "shuffle.fetch.retry: 1" in out

    pdf = pd.DataFrame({"k": np.arange(100, dtype=np.int64) % 4,
                        "v": rng.random(100)})
    (session.create_dataframe(pdf, 2).group_by("k")
     .agg(F.sum("v").alias("sv"))).collect()
    ppath = str(tmp_path / "q.profile.json")
    session.last_profile.save(ppath)
    assert mod.main([ppath]) == 0
    out = capsys.readouterr().out
    assert "operator" in out


def test_disabled_metrics_no_wrapping(session):
    """Overhead contract: metrics + tracing + compile ledger off ->
    executed_partitions returns the operator's raw partitions
    untouched. With the ledger ON (its default) the wrapper stays — it
    maintains the operator scope compile attribution rides on
    (obs/compileledger.py)."""
    from spark_rapids_tpu.exec.base import ExecContext, PhysicalPlan
    from spark_rapids_tpu.obs.compileledger import LEDGER

    sentinel = [lambda: iter(())]

    class P(PhysicalPlan):
        def partitions(self, ctx):
            return sentinel

    session.set_conf("spark.rapids.sql.metrics.enabled", False)
    try:
        ctx = ExecContext(session.conf, None)
        assert not TRACER.enabled
        assert LEDGER.enabled  # default on -> still wrapped
        assert P().executed_partitions(ctx) is not sentinel
        LEDGER.configure(False)
        try:
            assert P().executed_partitions(ctx) is sentinel
        finally:
            LEDGER.configure(True)
    finally:
        session.set_conf("spark.rapids.sql.metrics.enabled", True)


# ---------------------------------------------------------------------------
# one timeline for a query: the spans that tile an execution, the query
# identifier, and the device programs named by kernel family
# ---------------------------------------------------------------------------

# every span an execution over a Parquet table opens besides the operator
# and sync.* spans (docs/observability.md); thread "query" or "pool"
QUERY_SPANS = ("query.begin", "plan.logical", "plan.rewrite",
               "plan.partitions", "Query", "scan.chunk", "scan.upload",
               "upload.build", "upload.put", "collect.concat",
               "query.finish",
               # the scan's planning, the scan pull outside its wait and
               # its upload, an exchange's drain
               "scan.plan.splits", "scan.plan.stats", "scan.host.take",
               "scan.host.stats", "scan.host.meter", "scan.host.release",
               "exchange.drain")
# the children of the TpuScanExec operator span: siblings, disjoint
SCAN_PULL_SPANS = ("scan.host.take", "scan.prefetch.stall",
                   "scan.host.stats", "scan.chunk", "scan.upload",
                   "scan.host.meter", "scan.host.release")
POOL_SPANS = ("scan.decode", "scan.decode.read", "scan.decode.convert")


@pytest.fixture
def parquet_table(tmp_path, rng):
    n = 3000
    pdf = pd.DataFrame({"k": rng.integers(0, 7, n).astype(np.int64),
                        "tag": np.array(["t%d" % (i % 5) for i in range(n)]),
                        "v": rng.random(n)})
    path = str(tmp_path / "t.parquet")
    pdf.to_parquet(path, index=False)
    return path


def _agg_over(session, path):
    return (session.read.parquet(path).filter(F.col("v") > 0.05)
            .group_by("tag").agg(F.sum("v").alias("sv")))


@pytest.fixture
def traced_events(session, parquet_table):
    """The tracer's events after one traced collect() over a Parquet file
    whose one row group is cut into several batches."""
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.sql.batchSizeRows", 1024)
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    out = _agg_over(session, parquet_table).collect()
    assert len(out) == 5
    return TRACER.events()


def _spans(events, name):
    return [e for e in events if e["name"] == name and e["ph"] == "X"]


def _inside(inner, outer):
    return (outer["tid"] == inner["tid"] and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1)


@pytest.mark.parametrize("name", QUERY_SPANS + POOL_SPANS)
def test_span_present_with_the_query_id(traced_events, name):
    spans = _spans(traced_events, name)
    assert spans, sorted({e["name"] for e in traced_events})
    queries = {e["args"].get("query") for e in traced_events}
    assert len(queries) == 1 and None not in queries, queries
    main = _spans(traced_events, "Query")[0]["tid"]
    on_main = {e["tid"] == main for e in spans}
    assert on_main == {name in QUERY_SPANS}, (name, on_main)


@pytest.mark.parametrize("inner,outer", [
    ("upload.build", "scan.upload"), ("upload.put", "scan.upload"),
    ("upload.build", "sync.scan.upload"),
    ("scan.decode.read", "scan.decode"),
    ("scan.decode.convert", "scan.decode"),
    ("plan.partitions", "Query"), ("scan.upload", "Query"),
    ("scan.plan.splits", "plan.partitions"),
    ("scan.plan.stats", "plan.partitions"),
    ("scan.host.take", "TpuScanExec"), ("scan.host.stats", "TpuScanExec"),
    ("scan.host.meter", "TpuScanExec"),
    ("scan.host.release", "TpuScanExec"),
    ("TpuScanExec", "exchange.drain"),
    ("exchange.drain", "TpuShuffleExchangeExec")])
def test_span_nests_by_time_on_its_thread(traced_events, inner, outer):
    outers = _spans(traced_events, outer)
    inners = _spans(traced_events, inner)
    assert inners and outers
    for e in inners:
        assert any(_inside(e, o) for o in outers), (inner, outer, e)
    assert all(e["args"]["depth"] >= 1 for e in inners)


def test_chunk_copy_is_a_sibling_of_the_upload(traced_events):
    chunks = _spans(traced_events, "scan.chunk")
    uploads = _spans(traced_events, "scan.upload")
    assert len(chunks) == len(uploads) == 3  # 3000 rows in 1024-row batches
    assert sum(e["args"]["rows"] for e in chunks) == 3000
    for c in chunks:
        assert not any(_inside(c, u) or _inside(u, c) for u in uploads)
    # top-level spans of the query's thread follow each other: no root span
    main = _spans(traced_events, "Query")[0]["tid"]
    top = sorted((e for e in traced_events if e["tid"] == main
                  and e["ph"] == "X" and e["args"]["depth"] == 0),
                 key=lambda e: e["ts"])
    assert [e["name"] for e in top][:4] == [
        "query.begin", "plan.logical", "plan.rewrite", "Query"]
    assert [e["name"] for e in top][-2:] == ["query.finish", "collect.concat"]
    for a, b in zip(top, top[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1


def test_scan_pull_spans_are_disjoint_children_of_the_operator_span(
        traced_events):
    pulls = sorted((e for e in traced_events
                    if e["name"] in SCAN_PULL_SPANS and e["ph"] == "X"),
                   key=lambda e: e["ts"])
    assert {e["name"] for e in pulls} >= set(SCAN_PULL_SPANS) - {
        "scan.prefetch.stall"}
    for e in pulls:
        assert e["args"]["parent"] == "TpuScanExec", e
    for a, b in zip(pulls, pulls[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1, (a, b)


def test_an_exchange_drain_precedes_its_collapse(traced_events):
    drains = _spans(traced_events, "exchange.drain")
    collapses = _spans(traced_events, "exchange.collapse")
    assert len(drains) == len(collapses) == 1
    drain, collapse = drains[0], collapses[0]
    assert drain["ts"] + drain["dur"] <= collapse["ts"] + 1
    assert drain["args"]["parent"] == collapse["args"]["parent"] \
        == "TpuShuffleExchangeExec"
    assert drain["args"]["batches"] == collapse["args"]["batches"] >= 1
    assert drain["args"]["claimed"] is False
    assert drain["args"]["compacted"] == 0
    # under its bound (a quarter of the metered HBM budget) the collapse
    # is the one piece
    assert collapse["args"]["pieces"] == 1
    assert collapse["args"]["bound_bytes"] > 0


@pytest.mark.parametrize("scale,form", [(1, "dense"), (1 << 28, "sort")])
def test_a_join_probe_says_its_form_and_counts_its_sort_rows(
        session, rng, scale, form, monkeypatch):
    """``dispatch.join`` of a probe carries ``form`` (and, dense, the
    table's ``slots``); ``join.probe.sortRows`` grows by the stream rows
    the sort probe took (as the host knows them) and by 0 where the dense
    table took them; ``join.probe.compactRows`` by 0 in either, as
    neither probed a compact table; every collapse adds one
    ``exchange.collapse.pieces``."""
    from spark_rapids_tpu.obs.metrics import REGISTRY
    # the bounds of this test's scans alone (the session's registry
    # unions every scan of a column name it has seen)
    monkeypatch.setattr(session, "column_stats", {})
    monkeypatch.setattr(session, "column_aliases", {})
    n = 2000
    left = pd.DataFrame({"k": rng.integers(0, 500, n) * scale,
                         "v": rng.random(n)})
    right = pd.DataFrame({"k2": np.arange(500, dtype=np.int64) * scale,
                          "w": rng.random(500)})
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.sql.autoBroadcastJoinThreshold", "-1")
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    names = ("join.probe.sortRows", "exchange.collapse.pieces",
             "join.probe.compactRows")
    before = [REGISTRY.value(m) for m in names]
    out = (session.create_dataframe(left, 2)
           .join(session.create_dataframe(right, 1), left_on=["k"],
                 right_on=["k2"]).collect())
    assert len(out) == n
    events = TRACER.events()
    probes = [e["args"] for e in _spans(events, "dispatch.join")
              if "form" in e["args"]]
    assert probes and {a["form"] for a in probes} == {form}
    assert {a["type"] for a in probes} == {"inner"}
    # keys 0..499: the smallest table, 1024 slots
    assert {a.get("slots") for a in probes} \
        == {1024 if form == "dense" else None}
    sort_rows, pieces, compact_rows = (REGISTRY.value(m) - b
                                       for m, b in zip(names, before))
    # the stream is one collapse of two batches: its capacity
    assert sort_rows == (0 if form == "dense" else 2048)
    assert compact_rows == 0
    assert any(m.name == "join.probe.compactRows"
               for m in REGISTRY.metrics())
    assert pieces == len(_spans(events, "exchange.collapse")) == 2


def test_children_cover_a_scan_pull_of_a_full_batch(session, tmp_path, rng):
    """A split of 2^20 rows, one batch: what the query's thread does in a
    TpuScanExec pull lies inside the pull's child spans (at least 0.8 of
    its seconds here; the chip's share is PERF.md's)."""
    n = 1 << 20
    path = str(tmp_path / "big.parquet")
    pd.DataFrame({"k": rng.integers(0, 7, n).astype(np.int64),
                  "q": rng.integers(0, 50, n).astype(np.int32),
                  "v": rng.random(n)}).to_parquet(path, index=False)
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    q = (session.read.parquet(path).filter(F.col("q") < 24)
         .group_by("k").agg(F.sum("v").alias("sv")))
    q.collect()
    assert len(q.collect()) == 7        # the second execution is the warm one
    events = TRACER.events()
    scans = _spans(events, "TpuScanExec")
    kids = [e for e in events if e["ph"] == "X"
            and e["args"].get("parent") == "TpuScanExec"]
    assert {e["name"] for e in kids} <= set(SCAN_PULL_SPANS)
    for e in kids:
        assert any(_inside(e, s) for s in scans), e
    covered = sum(e["dur"] for e in kids) / sum(e["dur"] for e in scans)
    assert 0.8 <= covered <= 1.0, covered
    release = _spans(events, "scan.host.release")[0]["args"]
    stats = _spans(events, "scan.host.stats")[0]["args"]
    # the frame's three columns and the worker's prepared copies of them;
    # the footer declared k and q, so the upload measures neither again
    assert release["bytes"] >= n * (8 + 4 + 8)
    assert (stats["rows"], stats["columns"]) == (n, 0)
    assert _spans(events, "scan.plan.stats")[0]["args"]["columns"] == 2


def test_span_attributes(traced_events):
    takes = [e["args"] for e in _spans(traced_events, "scan.host.take")]
    assert [a["split"] for a in takes] == [0, 0]
    assert takes[0]["submitted"] == 1 and takes[1]["hit"] in (True, False)
    stats = _spans(traced_events, "scan.host.stats")[0]["args"]
    assert stats["rows"] == 3000 and stats["columns"] == 0
    assert all(e["args"]["bytes"] > 0
               for e in _spans(traced_events, "scan.host.meter"))
    assert _spans(traced_events, "scan.host.release")[0]["args"][
        "bytes"] > 3000 * 8
    splits = _spans(traced_events, "scan.plan.splits")[0]["args"]
    assert (splits["files"], splits["splits"], splits["pruned"]) == (1, 1, 0)
    # the query reads tag and v: no integer column to declare or measure
    assert _spans(traced_events, "scan.plan.stats")[0]["args"][
        "columns"] == 0
    read = _spans(traced_events, "scan.decode.read")[0]["args"]
    assert read["file"].endswith("t.parquet") and read["row_group"] == 0
    assert read["bytes"] > 0
    assert _spans(traced_events, "scan.decode.convert")[0]["args"]["rows"] \
        == 3000
    build = _spans(traced_events, "upload.build")[0]["args"]
    assert build["rows"] == 1024 and build["columns"] == 2
    assert build["bytes"] > 0
    assert _spans(traced_events, "upload.put")[0]["args"]["bytes"] \
        == build["bytes"]
    assert _spans(traced_events, "plan.partitions")[0]["args"][
        "partitions"] >= 1
    assert _spans(traced_events, "plan.rewrite")[0]["args"][
        "plan_cache_hit"] in (True, False)
    assert _spans(traced_events, "collect.concat")[0]["args"]["rows"] == 5
    families = {e["name"] for e in traced_events
                if e["name"].startswith("dispatch.")}
    assert {"dispatch.aggupd", "dispatch.aggmrg"} <= families, families
    assert not any(e["name"].startswith("kernelcache.hit")
                   for e in traced_events)


def test_events_after_a_collect_hold_that_query_alone(session, parquet_table):
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    df = _agg_over(session, parquet_table)
    df.collect()
    first = {e["args"]["query"] for e in TRACER.events()}
    df.collect()
    second = {e["args"].get("query") for e in TRACER.events()}
    assert len(first) == len(second) == 1 and first != second
    assert len(_spans(TRACER.events(), "Query")) == 1


def test_a_query_start_keeps_the_spans_of_queries_still_running():
    tr = Tracer()
    tr.configure(True)
    import threading
    started, go_on = threading.Event(), threading.Event()

    def first():
        tr.begin_query()
        with tr.span("query.begin"):
            tr.set_query("q-a")
        started.set()
        go_on.wait(10)
        with tr.span("Query"):
            pass
        tr.end_query("q-a")
    t = threading.Thread(target=first)
    t.start()
    started.wait(10)
    tr.begin_query()  # the second query starts beside the first
    tr.set_query("q-b")
    with tr.span("Query"):
        pass
    tr.instant("marker")
    go_on.set()
    t.join(10)
    by_query = {(e["name"], e["args"]["query"]) for e in tr.events()}
    assert by_query == {("query.begin", "q-a"), ("Query", "q-a"),
                        ("Query", "q-b"), ("marker", "q-b")}
    tr.end_query("q-b")
    tr.begin_query()  # both have ended: a third start drops them
    assert tr.events() == [] and tr.current_query() is None


def test_two_sessions_threads_share_the_tracer(session, parquet_table,
                                               monkeypatch):
    """Two collects on two threads: the second query starts while the
    first is inside its drain, and drops none of the first's spans."""
    import threading
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    in_drain, go_on = threading.Event(), threading.Event()
    drain = type(session)._drain
    main = threading.get_ident()

    def held_drain(self, plan, ctx, conf):
        if threading.get_ident() != main:
            in_drain.set()
            go_on.wait(30)
        return drain(self, plan, ctx, conf)
    monkeypatch.setattr(type(session), "_drain", held_drain)
    t = threading.Thread(
        target=lambda: _agg_over(session, parquet_table).collect())
    t.start()
    assert in_drain.wait(30)
    _agg_over(session, parquet_table).collect()
    go_on.set()
    t.join(60)
    assert not t.is_alive()
    events = TRACER.events()
    queries = {e["args"].get("query") for e in events}
    assert len(queries) == 2 and None not in queries
    for q in queries:
        names = {e["name"] for e in events if e["args"].get("query") == q}
        assert set(QUERY_SPANS) - {"scan.chunk"} <= names, (q, names)
        assert set(POOL_SPANS) <= names, (q, names)


def test_tracing_off_opens_no_span_at_any_site(session, parquet_table,
                                               monkeypatch):
    from spark_rapids_tpu.obs import trace as trace_mod

    def refuse(*a, **kw):
        raise AssertionError("a Span was built with tracing off")
    monkeypatch.setattr(trace_mod.Span, "__init__", refuse)
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.sql.batchSizeRows", 1024)
    assert len(_agg_over(session, parquet_table).collect()) == 5
    assert not TRACER.enabled and TRACER.events() == []
    assert TRACER.span("upload.build", rows=1) is TRACER.span("dispatch.x")


# -- device programs named by kernel family ---------------------------------

def test_kernel_family_is_the_text_before_the_first_bar():
    from spark_rapids_tpu.utils.kernelcache import kernel_family
    assert kernel_family("aggupd|sum(x)|mask=1") == "aggupd"
    assert kernel_family("join|inner|(0,)|(1,)|x0|probe") == "join"
    assert kernel_family("limitstep") == "limitstep"
    assert kernel_family("concat|dm1") == kernel_family("concat|dm0")
    assert kernel_family("exch-rr x|3") == "exch_rr_x"


def test_program_name_depends_on_the_family_alone():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.utils import kernelcache
    x = jnp.arange(8)
    texts, built = [], []

    def build():
        built.append(jax.jit(lambda v: v + 1))
        return built[-1]
    for sig in ("famtest|a=1", "famtest|a=2|b"):
        assert int(kernelcache.cached_jit(sig, build)(x)[0]) == 1
        texts.append(built[-1].lower(x).as_text().splitlines()[0])
    assert len(built) == 2
    assert texts[0] == texts[1]
    assert "@jit_srt_famtest " in texts[0], texts[0]
    static = kernelcache.name_program(
        jax.jit(lambda v, n: v + n, static_argnums=(1,)), "famstatic")
    assert "@jit_srt_famstatic " in static.lower(x, 2).as_text()
    # a builder's plain closure has nothing to name, and is handed back
    plain = lambda v: v  # noqa: E731
    assert kernelcache.name_program(plain, "fam") is plain
    assert plain.__name__ == "<lambda>"


@pytest.mark.parametrize("qname", ["q5", "q6"])
def test_tpch_kernels_lower_to_modules_named_by_family(session, qname,
                                                       monkeypatch):
    from spark_rapids_tpu.models import tpch_data
    from spark_rapids_tpu.models.tpch import QUERIES
    from spark_rapids_tpu.utils import kernelcache
    kernelcache.clear()
    calls = {}
    wrap = kernelcache._wrap_ledgered

    def recording(signature, fn, span_attrs=None):
        wrapped = wrap(signature, fn, span_attrs)

        def rec(*a, **kw):
            calls.setdefault(signature, (fn, a, kw))
            return wrapped(*a, **kw)
        return rec
    monkeypatch.setattr(kernelcache, "_wrap_ledgered", recording)
    sf = 0.002
    tables = {name: gen(sf) for name, gen in tpch_data.ALL_TABLES.items()}
    tables["nation"] = tpch_data.gen_nation()
    tables["region"] = tpch_data.gen_region()
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.sql.shuffle.partitions", 2)
    try:
        out = QUERIES[qname](session, {
            name: session.create_dataframe(df, 3 if len(df) > 50 else 1)
            for name, df in tables.items()}).collect()
    finally:
        kernelcache.clear()  # the recording wrappers must not outlive this
    assert len(out) > 0 and calls
    for signature, (fn, a, kw) in calls.items():
        family = kernelcache.kernel_family(signature)
        head = fn.lower(*a, **kw).as_text().splitlines()[0]
        assert f"@jit_srt_{family} " in head, (signature[:80], head)
