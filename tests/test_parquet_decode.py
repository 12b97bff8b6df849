"""Device-resident Parquet decode (ops/parquet_decode.py,
sql/parquet_raw.py, docs/scan_device.md): value equality against the
pandas decode oracle across every supported encoding, per-column
fallback mixing, encoded-page cache behaviour under pressure and mtime
churn, the deviceDecode-off identity pin, and the chipless q6
host-decode-byte evidence."""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.obs.metrics import REGISTRY

pytestmark = pytest.mark.smoke


def _metric(name):
    for m in REGISTRY.metrics():
        if m.name == name:
            return m.value
    return 0


def _read(session, path, device):
    session.set_conf("spark.rapids.sql.scan.deviceDecode", device)
    try:
        return session.read.parquet(str(path)).collect()
    finally:
        session.set_conf("spark.rapids.sql.scan.deviceDecode", False)


def _assert_equal(a, b):
    assert list(a.columns) == list(b.columns)
    assert len(a) == len(b)
    for c in a.columns:
        av, bv = a[c], b[c]
        assert av.isna().tolist() == bv.isna().tolist(), c
        ok = ~av.isna()
        if av.dtype.kind == "f" or str(av.dtype).startswith("Float"):
            assert np.allclose(av[ok].astype(float),
                               bv[ok].astype(float)), c
        else:
            assert av[ok].tolist() == bv[ok].tolist(), c


# --------------------------------------------------------------------------
# encoding coverage: device output == host-decode oracle
# --------------------------------------------------------------------------

def test_plain_and_dict_types_match_oracle(session, tmp_path, rng):
    """pandas-written files (dictionary encoding on, multiple row
    groups): int64, float64, bool, dict strings, nullable Int64."""
    rows = 600
    df = pd.DataFrame({
        "i": np.arange(rows, dtype=np.int64),
        "f": rng.random(rows),
        "b": (np.arange(rows) % 3 == 0),
        "s": [f"str{k % 13}" for k in range(rows)],
        "ni": pd.array([None if k % 7 == 0 else k for k in range(rows)],
                       dtype="Int64"),
        "ns": [None if k % 5 == 0 else f"v{k % 9}" for k in range(rows)],
    })
    p = tmp_path / "t.parquet"
    df.to_parquet(str(p), row_group_size=50, index=False)
    host = _read(session, p, False)
    dev = _read(session, p, True)
    _assert_equal(host, dev)
    assert _metric("scan.device.splits") > 0


def test_delta_binary_packed(session, tmp_path, rng):
    rows = 500
    tbl = pa.table({
        "d64": pa.array(np.cumsum(
            rng.integers(-50, 90, rows)).astype(np.int64)),
        "d32": pa.array(rng.integers(-10000, 10000, rows)
                        .astype(np.int32)),
    })
    p = tmp_path / "d.parquet"
    pq.write_table(tbl, str(p), row_group_size=128, use_dictionary=False,
                   column_encoding={"d64": "DELTA_BINARY_PACKED",
                                    "d32": "DELTA_BINARY_PACKED"})
    _assert_equal(_read(session, p, False), _read(session, p, True))


def test_plain_byte_array_strings(session, tmp_path):
    rows = 300
    tbl = pa.table({
        "s": pa.array([None if k % 11 == 0
                       else f"unique-{k}-{'x' * (k % 23)}"
                       for k in range(rows)]),
        "e": pa.array(["" if k % 2 else f"p{k}" for k in range(rows)]),
    })
    p = tmp_path / "s.parquet"
    pq.write_table(tbl, str(p), row_group_size=100, use_dictionary=False)
    _assert_equal(_read(session, p, False), _read(session, p, True))


def test_timestamps_and_small_ints(session, tmp_path):
    rows = 240
    df = pd.DataFrame({
        "ts": pd.date_range("2021-03-01", periods=rows, freq="37min"),
        "i8": np.arange(rows, dtype=np.int8),
        "i16": (np.arange(rows) * 7 - 500).astype(np.int16),
    })
    p = tmp_path / "ts.parquet"
    df.to_parquet(str(p), row_group_size=80, index=False)
    _assert_equal(_read(session, p, False), _read(session, p, True))


def test_multi_page_chunks(session, tmp_path, rng):
    """A tiny data-page size forces many pages per column chunk — the
    multi-page concat path (merged run tables, per-page base bits)."""
    rows = 2000
    tbl = pa.table({
        "i": pa.array(rng.integers(0, 1 << 40, rows).astype(np.int64)),
        "s": pa.array([f"s{k % 7}" for k in range(rows)]),
        "ni": pa.array([None if k % 9 == 0 else k for k in range(rows)],
                       type=pa.int64()),
    })
    p = tmp_path / "mp.parquet"
    pq.write_table(tbl, str(p), row_group_size=1000,
                   data_page_size=1024)
    _assert_equal(_read(session, p, False), _read(session, p, True))


def test_all_null_and_empty(session, tmp_path):
    tbl = pa.table({
        "an": pa.array([None] * 64, type=pa.int64()),
        "asn": pa.array([None] * 64, type=pa.string()),
        "i": pa.array(list(range(64)), type=pa.int32()),
    })
    p = tmp_path / "an.parquet"
    pq.write_table(tbl, str(p), row_group_size=32)
    _assert_equal(_read(session, p, False), _read(session, p, True))
    pe = tmp_path / "empty.parquet"
    pq.write_table(tbl.slice(0, 0), str(pe))
    host, dev = _read(session, pe, False), _read(session, pe, True)
    assert len(host) == len(dev) == 0
    assert list(host.columns) == list(dev.columns)


# --------------------------------------------------------------------------
# fallback mixing + journaling
# --------------------------------------------------------------------------

def test_fallback_mixing_unsupported_encoding(session, tmp_path):
    """An unsupported encoding falls back PER COLUMN: the supported
    sibling stays on the device path, the query stays correct, and the
    fallback is journaled with a reason (scanDeviceFallback)."""
    from spark_rapids_tpu.obs.events import EVENTS
    rows = 120
    tbl = pa.table({
        "i": pa.array(np.arange(rows, dtype=np.int64)),
        "bss": pa.array(np.linspace(0.0, 1.0, rows)),
    })
    p = tmp_path / "mix.parquet"
    pq.write_table(tbl, str(p), use_dictionary=False,
                   column_encoding={"i": "PLAIN",
                                    "bss": "BYTE_STREAM_SPLIT"})
    fb0 = _metric("scan.device.fallbackColumns")
    dc0 = _metric("scan.device.columns")
    dev = _read(session, p, True)
    _assert_equal(_read(session, p, False), dev)
    assert _metric("scan.device.fallbackColumns") > fb0
    assert _metric("scan.device.columns") > dc0, \
        "the supported column must stay on the device path"
    evs = [e for e in EVENTS.flight_events()
           if e.get("kind") == "scanDeviceFallback"]
    assert any(e.get("column") == "bss" and "BYTE_STREAM_SPLIT"
               in str(e.get("reason")) for e in evs), evs


def test_device_decode_off_identity(session, tmp_path, rng):
    """The rollback pin: deviceDecode off never consults the raw-page
    path (scan.device.splits stays flat) and the output matches the
    pandas read exactly — the legacy scan is byte-identical."""
    rows = 150
    df = pd.DataFrame({
        "i": np.arange(rows, dtype=np.int64),
        "s": [f"w{k % 5}" for k in range(rows)],
    })
    p = tmp_path / "off.parquet"
    df.to_parquet(str(p), row_group_size=50, index=False)
    s0 = _metric("scan.device.splits")
    out = _read(session, p, False)
    assert _metric("scan.device.splits") == s0
    pd.testing.assert_frame_equal(
        out.reset_index(drop=True), df.reset_index(drop=True))


# --------------------------------------------------------------------------
# encoded-page cache tier (memory/spill.py EncodedPageCache)
# --------------------------------------------------------------------------

def test_page_cache_warm_scan_no_file_reads(session, tmp_path, rng):
    """The cache-warm second scan touches ZERO host file bytes: every
    column chunk replays from the encoded-page cache."""
    rows = 400
    df = pd.DataFrame({"i": np.arange(rows, dtype=np.int64),
                       "f": rng.random(rows)})
    p = tmp_path / "warm.parquet"
    df.to_parquet(str(p), row_group_size=100, index=False)
    session.set_conf("spark.rapids.sql.cacheDeviceScans", False)
    try:
        first = _read(session, p, True)
        fr0 = _metric("scan.device.fileReads")
        frb0 = _metric("scan.device.fileReadBytes")
        second = _read(session, p, True)
        assert _metric("scan.device.fileReads") == fr0
        assert _metric("scan.device.fileReadBytes") == frb0
        _assert_equal(first, second)
    finally:
        session.set_conf("spark.rapids.sql.cacheDeviceScans", True)


def test_page_cache_mtime_invalidation(session, tmp_path):
    """Rewriting a file invalidates its cached pages (mtime rides the
    cache key): the next scan sees the NEW data, never a stale page."""
    p = tmp_path / "inv.parquet"
    pd.DataFrame({"i": np.arange(100, dtype=np.int64)}).to_parquet(
        str(p), row_group_size=50, index=False)
    session.set_conf("spark.rapids.sql.cacheDeviceScans", False)
    try:
        out1 = _read(session, p, True)
        assert out1["i"].tolist() == list(range(100))
        pd.DataFrame({"i": np.arange(100, 200, dtype=np.int64)}
                     ).to_parquet(str(p), row_group_size=50, index=False)
        os.utime(str(p), (1, 2_000_000_000))  # force a distinct mtime
        out2 = _read(session, p, True)
        assert out2["i"].tolist() == list(range(100, 200))
    finally:
        session.set_conf("spark.rapids.sql.cacheDeviceScans", True)


def test_page_cache_eviction_under_pressure():
    """Unit level: the host-tier byte budget evicts LRU-first, the
    device tier demotes instead of evicting, and counters track both."""
    from spark_rapids_tpu.memory.spill import EncodedPageCache
    ev0 = _metric("pagecache.evictions")
    dm0 = _metric("pagecache.demotions")
    c = EncodedPageCache(max_bytes=1000, device_max_bytes=500)
    for k in range(10):
        c.put(("f", 0.0, 0, k), {"col": k}, 300)
    st = c.stats
    assert st["bytes"] <= 1000
    assert st["entries"] <= 3
    assert _metric("pagecache.evictions") > ev0
    # oldest keys are gone, newest survive
    assert c.get(("f", 0.0, 0, 0)) is None
    assert c.get(("f", 0.0, 0, 9)) is not None
    # device tier: promotions demote colder residents instead of
    # dropping the host-tier entry
    live = [k for k in range(10) if c.get(("f", 0.0, 0, k)) is not None]
    for k in live:
        c.promote(("f", 0.0, 0, k), {"dev": k}, 300)
    assert c.stats["deviceBytes"] <= 500
    assert _metric("pagecache.demotions") > dm0
    assert c.get_device(("f", 0.0, 0, live[-1])) is not None
    c.clear()
    assert c.stats["entries"] == 0


# --------------------------------------------------------------------------
# observability plumbing
# --------------------------------------------------------------------------

def test_profile_scan_decode_mode_verdicts():
    from spark_rapids_tpu.obs.profile import scan_decode_mode
    assert scan_decode_mode({}) == "host"
    assert scan_decode_mode({"scan.device.splits": 3}) == "device"
    assert scan_decode_mode({"scan.device.splits": 3,
                             "scan.device.fallbackColumns": 1}) == "mixed"
    assert scan_decode_mode({"scan.device.splits": 3,
                             "scan.device.hostReads": 2}) == "mixed"


def test_qualification_ranks_fallback_reasons():
    from tools.qualification import records_from_events, build_report
    events = [
        {"kind": "queryStart", "query": "qa", "ts": 1.0},
        {"kind": "scanDeviceFallback", "query": "qa", "ts": 1.1,
         "column": "bss", "reason": "enc:BYTE_STREAM_SPLIT"},
        {"kind": "scanDeviceFallback", "query": "qa", "ts": 1.2,
         "column": "blob", "reason": "enc:BYTE_STREAM_SPLIT"},
        {"kind": "scanDeviceFallback", "query": "qa", "ts": 1.3,
         "column": "nest", "reason": "nested"},
        {"kind": "queryEnd", "query": "qa", "ts": 2.0, "status": "ok"},
    ]
    recs = records_from_events(events, source="test")
    rep = build_report(recs)
    ranked = rep["scan_device_fallbacks"]
    assert ranked and ranked[0]["reason"] == "enc:BYTE_STREAM_SPLIT"
    assert ranked[0]["count"] == 2
    assert set(ranked[0]["columns"]) == {"bss", "blob"}
    assert ranked[1]["reason"] == "nested"
    from tools.qualification import render_text
    txt = render_text(rep)
    assert "device-decode fallback reasons" in txt


def test_status_snapshot_scan_decode_section(session, tmp_path, rng):
    from spark_rapids_tpu.obs.monitor import status_snapshot
    rows = 120
    pd.DataFrame({"i": np.arange(rows, dtype=np.int64)}).to_parquet(
        str(tmp_path / "m.parquet"), row_group_size=60, index=False)
    _read(session, tmp_path / "m.parquet", True)
    snap = status_snapshot()
    sd = snap.get("scanDecode")
    assert sd and sd["mode"] in ("device", "mixed")
    assert sd["device"].get("splits", 0) > 0
    assert "pageCache" in sd


# --------------------------------------------------------------------------
# chipless perf evidence: q6 over parquet
# --------------------------------------------------------------------------

def test_q6_host_decode_bytes_cut(session, tmp_path):
    """The headline deterministic evidence: with deviceDecode on, q6's
    HOST-side decoded bytes drop at least 4x against the classic
    pipelined scan (here: to zero — every lineitem column q6 touches
    rides the device kernels), while the device path demonstrably did
    the work and produced the same answer."""
    from spark_rapids_tpu.models import tpch_data
    from spark_rapids_tpu.models.tpch import QUERIES
    p = str(tmp_path / "lineitem.parquet")
    li = tpch_data.gen_lineitem(0.002)
    li.to_parquet(p, row_group_size=max(len(li) // 3, 1), index=False)
    session.set_conf("spark.rapids.sql.cacheDeviceScans", False)
    try:
        def run():
            tables = {"lineitem": session.read.parquet(p)}
            return QUERIES["q6"](session, tables).collect()

        b0 = _metric("scan.prefetch.bytesDecoded")
        session.set_conf("spark.rapids.sql.scan.deviceDecode", False)
        classic = run()
        classic_bytes = _metric("scan.prefetch.bytesDecoded") - b0
        assert classic_bytes > 0

        session.set_conf("spark.rapids.sql.scan.deviceDecode", True)
        h0 = _metric("scan.device.bytesHost")
        d0 = _metric("scan.device.bytesDevice")
        dev = run()
        host_bytes = _metric("scan.device.bytesHost") - h0
        dev_bytes = _metric("scan.device.bytesDevice") - d0
        assert dev_bytes > 0, "device path did no work"
        assert host_bytes * 4 <= classic_bytes, (
            f"host decode bytes not cut 4x: classic={classic_bytes} "
            f"device-mode host={host_bytes}")
        pd.testing.assert_frame_equal(classic, dev)
    finally:
        session.set_conf("spark.rapids.sql.scan.deviceDecode", False)
        session.set_conf("spark.rapids.sql.cacheDeviceScans", True)


# --------------------------------------------------------------------------
# slow tier: full-suite oracle sweeps over parquet sources
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_tpch_device_decode_sweep(session, tmp_path):
    from spark_rapids_tpu.models import tpch_data
    from spark_rapids_tpu.models.tpch import QUERIES
    tpch_data.write_parquet(str(tmp_path), 0.01)
    names = ["lineitem", "orders", "customer", "supplier", "part",
             "partsupp", "nation", "region"]
    outs = {}
    for dev in (False, True):
        session.set_conf("spark.rapids.sql.scan.deviceDecode", dev)
        try:
            tables = {n: session.read.parquet(
                str(tmp_path / f"{n}.parquet")) for n in names}
            outs[dev] = {q: QUERIES[q](session, tables).collect()
                         for q in ("q1", "q3", "q6", "q14")}
        finally:
            session.set_conf("spark.rapids.sql.scan.deviceDecode", False)
    for q in outs[False]:
        _assert_equal(outs[False][q], outs[True][q])


@pytest.mark.slow
def test_tpcxbb_device_decode_sweep(session, tmp_path):
    from spark_rapids_tpu.models import tpcxbb_data
    from spark_rapids_tpu.models.tpcxbb import QUERIES
    data = {name: fn(0.05, None)
            for name, fn in tpcxbb_data.ALL_TABLES.items()}
    for name, df in data.items():
        df.to_parquet(str(tmp_path / f"{name}.parquet"),
                      row_group_size=max(len(df) // 2, 1), index=False)
    outs = {}
    for dev in (False, True):
        session.set_conf("spark.rapids.sql.scan.deviceDecode", dev)
        try:
            tables = {n: session.read.parquet(
                str(tmp_path / f"{n}.parquet")) for n in data}
            outs[dev] = {q: QUERIES[q](session, tables).collect()
                         for q in ("q6", "q7", "q9")}
        finally:
            session.set_conf("spark.rapids.sql.scan.deviceDecode", False)
    for q in outs[False]:
        _assert_equal(outs[False][q], outs[True][q])
