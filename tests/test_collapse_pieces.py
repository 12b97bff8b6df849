"""A local exchange collapse whose one batch would pass its bound
(exec/tpu._collapse_bound_bytes) hands its consumer consecutive pieces, each
concatenated as the drain reaches its end; a join streams them in rounds
(exec/tpujoin._rounds), and an inner join whose build passes the bound
streams that side against a build of the other (``_swapped``). The bound is
reached here by monkeypatching the function that computes it."""

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.columnar.batch import (
    DeviceBatch, Schema, bucket_capacity,
)
from spark_rapids_tpu.exec import tpu as tpu_exec
from spark_rapids_tpu.exec.base import ExecContext, PhysicalPlan
from spark_rapids_tpu.exec.tpu import TpuShuffleExchangeExec, _row_bytes
from spark_rapids_tpu.exec.tpujoin import TpuShuffledHashJoinExec
from spark_rapids_tpu.models import tpch_data
from spark_rapids_tpu.models.tpch import QUERIES
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.obs.trace import TRACER
from spark_rapids_tpu.sql import functions as F
from tests.querytest import assert_frames_equal, assert_tpu_and_cpu_equal

NO_BROADCAST = {"spark.rapids.sql.autoBroadcastJoinThreshold": "-1"}
BIG_PARTS, PART_ROWS = 8, 400   # the side that is cut: 8 batches of 512


def _bound_of(batches_a_piece: int, row_bytes: int) -> int:
    """A bound that lets a piece take ``batches_a_piece`` batches of
    PART_ROWS rows of ``row_bytes`` and no more."""
    return bucket_capacity(batches_a_piece * PART_ROWS) * row_bytes


@pytest.fixture
def bound(monkeypatch):
    def set_bound(nbytes: int) -> None:
        monkeypatch.setattr(tpu_exec, "_collapse_bound_bytes",
                            lambda: nbytes)
    return set_bound


def _sides(rng, big: str, scale: int = 1):
    """(left, right): the ``big`` side has BIG_PARTS x PART_ROWS rows over
    keys 0..299 with nulls, the other 150 rows over keys 0..199 (some keys
    repeat, some match nothing on either side), every key times
    ``scale``."""
    n = BIG_PARTS * PART_ROWS
    k = pd.array(rng.integers(0, 300, n) * scale, dtype="Int64")
    k[rng.random(n) < 0.05] = pd.NA
    bigf = pd.DataFrame({"k": k, "a": rng.integers(-5, 5, n),
                         "v": rng.random(n)})
    small = pd.DataFrame({"k2": rng.integers(0, 200, 150) * scale,
                          "b": rng.integers(-5, 5, 150),
                          "w": rng.random(150)})
    if big == "left":
        return bigf, small
    return (small.rename(columns={"k2": "k", "b": "a", "w": "v"}),
            bigf.rename(columns={"k": "k2", "a": "b", "v": "w"}))


def _collapse_spans():
    return [e["args"] for e in TRACER.events()
            if e["name"] == "exchange.collapse" and e["ph"] == "X"]


def _joins(session):
    out, stack = [], [session.last_plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuShuffledHashJoinExec):
            out.append(node)
        stack.extend(node.children)
    return out


# how, the side the bound cuts, the residual
_JOINS = {
    "inner": ("inner", "left", None),
    "left": ("left", "left", None),
    "leftsemi": ("leftsemi", "left", None),
    "leftanti": ("leftanti", "left", None),
    "right": ("right", "right", None),
    "full": ("full", "left", None),
    "inner_residual": ("inner", "left", "ne"),
    "leftanti_residual": ("leftanti", "left", "lt"),
}


@pytest.mark.parametrize("case", sorted(_JOINS))
def test_a_stream_in_pieces_matches_the_oracle(case, session, rng, bound):
    how, big, residual = _JOINS[case]
    left, right = _sides(rng, big)
    # two batches a piece: the cut side becomes 4 pieces, the other side
    # (one batch of 150 rows) stays whole
    bound(_bound_of(2, _row_bytes(Schema.from_pandas(left))))

    def q(s):
        on = F.col("k") == F.col("k2")
        if residual == "ne":
            on = on & (F.col("a") != F.col("b"))
        elif residual == "lt":
            on = on & (F.col("a") < F.col("b"))
        return s.create_dataframe(left, BIG_PARTS if big == "left" else 1)\
            .join(s.create_dataframe(right,
                                     BIG_PARTS if big == "right" else 1),
                  on=on, how=how)
    conf = dict(NO_BROADCAST, **{"spark.rapids.tpu.trace.enabled": True})
    before = REGISTRY.value("exchange.collapse.pieces")
    assert_tpu_and_cpu_equal(q, conf=conf, ignore_order=True)
    spans = _collapse_spans()
    # one collapse cut into 4 pieces, one left whole
    assert sorted(s["pieces"] for s in spans) == [1, 1, 2, 3, 4]
    assert REGISTRY.value("exchange.collapse.pieces") - before == 5
    assert {s["bound_bytes"] for s in spans} \
        == {_bound_of(2, _row_bytes(Schema.from_pandas(left)))}
    # the stream took the pieces: nothing was swapped
    assert all(j._swap is None for j in _joins(session))


@pytest.mark.parametrize("big", ["left", "right"])
def test_an_inner_join_streams_a_build_past_the_bound(big, session, rng,
                                                      bound):
    """The planned build of an inner join is its right side: past the
    bound, it streams in its pieces against a build of the left."""
    # keys 2^28 apart: past the dense table's range, so the sort probe
    left, right = _sides(rng, big, scale=1 << 28)
    bound(_bound_of(2, _row_bytes(Schema.from_pandas(left))))
    sort_rows = REGISTRY.counter("join.probe.sortRows")
    before = sort_rows.value

    def q(s):
        return s.create_dataframe(left, BIG_PARTS if big == "left" else 1)\
            .join(s.create_dataframe(right,
                                     BIG_PARTS if big == "right" else 1),
                  left_on=["k"], right_on=["k2"], how="inner")
    got = assert_tpu_and_cpu_equal(q, conf=NO_BROADCAST, ignore_order=True)
    # the columns keep the left side first
    assert list(got.columns) == ["k", "a", "v", "k2", "b", "w"]
    (join,) = _joins(session)
    assert (join._swap is not None) == (big == "right")
    # the stream's rows as the host knows them: 4 pieces of 1024 slots
    assert sort_rows.value - before == 4 * 1024


@pytest.mark.parametrize("top,form", [
    (1023, "packed"), (2047, "compact"), (2048, "sort")])
def test_a_swapped_join_takes_the_table_its_key_range_fits(
        top, form, session, rng, bound, monkeypatch):
    """With the dense table's HBM cut to 16 KiB, a build key range of
    1024 fits the packed table (16 bytes a slot), one of 2048 only the
    compact one (8 bytes a slot), and one of 2049 neither: the sort
    probe. Each gives the oracle's rows; the stream's pieces count where
    their probe took them."""
    monkeypatch.setattr(TpuShuffledHashJoinExec, "_DENSE_TABLE_BYTES",
                        2048 * 8)
    # the bounds of this test's scans alone (the session's registry
    # unions every scan of a column name it has seen)
    monkeypatch.setattr(session, "column_stats", {})
    monkeypatch.setattr(session, "column_aliases", {})
    left, right = _sides(rng, "right", scale=top // 200)
    # the build (the left side, swapped in) spans exactly 0..top
    left.loc[0, "k"], left.loc[1, "k"] = 0, top
    bound(_bound_of(2, _row_bytes(Schema.from_pandas(left))))
    names = ("join.probe.compactRows", "join.probe.sortRows")
    before = [REGISTRY.value(n) for n in names]
    session.set_conf("spark.rapids.tpu.trace.enabled", True)

    def q(s):
        return s.create_dataframe(left, 1).join(
            s.create_dataframe(right, BIG_PARTS), left_on=["k"],
            right_on=["k2"], how="inner")
    assert_tpu_and_cpu_equal(q, conf=NO_BROADCAST, ignore_order=True)
    (join,) = _joins(session)
    assert join._swap is not None
    compact_rows, sort_rows = (REGISTRY.value(n) - b
                               for n, b in zip(names, before))
    # the stream's rows as the host knows them: 4 pieces of 1024 slots
    assert compact_rows == (4 * 1024 if form == "compact" else 0)
    assert sort_rows == (4 * 1024 if form == "sort" else 0)
    probes = {(e["args"]["form"], e["args"].get("slots"))
              for e in TRACER.events() if e["name"] == "dispatch.join"
              and "form" in e["args"]}
    assert probes == {{"packed": ("dense", 1024), "compact": ("dense", 2048),
                       "sort": ("sort", None)}[form]}


def test_a_collapse_within_its_bound_is_the_one_concat(session, rng,
                                                       monkeypatch):
    """Under the bound the drain takes every batch and one _collapse_concat
    call makes the one batch, as it did before there was a bound."""
    calls = []
    real = tpu_exec._collapse_concat

    def recording(batches, *a, **kw):
        calls.append((len(batches), a, kw))
        return real(batches, *a, **kw)
    monkeypatch.setattr(tpu_exec, "_collapse_concat", recording)
    src = _Source(rng, BIG_PARTS)
    out = _drain_exchange(session, src)
    assert len(out) == 1 and len(calls) == 1
    n, args, kw = calls[0]
    # no masks, none compacted, the collapse's first and only piece
    assert n == BIG_PARTS and args[2:] == (None, 0, 1) and kw == {}
    _same_rows(out, src)


@pytest.mark.parametrize("per_piece,pieces", [(1, 8), (2, 4), (4, 2)])
def test_pieces_are_consecutive_and_drained_a_group_at_a_time(
        per_piece, pieces, session, rng, bound):
    src = _Source(rng, BIG_PARTS)
    bound(_bound_of(per_piece, _row_bytes(src.schema)))
    ctx = ExecContext(session.conf, session)
    (part,) = TpuShuffleExchangeExec(src, ("hash", [0], 1)).partitions(ctx)
    it = part()
    first = next(it)
    # the group's batches and the one that ended it, nothing further
    assert src.pulls == per_piece + 1
    rest = list(it)
    assert 1 + len(rest) == pieces and src.pulls == BIG_PARTS
    assert first.capacity * _row_bytes(src.schema) \
        <= _bound_of(per_piece, _row_bytes(src.schema))
    _same_rows([first] + rest, src)


class _Source(PhysicalPlan):
    """A leaf of ``n`` batches of PART_ROWS rows that counts its pulls."""
    columnar_output = True

    def __init__(self, rng, n):
        super().__init__([])
        self.frames = [pd.DataFrame({"k": rng.integers(0, 99, PART_ROWS),
                                     "v": rng.random(PART_ROWS)})
                       for _ in range(n)]
        self.schema = Schema.from_pandas(self.frames[0])
        self.pulls = 0

    def output_schema(self):
        return self.schema

    def partitions(self, ctx):
        def run():
            for f in self.frames:
                self.pulls += 1
                yield DeviceBatch.from_pandas(f)
        return [run]


def _drain_exchange(session, src):
    ctx = ExecContext(session.conf, session)
    (part,) = TpuShuffleExchangeExec(src, ("hash", [0], 1)).partitions(ctx)
    return list(part())


def _same_rows(batches, src):
    got = pd.concat([b.to_pandas() for b in batches], ignore_index=True)
    want = pd.concat(src.frames, ignore_index=True)
    assert got["k"].tolist() == want["k"].tolist()
    assert np.array_equal(got["v"].to_numpy(), want["v"].to_numpy())


def _q5_reference(t):
    """TPC-H Q5 (ASIA, 1994) in plain pandas: the semantics of
    benchmarks/queries/q5.py's reference."""
    o = t["orders"]
    o = o[(o.o_orderdate >= pd.Timestamp(1994, 1, 1))
          & (o.o_orderdate < pd.Timestamp(1995, 1, 1))]
    r = t["region"]
    j = (r[r.r_name == "ASIA"]
         .merge(t["nation"], left_on="r_regionkey", right_on="n_regionkey")
         .merge(t["customer"], left_on="n_nationkey",
                right_on="c_nationkey")
         .merge(o, left_on="c_custkey", right_on="o_custkey")
         .merge(t["lineitem"], left_on="o_orderkey", right_on="l_orderkey")
         .merge(t["supplier"], left_on=["l_suppkey", "n_nationkey"],
                right_on=["s_suppkey", "s_nationkey"]))
    j = j.assign(revenue=j.l_extendedprice * (1 - j.l_discount))
    return (j.groupby("n_name", sort=False).agg(revenue=("revenue", "sum"))
            .reset_index().sort_values("revenue", ascending=False)
            .reset_index(drop=True))


def test_q5_with_lineitem_in_pieces_matches_pandas(session, bound):
    sf = 0.01
    t = {name: gen(sf) for name, gen in tpch_data.ALL_TABLES.items()}
    t["nation"], t["region"] = tpch_data.gen_nation(), tpch_data.gen_region()
    cols = ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"]
    t["lineitem"] = t["lineitem"][cols]
    parts = 12
    per = -(-len(t["lineitem"]) // parts)
    # three batches a piece: lineitem (the lineitem join's planned build)
    # is cut into 4 pieces; the other collapses are under the bound
    row = _row_bytes(Schema.from_pandas(t["lineitem"]))
    bound(bucket_capacity(3 * bucket_capacity(per)) * row)
    session.set_conf("spark.rapids.sql.enabled", True)
    session.set_conf("spark.rapids.sql.test.enabled", True)
    session.set_conf("spark.rapids.sql.autoBroadcastJoinThreshold", "-1")
    session.set_conf("spark.rapids.tpu.trace.enabled", True)
    tables = {name: session.create_dataframe(
        df, parts if name == "lineitem" else 1) for name, df in t.items()}
    got = QUERIES["q5"](session, tables).collect()
    assert max(s["pieces"] for s in _collapse_spans()) >= 3
    assert any(j._swap is not None for j in _joins(session))
    want = _q5_reference(t)
    assert len(want) >= 4   # ASIA's nations with a local supplier's line
    assert got["n_name"].tolist() == want["n_name"].tolist()
    assert np.allclose(got["revenue"].to_numpy(), want["revenue"].to_numpy(),
                       rtol=1e-9, atol=0)
    assert_frames_equal(got, want, ignore_order=False, approx=True)
