"""A collapse's claimed filter compacts each batch as it arrives
(exec/tpu._fused_filter_source, _drain_claimed): where the selected columns
are ``rowops.sort_compactable`` the filter's own sorting kernel leaves the
batch prefix-compact and the collapse is the unmasked concat's block
copies; any other batch keeps the mask form (``filtermask`` +
``concatmask``). Either way the rows and their order are the mask form's,
through the exchange and through the broadcast materialization."""

import jax.numpy as jnp
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
from spark_rapids_tpu.exec.base import ExecContext, PhysicalPlan
from spark_rapids_tpu.exec.coalesce import TargetSize, TpuCoalesceBatchesExec
from spark_rapids_tpu.exec.tpu import TpuFilterExec, TpuShuffleExchangeExec
from spark_rapids_tpu.exec.tpujoin import TpuBroadcastExchangeExec
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.obs.trace import TRACER
from spark_rapids_tpu.ops import rowops
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.exprs.core import bind_references
from spark_rapids_tpu.sql.sources import _arrow_decode, _attach_dict_hints

_BATCHES = "exchange.collapse.batches"
_COMPACTED = "exchange.collapse.compactedBatches"


def _hinted(frame: pd.DataFrame) -> pd.DataFrame:
    """``frame`` as a scan's decode worker hands it on: its string columns
    carry the Arrow dictionary hint, so they upload codes-only."""
    table = pa.Table.from_pandas(frame, preserve_index=False)
    return _attach_dict_hints(_arrow_decode(table, True), table)


def _upload(frame, how, state):
    """One batch of ``frame`` with its strings as ``how`` says: ``codes``
    (a codes-only dictionary column), ``chars`` (packed chars and offsets)
    or ``slab`` (fixed-stride words)."""
    if how == "codes":
        return DeviceBatch.from_pandas(_hinted(frame), dict_numerics=False,
                                       dict_state=state)
    return DeviceBatch.from_pandas(frame, dict_numerics=False,
                                   dict_encode=False,
                                   blocked_chars=64 if how == "slab" else 0)


class _Source(PhysicalPlan):
    """A leaf that uploads ``frames`` a batch at a time and records every
    pull in ``log``."""
    columnar_output = True

    def __init__(self, frames, hows, log=None):
        super().__init__([])
        self.frames, self.hows, self.log = frames, hows, log
        self.schema = Schema.from_pandas(frames[0])

    def output_schema(self):
        return self.schema

    def partitions(self, ctx):
        def run():
            state = {}
            for i, (f, how) in enumerate(zip(self.frames, self.hows)):
                if self.log is not None:
                    self.log.append(("pull", i))
                yield _upload(f, how, state)
        return [run]


def _ints(rng, n, null_share=0.2):
    vals = pd.array(rng.integers(-50, 50, n), dtype="Int64")
    vals[rng.random(n) < null_share] = pd.NA
    return vals


def _frames(rng, columns, sizes):
    """Frames of ``sizes`` rows: ``k`` an int64 with nulls (the predicate's
    column), then ``columns`` more: i int64, d float64, j int32, s a string
    of few values, all with nulls."""
    out = []
    for n in sizes:
        data = {"k": _ints(rng, n)}
        for c in columns:
            if c == "i":
                data[c] = _ints(rng, n)
            elif c == "j":
                data[c] = _ints(rng, n).astype("Int32")
            elif c == "d":
                d = pd.array(rng.random(n), dtype="Float64")
                d[rng.random(n) < 0.2] = pd.NA
                data[c] = d
            else:
                s = rng.choice(np.array(["ash", "", "birch", "cedar-wood"],
                                        dtype=object), n)
                s[rng.random(n) < 0.2] = None
                data[c] = pd.array(s, dtype="string")
        out.append(pd.DataFrame(data))
    return out


# name: (other columns, strings as, selected columns or None, compacted)
# over three batches of 40, 0 and 25 rows; ``compacted`` is how many of
# the three the claimed filter hands on compacted
_CASES = {
    "one_column": ((), "chars", ("k",), 3),
    "two_columns": (("i",), "chars", None, 3),
    "three_columns": (("i", "d"), "chars", None, 3),
    "four_columns": (("i", "d", "j"), "chars", None, 3),
    "predicate_column_dropped": (("i", "d", "j", "s"), "chars",
                                 ("i", "d", "j", "s")[:3], 3),
    "codes_only_dictionary": (("i", "s"), "codes", None, 3),
    "five_columns": (("i", "d", "j", "s"), "codes", None, 0),
    "chars_string": (("s",), "chars", None, 0),
    "slab_string": (("s",), "slab", None, 0),
    "mixed_representations": (("s",), ("codes", "chars", "codes"), None, 2),
}


def _plan(kind, source, cond, sel, coalesce=False):
    schema = source.output_schema()
    out_sel = None if sel is None else (
        tuple(sel), tuple(schema.names.index(n) for n in sel))
    node = TpuFilterExec(source, bind_references(cond.expr, schema), out_sel)
    if coalesce:
        node = TpuCoalesceBatchesExec(node, TargetSize(1 << 20))
    if kind == "broadcast":
        return TpuBroadcastExchangeExec(node)
    return TpuShuffleExchangeExec(node, ("single",))


def _run(session, plan, with_session=True):
    """(the collapse's one batch as a frame, families launched, growth of
    the two counters)."""
    ctx = ExecContext(session.conf, session if with_session else None)
    before = (REGISTRY.value(_BATCHES), REGISTRY.value(_COMPACTED))
    TRACER.clear()
    TRACER.configure(True)
    try:
        out = [b for p in plan.partitions(ctx) for b in p()]
    finally:
        TRACER.configure(False)
    try:
        frame = out[0].to_pandas()
    finally:
        # a broadcast parks its table in the session's catalog until the
        # query ends; there is no query here to end
        session.release_transient_buffers()
    families = [e["name"][len("dispatch."):] for e in TRACER.events()
                if e["name"].startswith("dispatch.")]
    spans = [e["args"] for e in TRACER.events()
             if e["name"] == "exchange.collapse"]
    assert len(out) == 1
    grown = (REGISTRY.value(_BATCHES) - before[0],
             REGISTRY.value(_COMPACTED) - before[1])
    return frame, families, grown, spans


def _same_rows(a: pd.DataFrame, b: pd.DataFrame):
    assert list(a.columns) == list(b.columns) and len(a) == len(b)
    for c in a.columns:
        x, y = a[c], b[c]
        assert x.isna().tolist() == y.isna().tolist(), c
        assert x[~x.isna()].tolist() == y[~y.isna()].tolist(), c


@pytest.mark.parametrize("kind", ["exchange", "broadcast"])
@pytest.mark.parametrize("case", sorted(_CASES))
def test_claimed_filter_gives_the_mask_forms_rows(case, kind, session, rng,
                                                  monkeypatch):
    columns, hows, sel, compacted = _CASES[case]
    frames = _frames(rng, columns, (40, 0, 25))
    hows = (hows,) * 3 if isinstance(hows, str) else hows
    # nulls in the predicate too: k > -10 is NULL where k is
    cond = (F.col("k") > -10) & (F.col("k") < 40)
    got, families, grown, spans = _run(
        session, _plan(kind, _Source(frames, hows), cond, sel))
    # which form ran, and what the counters say of it
    assert families.count("filter") == compacted
    assert families.count("filtermask") == 3 - compacted
    assert ("concatmask" in families) == (compacted < 3)
    assert ("concat" in families) == (compacted == 3)
    assert grown == ((3 if kind == "exchange" else 0), compacted)
    if kind == "exchange":
        assert [s["compacted"] for s in spans] == [compacted]
        assert spans[0]["batches"] == 3
        # under its bound the collapse is one piece
        assert [s["pieces"] for s in spans] == [1]
        assert spans[0]["bound_bytes"] > 0
    # the mask form of the same plan over the same batches
    monkeypatch.setattr(rowops, "sort_compactable", lambda cols: False)
    want, masked, _, _ = _run(
        session, _plan(kind, _Source(frames, hows), cond, sel))
    assert "filter" not in masked and masked.count("filtermask") == 3
    _same_rows(got, want)
    # and what pandas keeps, in the batches' order
    whole = pd.concat(frames, ignore_index=True)
    keep = ((whole.k > -10) & (whole.k < 40)).fillna(False).astype(bool)
    kept = whole[keep].reset_index(drop=True)
    _same_rows(got, kept[list(sel)] if sel is not None else kept)
    assert 0 < len(got) < len(whole)


@pytest.mark.parametrize("kind", ["exchange", "broadcast"])
def test_every_row_dropped_and_a_lone_batch(kind, session, rng):
    """A batch that loses every row contributes nothing; a collapse of one
    compacted batch hands it on as it is (no concat at all)."""
    frames = _frames(rng, ("i",), (30, 20))
    frames[0]["k"] = pd.array([-40] * 30, dtype="Int64")  # all dropped
    cond = F.col("k") > 0
    got, families, grown, _ = _run(
        session, _plan(kind, _Source(frames, ("chars",) * 2), cond, None))
    whole = pd.concat(frames, ignore_index=True)
    kept = whole[(whole.k > 0).fillna(False).astype(bool)]
    _same_rows(got, kept.reset_index(drop=True))
    assert grown[1] == 2 and families.count("filter") == 2
    one, families, grown, _ = _run(
        session, _plan(kind, _Source(frames[1:], ("chars",)), cond, None))
    _same_rows(one, frames[1][(frames[1].k > 0).fillna(False).astype(bool)]
               .reset_index(drop=True))
    assert families == ["filter"] and grown[1] == 1


def test_no_batch_at_all(session, rng):
    frames = _frames(rng, ("i",), (5,))
    src = _Source(frames, ())  # yields nothing
    got, families, grown, _ = _run(
        session, _plan("exchange", src, F.col("k") > 0, None))
    assert len(got) == 0 and families == [] and grown == (0, 0)


@pytest.mark.parametrize("kind", ["exchange", "broadcast"])
def test_claim_looks_through_one_coalesce_and_obeys_the_conf(kind, session,
                                                             rng):
    frames = _frames(rng, ("i", "d"), (40, 25))
    cond = F.col("k") > 0
    plan = _plan(kind, _Source(frames, ("chars",) * 2), cond, None,
                 coalesce=True)
    got, families, grown, _ = _run(session, plan)
    assert families == ["filter", "filter", "concat"] and grown[1] == 2
    # fuseFilter off: nothing is claimed, the filter runs as the operator
    # it is and the counter stands
    session.set_conf("spark.rapids.sql.exchange.fuseFilter", False)
    off, families, grown, _ = _run(
        session, _plan(kind, _Source(frames, ("chars",) * 2), cond, None,
                       coalesce=True))
    assert grown[1] == 0 and "filtermask" not in families
    _same_rows(got, off)


def test_compacted_batches_by_hand(session, rng):
    """``exchange.collapse.compactedBatches`` beside
    ``exchange.collapse.batches``: 5 of 5 for a two-column collapse, 0 of
    4 for one of five columns, 1 of 3 where two batches carry chars, and
    nothing where no filter is claimed."""
    cond = F.col("k") > 0

    def grown(columns, hows, filtered=True):
        frames = _frames(rng, columns, (12,) * len(hows))
        src = _Source(frames, hows)
        plan = (_plan("exchange", src, cond, None) if filtered
                else TpuShuffleExchangeExec(src, ("single",)))
        return _run(session, plan)[2]

    assert grown(("i",), ("chars",) * 5) == (5, 5)
    assert grown(("i", "d", "j", "s"), ("codes",) * 4) == (4, 0)
    assert grown(("s",), ("chars", "codes", "chars")) == (3, 1)
    assert grown(("i",), ("chars",) * 2, filtered=False) == (2, 0)


@pytest.mark.parametrize("kind", ["exchange", "broadcast"])
@pytest.mark.parametrize("hows", [("chars",) * 3, ("slab",) * 3],
                         ids=["compacting", "mask"])
def test_claimed_kernel_runs_before_the_next_batch_is_pulled(kind, hows,
                                                             session, rng,
                                                             monkeypatch):
    """Per batch, under the drain: batch i's program is dispatched before
    the child is asked for batch i + 1, in both forms."""
    log = []
    frames = _frames(rng, ("s",) if hows[0] == "slab" else ("i",),
                     (20, 20, 20))
    plan = _plan(kind, _Source(frames, hows, log), F.col("k") > 0, None)
    from spark_rapids_tpu.exec import tpu as tpuexec
    real = tpuexec._fused_filter_source

    def recording(node, ctx):
        src, claimed = real(node, ctx)

        def run(batch):
            log.append(("kernel", sum(1 for e in log if e[0] == "kernel")))
            return claimed(batch)
        return src, run
    monkeypatch.setattr(tpuexec, "_fused_filter_source", recording)
    _run(session, plan, with_session=False)
    assert log == [("pull", 0), ("kernel", 0), ("pull", 1), ("kernel", 1),
                   ("pull", 2), ("kernel", 2)]


# --------------------------------------------------------------------------
# filter_batch's sort branch with dictionary columns
# --------------------------------------------------------------------------

def _gather_form(batch, keep):
    from spark_rapids_tpu.ops.tablekernels import compact_permutation
    perm, n = compact_permutation(keep & batch.row_mask())
    return rowops.gather_batch(batch, perm, n)


@pytest.mark.parametrize("columns,hows", [
    (("s",), "codes"), (("i", "s"), "codes"), (("s", "d", "s2"), "codes"),
    (("s",), "full"),
], ids=["string", "int+string", "two_strings", "dictionary_beside_chars"])
def test_sort_branch_carries_dictionary_columns(columns, hows, rng):
    frame = _frames(rng, [c for c in columns if c != "s2"], (50,))[0]
    if "s2" in columns:
        frame["s2"] = frame["s"].str.upper()
    state = {}
    if hows == "codes":
        batch = _upload(frame, "codes", state)
    else:  # chars uploaded, a dictionary attached beside them
        batch = DeviceBatch.from_pandas(frame, dict_numerics=False)
        assert not batch.column("s").is_lazy
    assert batch.column("s").dict_values is not None
    assert rowops.sort_compactable(batch.columns)
    keep = np.zeros(batch.capacity, bool)
    keep[:50] = rng.random(50) < 0.6
    keep[50:] = True  # past the rows: filter_batch masks them itself
    got = rowops.filter_batch(batch, jnp.asarray(keep))
    want = _gather_form(batch, jnp.asarray(keep))
    assert int(got.num_rows) == int(want.num_rows) == keep[:50].sum()
    _same_rows(got.to_pandas(), want.to_pandas())
    _same_rows(got.to_pandas(), frame[keep[:50]].reset_index(drop=True))
    for g, w in zip(got.columns, want.columns):
        np.testing.assert_array_equal(np.asarray(g.validity),
                                      np.asarray(w.validity))
        if g.dtype.is_string:
            # codes-only against the same dictionary, NULL code in the
            # dead slots: leaf for leaf what the gather leaves
            assert g.is_lazy and g.dict_values == w.dict_values
            np.testing.assert_array_equal(np.asarray(g.dict_codes),
                                          np.asarray(w.dict_codes))


@pytest.mark.parametrize("columns,how,ok", [
    ((), "chars", True), (("i", "d", "j"), "chars", True),
    (("i", "d", "s"), "codes", True), (("i", "d", "j", "s"), "codes", False),
    (("s",), "chars", False), (("s",), "slab", False),
], ids=["one", "four", "four_with_codes", "five", "chars", "slab"])
def test_sort_compactable_reads_the_batch(columns, how, ok, rng):
    batch = _upload(_frames(rng, columns, (9,))[0], how, {})
    assert rowops.sort_compactable(batch.columns) is ok


def test_a_dictionary_on_a_fixed_width_column_keeps_the_gather(rng):
    frame = _frames(rng, (), (30,))[0]
    batch = DeviceBatch.from_pandas(frame)  # dict_numerics: k gets codes
    assert batch.column("k").dict_values is not None
    assert not rowops.sort_compactable(batch.columns)
    keep = jnp.asarray(rng.random(batch.capacity) < 0.5)
    _same_rows(rowops.filter_batch(batch, keep).to_pandas(),
               _gather_form(batch, keep).to_pandas())


@pytest.mark.parametrize("case", ["two_columns", "five_columns",
                                  "mixed_representations"])
def test_a_claimed_filter_compacts_each_piece(case, session, rng,
                                              monkeypatch):
    """Where the bound cuts the collapse (exec/tpu._collapse_bound_bytes),
    the claimed filter runs on every batch as before and each piece is its
    group's concat, compacted or masked: the pieces hold the uncut
    collapse's rows in its order, and the counters add up the same."""
    from spark_rapids_tpu.exec import tpu as tpuexec
    columns, hows, sel, compacted = _CASES[case]
    frames = _frames(rng, columns, (40, 0, 25, 30))
    hows = (hows,) * 4 if isinstance(hows, str) else hows + ("codes",)
    cond = (F.col("k") > -10) & (F.col("k") < 40)

    def run():
        plan = _plan("exchange", _Source(frames, hows), cond, sel)
        before = (REGISTRY.value(_BATCHES), REGISTRY.value(_COMPACTED),
                  REGISTRY.value("exchange.collapse.pieces"))
        TRACER.clear()
        TRACER.configure(True)
        try:
            out = [b for p in plan.partitions(ExecContext(session.conf,
                                                          session))
                   for b in p()]
        finally:
            TRACER.configure(False)
        spans = [e["args"] for e in TRACER.events()
                 if e["name"] == "exchange.collapse"]
        grown = (REGISTRY.value(_BATCHES) - before[0],
                 REGISTRY.value(_COMPACTED) - before[1],
                 REGISTRY.value("exchange.collapse.pieces") - before[2])
        return pd.concat([b.to_pandas() for b in out],
                         ignore_index=True), spans, grown

    whole, spans, grown = run()
    assert [s["pieces"] for s in spans] == [1] and grown[2] == 1
    # a bound of 64 slots: every batch of rows is a piece of its own (the
    # empty one rides with its neighbour)
    schema = _plan("exchange", _Source(frames, hows), cond,
                   sel).output_schema()
    monkeypatch.setattr(tpuexec, "_collapse_bound_bytes",
                        lambda: 64 * tpuexec._row_bytes(schema))
    cut, spans, cut_grown = run()
    assert [s["pieces"] for s in spans] == [1, 2, 3]
    assert sum(s["batches"] for s in spans) == 4
    assert cut_grown == (grown[0], grown[1], 3)
    _same_rows(cut, whole)
