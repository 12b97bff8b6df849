"""LIKE patterns of literal segments separated by ``%`` and the per-value
path of the pattern predicates: every pattern over every kind of string
column against ``re.fullmatch``; a predicate over a codes-only column
leaves it lazy and is counted as answered from the dictionary; ``_`` and
escapes still tag off, by name."""

import itertools
import re

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar import dtypes
from spark_rapids_tpu.columnar.batch import DeviceBatch
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.exprs.core import BoundRef, Literal
from spark_rapids_tpu.sql.exprs.evalbridge import make_context
from spark_rapids_tpu.sql.exprs.stringexprs import (
    ConcatStrings, Contains, EndsWith, Like, StartsWith, Substring,
    _classify_like, _like_to_regex, counting_pattern_predicates,
)
from spark_rapids_tpu.sql.sources import _arrow_decode, _attach_dict_hints

# every string over {a, b, c} of at most four bytes, the generator's order
# comments, the two words in the wrong order, and NULLs; six times over, so
# that a scan's dictionary takes the 126 distinct values
VALUES = (["".join(t) for n in range(5)
           for t in itertools.product("abc", repeat=n)]
          + ["special requests sleep", "requests are special",
             "special packages wake among the requests", "specialrequests",
             "quick ideas", None, None]) * 6

PATTERNS = [
    "%a%b%", "a%b", "a%b%c", "%a%b",
    "%ab%bc%",          # needles that overlap in "abc" must not both match
    "%b%a%",            # the same segments in the other order
    "%%", "%a%%b%",     # an empty segment is no segment
    "aa%aa",            # head and tail may not share bytes ("aaa")
    "%abcab%cabca%",    # needles longer than any value over {a, b, c}
    "abcabc%", "c%c%c%c",
    "%special%requests%", "%requests%special%", "special%sleep",
    "a%", "%c", "%bc%", "abc",      # the one-segment kinds
]


def _lazy_batch(values):
    """One codes-only string column, as a Parquet scan uploads it."""
    table = pa.table({"s": pa.array(values, pa.string())})
    df = _attach_dict_hints(_arrow_decode(table, True), table)
    batch = DeviceBatch.from_pandas(df, dict_numerics=False)
    assert batch.column("s").is_lazy
    return batch


def _plain_batch(values, **kw):
    return DeviceBatch.from_pandas(pd.DataFrame({"s": values}),
                                   dict_encode=False, **kw)


REF = BoundRef(0, dtypes.STRING, "s")
# kind -> (batch of VALUES, the predicate's child)
KINDS = {
    "dictionary": lambda: (_lazy_batch(VALUES), REF),
    "chars": lambda: (_plain_batch(VALUES), REF),
    "slab": lambda: (_plain_batch(VALUES, blocked_chars=64), REF),
    "concat": lambda: (_plain_batch(VALUES),
                       ConcatStrings([REF, Literal("")])),
    "substring-of-dictionary": lambda: (_lazy_batch(VALUES),
                                        Substring(REF, 1, 1000)),
}


def _evaluate(pred, batch):
    ctx = make_context(batch)
    out = pred.eval_device(ctx)
    n = batch.num_rows_host()
    return (np.asarray(out.data)[:n].astype(bool),
            np.asarray(out.validity)[:n].astype(bool))


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("pattern", PATTERNS)
def test_like_agrees_with_fullmatch(pattern, kind):
    batch, child = KINDS[kind]()
    like = Like(child, pattern)
    assert like.device_supported(batch.schema) is None
    data, validity = _evaluate(like, batch)
    regex = re.compile(_like_to_regex(pattern), re.DOTALL)
    want = [v is not None and regex.fullmatch(v) is not None
            for v in VALUES]
    assert validity.tolist() == [v is not None for v in VALUES]
    assert (data & validity).tolist() == want, [
        v for v, g, w in zip(VALUES, data & validity, want) if g != w]
    if kind == "dictionary":
        assert batch.column("s").is_lazy


def test_the_patterns_tell_an_ordered_matcher_from_two_contains():
    """What the benchmark's cell cannot see, these values do."""
    for pattern, value in [("%special%requests%", "requests are special"),
                           ("%ab%bc%", "abc"), ("aa%aa", "aaa")]:
        words = [w for w in pattern.split("%") if w]
        assert all(w in value for w in words)
        assert not re.fullmatch(_like_to_regex(pattern), value)
        assert value in VALUES


@pytest.mark.parametrize("pattern,kind", [
    ("abc", "exact"), ("a%", "prefix"), ("%a", "suffix"), ("%a%", "contains"),
    ("%%", "contains"), ("%a%%", "contains"), ("a%b", "segments"),
    ("%a%b%", "segments"), ("a%b%c", "segments"), ("%a%b", "segments")])
def test_classification(pattern, kind):
    assert _classify_like(pattern)[0] == kind


@pytest.mark.parametrize("pattern,names", [
    ("a_c", "_"), ("%a_", "_"), ("a\\%b", "escape"), ("100\\%", "escape")])
def test_underscore_and_escape_tag_off_by_name(session, pattern, names,
                                               capsys):
    reason = Like(REF, pattern).device_supported(None)
    assert reason is not None and names in reason
    assert "regex" not in reason
    df = session.create_dataframe(pd.DataFrame({"s": ["abc", "a%b"]}))
    query = df.filter(F.col("s").like(pattern))
    assert reason in query.explain()
    capsys.readouterr()
    session.set_conf("spark.rapids.sql.test.enabled", True)
    with pytest.raises(AssertionError, match="did not run on the TPU"):
        query.collect()


def _counter(name):
    return sum(v for (n, _), v in REGISTRY.values().items() if n == name)


PREDICATES = {
    "contains": lambda c: Contains(c, "requests"),
    "endswith": lambda c: EndsWith(c, "sleep"),
    "like": lambda c: Like(c, "%special%requests%"),
    "startswith": lambda c: StartsWith(c, "special package"),  # 15 bytes
}


@pytest.mark.parametrize("fn", sorted(PREDICATES))
def test_a_predicate_over_a_codes_only_column_leaves_it_lazy(fn):
    batch = _lazy_batch(VALUES)
    pred = PREDICATES[fn](REF)
    seen = []
    kernel = counting_pattern_predicates([pred])(
        lambda b: seen.append(_evaluate(pred, b)))
    rows = _counter("expr.dictPredicate.rows")
    batches = REGISTRY.value("expr.dictPredicate.batches", fn=fn)
    rebuilt = _counter("strings.charsRebuilt.bytes")
    kernel(batch)
    data, validity = seen[0]
    want = [v is not None and pred.host_match(v) for v in VALUES]
    assert (data & validity).tolist() == want and any(want)
    assert batch.column("s").is_lazy
    assert _counter("expr.dictPredicate.rows") - rows == len(VALUES)
    assert REGISTRY.value("expr.dictPredicate.batches", fn=fn) \
        - batches == 1
    # touched, with nothing: the metric reads 0 and is not left out
    assert _counter("strings.charsRebuilt.bytes") == rebuilt
    assert ("strings.charsRebuilt.bytes", ()) in REGISTRY.values()


def test_a_predicate_over_a_derived_string_counts_the_rebuilt_chars():
    batch = _lazy_batch(VALUES)
    pred = Contains(Substring(REF, 1, 1000), "requests")
    rows = _counter("expr.dictPredicate.rows")
    rebuilt = _counter("strings.charsRebuilt.bytes")
    kernel = counting_pattern_predicates([pred])(lambda b: None)
    capacity = batch.column("s").rebuilt_char_capacity()
    kernel(batch)
    assert capacity >= batch.capacity * len("special packages wake among "
                                            "the requests")
    assert _counter("strings.charsRebuilt.bytes") - rebuilt == capacity
    assert _counter("expr.dictPredicate.rows") == rows
    # a column with chars of its own rebuilds nothing
    counting_pattern_predicates([pred])(lambda b: None)(
        _plain_batch(VALUES))
    assert _counter("strings.charsRebuilt.bytes") - rebuilt == capacity
    # no pattern predicate, no wrapper
    plain = lambda b: None  # noqa: E731
    assert counting_pattern_predicates([REF])(plain) is plain


def test_a_filter_through_a_parquet_scan_is_answered_from_the_dictionary(
        session, tmp_path):
    """The counters grow every execution, not once a trace."""
    session.set_conf("spark.rapids.sql.test.enabled", True)
    n = 5000
    comments = ["", "special requests sleep", "above the ideas",
                "special packages wake among the requests",
                "requests are special", None]
    values = [comments[i % 6] for i in range(n)]
    frame = pd.DataFrame({"k": np.arange(n), "c": values})
    pq.write_table(pa.Table.from_pandas(frame),
                   str(tmp_path / "t.parquet"), row_group_size=1250)
    table = session.read.parquet(str(tmp_path / "t.parquet"))
    regex = re.compile(r"(?s).*special.*requests.*")
    want = [k for k, c in enumerate(values)
            if c is not None and not regex.fullmatch(c)]
    for _ in range(2):
        rows = _counter("expr.dictPredicate.rows")
        rebuilt = _counter("strings.charsRebuilt.bytes")
        lazy = REGISTRY.value("scan.upload.codesOnlyColumns")
        got = (table.filter(~F.col("c").like("%special%requests%"))
               .select("k").collect())
        assert sorted(got["k"]) == want
        assert REGISTRY.value("scan.upload.codesOnlyColumns") - lazy == 4
        assert _counter("expr.dictPredicate.rows") - rows == n
        assert _counter("strings.charsRebuilt.bytes") == rebuilt
