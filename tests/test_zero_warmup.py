"""Zero-warm-up serving: shape buckets, AOT pre-warm, shared cache.

The three layers of ISSUE 13 / ROADMAP item 3:

  * coarse secondary-dimension shape buckets at the kernel-cache
    dispatch boundary (``spark.rapids.tpu.compile.shapeBuckets``) — off
    is byte-identical, on is value-identical with padded capacities;
  * AOT pre-warm from history (``serving/prewarm.py``): replayable
    argument specs captured at compile time, replayed as zero-filled
    dummy calls in a (possibly fresh) process;
  * the cross-process shared persistent compile cache
    (``obs/compilecache.SharedCompileCache``): file-locked manifest,
    versioned keys, hit/miss/steal accounting.

Tier-1 acceptance: a FRESH process riding the shared cache + AOT
manifest runs tpch q6 with ZERO real XLA compiles (subprocess test at
the bottom).
"""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.config.conf import TpuConf
from spark_rapids_tpu.obs.compileledger import (
    LEDGER, analyze, kernel_key,
)
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.utils import argspec, kernelcache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_zero_warmup_state():
    """These layers are process-global; every test leaves them off."""
    from spark_rapids_tpu.obs.compilecache import SHARED
    from spark_rapids_tpu.serving import prewarm
    yield
    prewarm.cancel_active()
    kernelcache.set_build_hook(None)
    kernelcache.configure_shape_buckets(False)
    SHARED.reset_for_tests()


# ---------------------------------------------------------------------------
# Shape buckets
# ---------------------------------------------------------------------------

class TestBucketDim:
    def test_off_is_identity(self):
        kernelcache.configure_shape_buckets(False)
        for n in (1, 7, 777, 4096, 1 << 20):
            assert kernelcache.bucket_dim(n) == n

    def test_ladder_floor_and_growth(self):
        kernelcache.configure_shape_buckets(True, 4096, 2.0)
        assert kernelcache.bucket_dim(8) == 4096
        assert kernelcache.bucket_dim(4096) == 4096
        assert kernelcache.bucket_dim(4097) == 8192
        assert kernelcache.bucket_dim(5000) == 8192
        kernelcache.configure_shape_buckets(True, 1024, 4.0)
        assert kernelcache.bucket_dim(1500) == 4096
        assert kernelcache.bucket_dim(5000) == 16384

    def test_conf_wiring_default_off(self):
        assert kernelcache.configure_shape_buckets_from_conf(
            TpuConf()) is False
        assert kernelcache.bucket_dim(13) == 13
        conf = TpuConf({"spark.rapids.tpu.compile.shapeBuckets": True})
        assert kernelcache.configure_shape_buckets_from_conf(conf)
        assert kernelcache.bucket_dim(13) == 4096

    def test_concat_device_byte_identical_when_off(self, session):
        """Pinned: with shapeBuckets off, the coarse flag changes
        NOTHING — single batches pass through by identity."""
        from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
        from spark_rapids_tpu.columnar import dtype as dtypes
        from spark_rapids_tpu.exec.tpu import _concat_device
        kernelcache.configure_shape_buckets(False)
        b = DeviceBatch.from_pandas(pd.DataFrame({"a": [1, 2, 3]}))
        out = _concat_device([b], b.schema, 2.0, coarse=True)
        assert out is b


class TestShapeBucketOracles:
    """Padded vs unpadded results are identical (masks included) across
    the join / fused count-distinct / fused-stage paths."""

    def _frames(self):
        left = pd.DataFrame({
            "k": pd.array([1, 2, 3, 4, 2, None, 7, 3] * 9,
                          dtype="Int64"),
            "v": [float(i) for i in range(72)],
            "s": (["aa", "b", None, "dddd"] * 18),
        })
        right = pd.DataFrame({
            "k": pd.array([2, 3, 9, None], dtype="Int64"),
            "w": [10.0, None, 30.0, 40.0],
        })
        return left, right

    def _run_join(self, session):
        left, right = self._frames()
        l = session.create_dataframe(left, 3)
        r = session.create_dataframe(right, 1)
        # numeric aggregates only: a string min/max here would compile
        # the char-reduction kernels three times over (~10s of pure
        # compile; the count-distinct oracle below keeps string-column
        # coverage through its dictionary path)
        out = (l.join(r, on="k", how="left")
               .filter(F.col("v") >= 1.0)
               .group_by("k")
               .agg(F.count("*").alias("n"), F.sum("w").alias("sw"))
               .collect())
        return out.sort_values("k", na_position="last") \
            .reset_index(drop=True)

    def _run_count_distinct(self, session):
        left, _ = self._frames()
        df = session.create_dataframe(left, 2)
        out = df.group_by("s").agg(
            F.count_distinct("k").alias("cd")).collect()
        return out.sort_values("s", na_position="last") \
            .reset_index(drop=True)

    def _with_buckets(self, session, fn):
        base = fn(session)
        session.set_conf("spark.rapids.tpu.compile.shapeBuckets", True)
        try:
            on = fn(session)
        finally:
            session.set_conf("spark.rapids.tpu.compile.shapeBuckets",
                             False)
        off_again = fn(session)
        pd.testing.assert_frame_equal(base, on)
        pd.testing.assert_frame_equal(base, off_again)
        return base

    def test_join_agg_padded_results_identical(self, session):
        out = self._with_buckets(session, self._run_join)
        # NULL masks preserved: the Int64 key column keeps its NA row
        assert out["k"].isna().sum() == 1

    def test_fused_count_distinct_padded_identical(self, session):
        out = self._with_buckets(session, self._run_count_distinct)
        assert out["s"].isna().sum() == 1  # null group survives

    def test_fused_stage_padded_identical(self, session):
        def run(s):
            left, _ = self._frames()
            df = s.create_dataframe(left, 3)
            return (df.with_column("v2", F.col("v") * 2.0)
                    .filter(F.col("v2") > 10.0)
                    .with_column("v3", F.col("v2") + 1.0)
                    .collect().reset_index(drop=True))
        session.set_conf("spark.rapids.sql.fusion.stageEnabled", True)
        try:
            self._with_buckets(session, run)
        finally:
            session.set_conf("spark.rapids.sql.fusion.stageEnabled",
                             False)


# ---------------------------------------------------------------------------
# Analyzer noise fix (already-bucketed dims)
# ---------------------------------------------------------------------------

def _entry(kernel="k", avals=(), seconds=1.0):
    return {"op": "Op(x)", "kernel": kernel, "avals": list(avals),
            "seconds": seconds, "query": "q-1", "outcome": None}


class TestAnalyzerStableDims:
    def test_power_of_two_dims_recommend_nothing(self):
        # the row-capacity dim: already exact bucket values — padding
        # to the "recommended" power-of-two buckets changes nothing
        rep = analyze([_entry(avals=[f"int32[{n}]"], seconds=2.0)
                       for n in (1024, 2048, 4096)])
        g = rep["groups"][0]
        v = g["varying"][0]
        assert v["stable"] is True and v["buckets"] == []
        assert g["already_bucketed"] is True
        assert g["projected_savings_s"] == 0.0
        assert rep["projected_savings_s"] == 0.0

    def test_unstable_dims_still_recommend(self):
        rep = analyze([_entry(avals=[f"int32[{n}]"])
                       for n in (1000, 1100, 1200)])
        g = rep["groups"][0]
        assert g["varying"][0]["buckets"] == [1024, 2048]
        assert g["already_bucketed"] is False
        assert g["projected_savings_s"] > 0

    def test_mixed_stable_and_actionable_dim(self):
        # arg0 already bucketed, arg1 not: savings project from the
        # actionable dim only
        rep = analyze([
            _entry(avals=["int32[1024]", "=1000"]),
            _entry(avals=["int32[2048]", "=3000"]),
        ])
        g = rep["groups"][0]
        by_arg = {v["arg"]: v for v in g["varying"]}
        assert by_arg[0]["stable"] and not by_arg[0]["buckets"]
        assert by_arg[1]["buckets"] == [1024, 4096]
        assert g["already_bucketed"] is False
        assert g["projected_savings_s"] == 0.0  # 2 compiles, 2 buckets

    def test_stable_static_scalars_filtered(self):
        rep = analyze([_entry(avals=["=1024"]),
                       _entry(avals=["=4096"])])
        v = rep["groups"][0]["varying"][0]
        assert v["stable"] is True and v["buckets"] == []


# ---------------------------------------------------------------------------
# Argspec capture / rebuild
# ---------------------------------------------------------------------------

class TestArgspec:
    def _batch(self):
        from spark_rapids_tpu.columnar.batch import DeviceBatch
        df = pd.DataFrame({
            "i": pd.array([1, None, 3], dtype="Int64"),
            "f": [1.5, 2.5, 3.5],
            "s": ["aa", None, "cc"],
        })
        return DeviceBatch.from_pandas(df)

    def test_roundtrip_preserves_treedef_and_avals(self):
        import jax

        from spark_rapids_tpu.obs.compileledger import aval_signature
        b = self._batch()
        args = (b, np.asarray([1, 2], np.int64), 7, (16, "x"), None)
        spec = argspec.capture(args, {})
        assert spec is not None
        ra, rkw = argspec.build(spec)
        assert rkw == {}
        assert aval_signature(ra, rkw) == aval_signature(args, {})
        # identical treedef = identical jit trace identity
        assert jax.tree_util.tree_structure((ra,)) \
            == jax.tree_util.tree_structure((args,))
        # static scalars and tuples reproduce EXACTLY
        assert ra[2] == 7 and ra[3] == (16, "x") and ra[4] is None
        # rebuilt rows are all-padding: zero num_rows, all-false masks
        assert int(np.asarray(ra[0].num_rows)) == 0
        assert not np.asarray(ra[0].columns[0].validity).any()

    def test_dictionary_columns_roundtrip(self):
        from spark_rapids_tpu.columnar.batch import DeviceBatch
        df = pd.DataFrame({"d": ["x", "y", "x", "y", "x", "y"] * 4})
        b = DeviceBatch.from_pandas(df, dict_encode=True)
        col = b.columns[0]
        if col.dict_values is None:
            pytest.skip("dictionary probe declined this column")
        spec = argspec.capture((b,), {})
        assert spec is not None
        (rb,), _ = argspec.build(spec)
        assert rb.columns[0].dict_values == col.dict_values

    def test_oversized_dictionary_not_replayable(self):
        from spark_rapids_tpu.columnar import dtype as dtypes
        from spark_rapids_tpu.columnar.column import DeviceColumn
        col = DeviceColumn(
            dtypes.STRING, None, np.zeros(8, np.bool_),
            dict_codes=np.zeros(8, np.int32),
            dict_values=tuple("v" * 100 for _ in range(200)))
        assert argspec.capture((col,), {}) is None

    def test_host_object_not_replayable(self):
        assert argspec.capture((object(),), {}) is None

    def test_ledger_entries_carry_argspec(self, session):
        import jax
        kernelcache.clear()
        jax.clear_caches()
        seq0 = LEDGER.seq
        session.create_dataframe(
            pd.DataFrame({"a": list(range(32))}), 1).filter(
            F.col("a") > 3).collect()
        entries = LEDGER.entries(since_seq=seq0)
        assert entries
        specs = [e for e in entries if e.get("argspec")]
        assert specs, "compile entries must carry replayable argspecs"
        # and the full-signature key that survives kernel truncation
        assert all(e.get("kernelKey") for e in entries)


# ---------------------------------------------------------------------------
# AOT manifest + pre-warmer
# ---------------------------------------------------------------------------

def _load_tool(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"srt_{name}", os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestAotManifest:
    def test_manifest_dedups_and_counts_replayable(self):
        cr = _load_tool("compile_report")
        ents = [
            {"kernel": "k1", "kernelKey": "a1", "avals": ["int32[8]"],
             "argspec": {"args": [], "kwargs": {}}, "seconds": 1.0},
            {"kernel": "k1", "kernelKey": "a1", "avals": ["int32[8]"],
             "argspec": {"args": [], "kwargs": {}}, "seconds": 2.0},
            {"kernel": "k1", "kernelKey": "a1", "avals": ["int32[16]"],
             "argspec": None, "seconds": 1.0},
            {"kernel": None, "seconds": 9.0},
        ]
        man = cr.build_aot_manifest(ents)
        assert len(man["entries"]) == 2
        assert man["replayable"] == 1
        dup = next(e for e in man["entries"]
                   if e["avals"] == ["int32[8]"])
        assert dup["count"] == 2 and dup["seconds"] == 3.0

    def test_emitter_cli(self, tmp_path, session):
        import jax
        kernelcache.clear()
        jax.clear_caches()
        ev = tmp_path / "ev.jsonl"
        session.set_conf("spark.rapids.tpu.eventLog.path", str(ev))
        try:
            session.create_dataframe(
                pd.DataFrame({"a": [1.0, 2.0, 3.0]}), 1).group_by() \
                .agg(F.sum("a").alias("s")).collect()
        finally:
            session.set_conf("spark.rapids.tpu.eventLog.path", "")
            from spark_rapids_tpu.obs.events import EVENTS
            EVENTS.configure(False, "")
        cr = _load_tool("compile_report")
        out = tmp_path / "aot.json"
        rc = cr.main([str(ev), "--aot-manifest", str(out)])
        assert rc == 0 and out.exists()
        man = json.load(open(out))
        assert man["version"] == 1 and man["replayable"] >= 1


class TestPrewarmer:
    def _manifest(self, tmp_path, entries):
        p = tmp_path / "aot.json"
        p.write_text(json.dumps({"version": 1, "entries": entries}))
        return str(p)

    def _fake_entry(self, sig, shape=(8,), argspec_=...):
        if argspec_ is ...:
            argspec_ = {"args": [{"t": "arr", "dtype": "float64",
                                  "shape": list(shape)}], "kwargs": {}}
        return {"kernel": sig[:200], "kernelKey": kernel_key(sig),
                "avals": [f"float64[{shape[0]}]"],
                "argspec": argspec_, "seconds": 0.5}

    def test_replays_on_kernel_build(self, tmp_path):
        from spark_rapids_tpu.serving.prewarm import AotPrewarmer
        sig = "zwtest|replay|" + "x" * 300  # longer than the 200 cut
        calls = []
        p = AotPrewarmer(self._manifest(tmp_path, [
            self._fake_entry(sig),
            self._fake_entry(sig, shape=(16,)),
        ]), budget_s=30.0).start()
        try:
            kernelcache.cached_jit(
                sig, lambda: lambda x: calls.append(x.shape) or x)
            assert p.wait_idle(10.0)
            snap = p.snapshot()
            assert snap["warmed"] == 2 and snap["failed"] == 0
            assert sorted(calls) == [(8,), (16,)]
        finally:
            p.cancel()
            kernelcache.clear()

    def test_skipped_and_pending_accounting(self, tmp_path):
        from spark_rapids_tpu.serving.prewarm import AotPrewarmer
        p = AotPrewarmer(self._manifest(tmp_path, [
            self._fake_entry("zwtest|never-built"),
            self._fake_entry("zwtest|no-spec", argspec_=None),
        ]), budget_s=30.0).start()
        try:
            assert p.wait_idle(5.0)
            snap = p.snapshot()
            assert snap["skipped"] == 1
            assert snap["pending"] == 1  # kernel never came into being
            assert snap["warmed"] == 0
        finally:
            p.cancel()

    def test_budget_cap_stops_the_pass(self, tmp_path):
        from spark_rapids_tpu.serving.prewarm import AotPrewarmer
        sig = "zwtest|budget"
        p = AotPrewarmer(self._manifest(tmp_path, [
            self._fake_entry(sig, shape=(8,)),
            self._fake_entry(sig, shape=(16,)),
            self._fake_entry(sig, shape=(32,)),
        ]), budget_s=1e-9).start()
        try:
            kernelcache.cached_jit(sig, lambda: lambda x: x)
            assert p.wait_idle(10.0)
            snap = p.snapshot()
            assert snap["budgetExhausted"] is True
            assert snap["warmed"] == 1  # first replay spends the budget
            assert snap["pending"] == 2
        finally:
            p.cancel()
            kernelcache.clear()

    def test_maybe_start_from_conf_idempotent_and_cancellable(
            self, tmp_path):
        from spark_rapids_tpu.serving import prewarm
        man = self._manifest(tmp_path,
                             [self._fake_entry("zwtest|conf")])
        conf = TpuConf({"spark.rapids.tpu.compile.aot.manifest": man})
        p1 = prewarm.maybe_start_from_conf(conf)
        p2 = prewarm.maybe_start_from_conf(conf)
        assert p1 is p2 is prewarm.active()
        prewarm.cancel_active()
        assert prewarm.active() is None
        assert prewarm.maybe_start_from_conf(TpuConf()) is None


# ---------------------------------------------------------------------------
# Shared compile cache
# ---------------------------------------------------------------------------

class TestSharedCompileCache:
    def test_manifest_append_and_steal_census(self, tmp_path):
        from spark_rapids_tpu.obs.compilecache import SHARED
        from spark_rapids_tpu.obs.metrics import REGISTRY
        assert SHARED.configure(str(tmp_path / "cc"))
        SHARED.note_compile({"kernelKey": "kk1", "kernel": "k1",
                             "op": "Op", "avals": ["int32[8]"],
                             "seconds": 0.5, "ts": 1.0})
        ents = SHARED.manifest_entries()
        assert len(ents) == 1
        rec = next(iter(ents.values()))
        assert rec["pid"] == os.getpid()
        # a record from ANOTHER process: reuse counts as a steal
        class _D:  # minimal dispatch twin
            kernel = "kk2-full-sig"
            args = ()
            kwargs = {}
        foreign_key = SHARED.key_for(kernel_key(_D.kernel), [])
        with open(tmp_path / "cc" / "manifest.jsonl", "a") as f:
            f.write(json.dumps(dict(rec, key=foreign_key, pid=1,
                                    host="elsewhere")) + "\n")
        s0 = REGISTRY.counter("sharedCache.steals").value
        SHARED.note_cache_event("hit", _D)
        assert REGISTRY.counter("sharedCache.steals").value == s0 + 1
        st = SHARED.stats()
        assert st["enabled"] and st["knownKernels"] >= 2

    def test_hit_outcomes_do_not_rewrite_manifest(self, tmp_path):
        from spark_rapids_tpu.obs.compilecache import SHARED
        SHARED.configure(str(tmp_path / "cc"))
        SHARED.note_compile({"kernelKey": "kk", "kernel": "k",
                             "avals": [], "seconds": 0.1, "ts": 1.0,
                             "outcome": "hit"})
        assert SHARED.manifest_entries() == {}

    def test_torn_manifest_lines_are_skipped(self, tmp_path):
        from spark_rapids_tpu.obs.compilecache import SHARED
        d = tmp_path / "cc"
        SHARED.configure(str(d))
        with open(d / "manifest.jsonl", "w") as f:
            f.write('{"key": "good", "pid": 1}\n{"key": "torn', )
        assert list(SHARED.manifest_entries()) == ["good"]

    def test_two_process_contention(self, tmp_path):
        """Two concurrent PROCESSES hammer the manifest: every line
        must land whole (file-locked appends), none lost."""
        d = str(tmp_path / "cc")
        prog = (
            "import os, sys\n"
            "sys.path.insert(0, %r)\n"
            "from spark_rapids_tpu.obs.compilecache import SHARED\n"
            "SHARED.configure(sys.argv[1])\n"
            "tag = sys.argv[2]\n"
            "for i in range(40):\n"
            "    SHARED.note_compile({'kernelKey': f'{tag}-{i}',\n"
            "        'kernel': f'{tag}-{i}', 'op': 'Op',\n"
            "        'avals': ['int32[8]'], 'seconds': 0.01,\n"
            "        'ts': 1.0})\n"
            "print('done', tag)\n" % _REPO)
        procs = [subprocess.Popen(
            [sys.executable, "-c", prog, d, f"w{i}"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for i in range(2)]
        for p in procs:
            out, err = p.communicate(timeout=120)
            assert p.returncode == 0, err[-800:]
        lines = open(os.path.join(d, "manifest.jsonl")).read() \
            .strip().splitlines()
        assert len(lines) == 80
        recs = [json.loads(ln) for ln in lines]  # every line parses
        assert len({r["key"] for r in recs}) == 80
        assert {r["kernel"].split("-")[0] for r in recs} \
            == {"w0", "w1"}

    def test_n_process_append_hammer_zero_torn_records(self, tmp_path):
        """Four processes hammer ``locked_append`` with records far
        beyond any atomic-write size (up to ~64KB): the flock +
        looped-write contract means EVERY record lands whole — exact
        count, every line parses, every writer's full sequence present
        — the fleet's shared warm manifest depends on it."""
        from spark_rapids_tpu.obs.compilecache import locked_append
        path = str(tmp_path / "hammer.jsonl")
        n_procs, n_recs = 4, 150
        prog = (
            "import json, sys\n"
            "sys.path.insert(0, %r)\n"
            "from spark_rapids_tpu.obs.compilecache import "
            "locked_append\n"
            "path, tag = sys.argv[1], sys.argv[2]\n"
            "for i in range(%d):\n"
            "    doc = {'writer': tag, 'seq': i,\n"
            "           'fill': 'x' * ((i %% 16) * 4096)}\n"
            "    assert locked_append(\n"
            "        path, (json.dumps(doc) + '\\n').encode())\n"
            "print('done', tag)\n" % (_REPO, n_recs))
        procs = [subprocess.Popen(
            [sys.executable, "-c", prog, path, f"w{i}"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
            for i in range(n_procs)]
        for p in procs:
            out, err = p.communicate(timeout=120)
            assert p.returncode == 0, err[-800:]
        lines = open(path).read().splitlines()
        assert len(lines) == n_procs * n_recs
        recs = [json.loads(ln) for ln in lines]  # zero torn records
        by_writer = {}
        for r in recs:
            by_writer.setdefault(r["writer"], []).append(r["seq"])
            assert r["fill"] == "x" * ((r["seq"] % 16) * 4096)
        assert set(by_writer) == {f"w{i}" for i in range(n_procs)}
        for seqs in by_writer.values():
            assert sorted(seqs) == list(range(n_recs))
        # and the in-process writer interleaves with them safely too
        assert locked_append(path, b'{"writer": "main", "seq": 0}\n')


# ---------------------------------------------------------------------------
# Monitor surfacing
# ---------------------------------------------------------------------------

class TestStatusSurfacing:
    def test_status_snapshot_reports_aot_and_shared_cache(
            self, tmp_path, session):
        from spark_rapids_tpu.obs import monitor
        from spark_rapids_tpu.obs.compilecache import SHARED
        from spark_rapids_tpu.serving import prewarm
        SHARED.configure(str(tmp_path / "cc"))
        man = tmp_path / "aot.json"
        man.write_text(json.dumps({"version": 1, "entries": []}))
        prewarm.maybe_start_from_conf(TpuConf(
            {"spark.rapids.tpu.compile.aot.manifest": str(man)}))
        snap = monitor.status_snapshot()
        assert "aot" in snap and "sharedCompileCache" in snap
        assert snap["sharedCompileCache"]["enabled"] is True
        for k in ("warmed", "pending", "skipped", "seconds"):
            assert k in snap["aot"]


# ---------------------------------------------------------------------------
# Tier-1 acceptance: fresh process compiles NOTHING on a second sweep
# ---------------------------------------------------------------------------

_FRESH_PROG = r"""
import json, os, sys
sys.path.insert(0, sys.argv[4])
shared, manifest, evlog = sys.argv[1], sys.argv[2], sys.argv[3]
from spark_rapids_tpu.session import TpuSparkSession
b = TpuSparkSession.builder().config(
    "spark.rapids.tpu.compile.sharedCache.dir", shared)
if manifest:
    b = b.config("spark.rapids.tpu.compile.aot.manifest", manifest)
if evlog:
    b = b.config("spark.rapids.tpu.eventLog.path", evlog)
s = b.get_or_create()
from spark_rapids_tpu.models import tpch_data
from spark_rapids_tpu.models.tpch import QUERIES
li = tpch_data.gen_lineitem(0.002)

def run():
    tables = {"lineitem": s.create_dataframe(li, 3)}
    return QUERIES["q6"](s, tables).collect()

out1 = run()
if manifest:
    from spark_rapids_tpu.serving import prewarm
    p = prewarm.active()
    p.wait_idle(30)
out2 = run()
from spark_rapids_tpu.obs.compileledger import LEDGER
real = [e for e in LEDGER.entries() if e.get("outcome") != "hit"]
from spark_rapids_tpu.obs.metrics import REGISTRY
print(json.dumps({
    "real_compiles": len(real),
    "real_kernels": [(e.get("op"), (e.get("kernel") or "")[:60])
                     for e in real][:10],
    "persistent_hits":
        REGISTRY.counter("compileCache.persistentHits").value,
    "steals": REGISTRY.counter("sharedCache.steals").value,
    "rows": len(out1) + len(out2),
}))
"""


def _run_fresh(args, xla_cache):
    # the executables ride jax's own cache, placed by the environment
    # (XLA:CPU keeps it off otherwise); the shared dir holds the manifest
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=xla_cache)
    r = subprocess.run([sys.executable, "-c", _FRESH_PROG] + args,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_second_sweep_in_fresh_process_compiles_nothing(tmp_path):
    """The acceptance criterion: sweep #1 (process 1) populates the
    shared cache + the event log; its ledger distills into an AOT
    manifest; sweep #2 runs in a FRESH process and pays ZERO real XLA
    compiles — every backend-compile event is a persistent-cache load,
    and the pre-warmer replays history before/alongside the query."""
    shared = str(tmp_path / "cache")
    xla_cache = str(tmp_path / "xla")
    evlog = str(tmp_path / "ev.jsonl")
    manifest = str(tmp_path / "aot.json")

    first = _run_fresh([shared, "", evlog, _REPO], xla_cache)
    assert first["real_compiles"] > 0  # cold cluster genuinely compiles
    # executables went where the environment said and nowhere else
    assert len(os.listdir(xla_cache)) >= first["real_compiles"]
    assert os.listdir(shared) == ["manifest.jsonl"]

    cr = _load_tool("compile_report")
    entries = cr._load_entries(evlog)
    man = cr.build_aot_manifest(entries)
    assert man["replayable"] >= 1
    json.dump(man, open(manifest, "w"))

    second = _run_fresh([shared, manifest, "", _REPO], xla_cache)
    assert second["real_compiles"] == 0, (
        "fresh process recompiled despite shared cache + AOT replay: "
        f"{second['real_kernels']}")
    assert second["persistent_hits"] >= first["real_compiles"]
    assert second["steals"] > 0  # reuse of ANOTHER process's compiles
