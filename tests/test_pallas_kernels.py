"""Pallas kernel tests: the compaction prefix-count kernel in interpreter
mode against the jnp twin and a numpy oracle (the kernel itself runs
un-interpreted only on real TPUs)."""

import numpy as np
import pytest

from spark_rapids_tpu.ops import pallas_kernels as pk


@pytest.mark.parametrize("n", [1, 7, 128, 2048, 2049, 5000])
def test_dual_prefix_jnp_matches_numpy(n, rng):
    keep = rng.random(n) < 0.4
    import jax.numpy as jnp
    kex, dex, tot = pk._dual_prefix_jnp(jnp.asarray(keep, jnp.int32))
    k = keep.astype(np.int64)
    np.testing.assert_array_equal(np.asarray(kex), np.cumsum(k) - k)
    np.testing.assert_array_equal(np.asarray(dex),
                                  np.cumsum(1 - k) - (1 - k))
    assert int(tot) == int(k.sum())


@pytest.mark.parametrize("n", [64, 2048, 2050, 4096])
def test_pallas_kernel_interpret_matches_jnp(n, rng):
    import jax.numpy as jnp
    keep = jnp.asarray(rng.random(n) < 0.55, jnp.int32)
    kex_p, dex_p, tot_p = pk._dual_prefix_pallas(keep, True)
    kex_j, dex_j, tot_j = pk._dual_prefix_jnp(keep)
    np.testing.assert_array_equal(np.asarray(kex_p), np.asarray(kex_j))
    np.testing.assert_array_equal(np.asarray(dex_p), np.asarray(dex_j))
    assert int(tot_p) == int(tot_j)


def test_compact_permutation_stable(rng):
    import jax.numpy as jnp
    keep = jnp.asarray(rng.random(300) < 0.3)
    perm, total = pk.compact_permutation(keep)
    k = np.asarray(keep)
    expect = np.concatenate([np.nonzero(k)[0], np.nonzero(~k)[0]])
    np.testing.assert_array_equal(np.asarray(perm), expect)
    assert int(total) == int(k.sum())


def test_mode_env_toggle(monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS", "0")
    assert pk._mode() == "jnp"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS", "interpret")
    assert pk._mode() == "interpret"
    # auto stays on the XLA path (Mosaic is opt-in for attached chips)
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS", "auto")
    assert pk._mode() == "jnp"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS", "1")
    import jax
    expect = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert pk._mode() == expect


# ---------------------------------------------------------------------------
# Open-addressing hash-table kernels: build/probe (join) + grouped-agg.
# Interpret mode runs the REAL sequential-insert kernel; the jnp twin is
# the vectorized round-claiming algorithm — both are oracle-checked
# against plain python dict/set semantics.
# ---------------------------------------------------------------------------

MODES = ["jnp", "interpret"]


def _join_oracle(bk, bv, sk, sv):
    from collections import defaultdict
    groups = defaultdict(list)
    for i, (k, v) in enumerate(zip(bk, bv)):
        if v:
            groups[k].append(i)
    counts = np.asarray([len(groups[k]) if v else 0
                         for k, v in zip(sk, sv)])
    return groups, counts


def _check_join(bk, bv, sk, sv, mode):
    import jax.numpy as jnp
    groups, ocounts = _join_oracle(bk, bv, sk, sv)
    T = pk.hash_table_size(len(bk))
    counts, bstart, bperm = pk.hash_join_probe(
        [jnp.asarray(bk)], jnp.asarray(bv),
        [jnp.asarray(sk)], jnp.asarray(sv), T, mode=mode)
    counts = np.asarray(counts)
    bstart = np.asarray(bstart)
    bperm = np.asarray(bperm)
    np.testing.assert_array_equal(counts, ocounts)
    assert sorted(bperm.tolist()) == list(range(len(bk)))  # permutation
    for i in range(len(sk)):
        if counts[i]:
            got = sorted(bperm[bstart[i]:bstart[i] + counts[i]].tolist())
            assert got == sorted(groups[sk[i]]), i


@pytest.mark.parametrize("mode", MODES)
def test_hash_join_probe_matches_oracle(mode, rng):
    nb, ns = 257, 400
    bk = rng.integers(0, 60, nb).astype(np.uint64)
    bv = rng.random(nb) < 0.85
    sk = rng.integers(0, 80, ns).astype(np.uint64)  # some keys absent
    sv = rng.random(ns) < 0.9
    _check_join(bk, bv, sk, sv, mode)


@pytest.mark.parametrize("mode", ["interpret"])
def test_hash_join_probe_skewed_single_key(mode, rng):
    # every build row the same key: one giant group, contiguous in bperm
    nb = 64
    bk = np.full(nb, 7, np.uint64)
    bv = np.ones(nb, bool)
    sk = np.asarray([7, 8, 7], np.uint64)
    sv = np.ones(3, bool)
    _check_join(bk, bv, sk, sv, mode)


@pytest.mark.parametrize("mode", ["interpret"])
def test_hash_join_probe_all_null_and_empty(mode, rng):
    # SQL: null keys never match — all-invalid build yields zero counts
    nb, ns = 32, 16
    bk = rng.integers(0, 4, nb).astype(np.uint64)
    bv = np.zeros(nb, bool)
    sk = rng.integers(0, 4, ns).astype(np.uint64)
    sv = np.ones(ns, bool)
    _check_join(bk, bv, sk, sv, mode)
    # and an all-invalid stream
    _check_join(bk, np.ones(nb, bool), sk, np.zeros(ns, bool), mode)


@pytest.mark.parametrize("mode", ["interpret"])
def test_hash_join_probe_multi_key(mode, rng):
    import jax.numpy as jnp
    nb, ns = 120, 200
    b1 = rng.integers(0, 6, nb).astype(np.uint64)
    b2 = rng.integers(0, 6, nb).astype(np.uint64)
    bv = rng.random(nb) < 0.9
    s1 = rng.integers(0, 7, ns).astype(np.uint64)
    s2 = rng.integers(0, 7, ns).astype(np.uint64)
    sv = rng.random(ns) < 0.9
    from collections import defaultdict
    groups = defaultdict(list)
    for i in range(nb):
        if bv[i]:
            groups[(b1[i], b2[i])].append(i)
    ocounts = np.asarray([
        len(groups[(s1[i], s2[i])]) if sv[i] else 0 for i in range(ns)])
    T = pk.hash_table_size(nb)
    counts, bstart, bperm = pk.hash_join_probe(
        [jnp.asarray(b1), jnp.asarray(b2)], jnp.asarray(bv),
        [jnp.asarray(s1), jnp.asarray(s2)], jnp.asarray(sv), T,
        mode=mode)
    counts = np.asarray(counts)
    np.testing.assert_array_equal(counts, ocounts)
    bstart = np.asarray(bstart)
    bperm = np.asarray(bperm)
    for i in range(ns):
        if counts[i]:
            got = sorted(bperm[bstart[i]:bstart[i] + counts[i]].tolist())
            assert got == sorted(groups[(s1[i], s2[i])]), i


@pytest.mark.parametrize("mode", ["interpret"])
@pytest.mark.parametrize("np_dtype", [np.int64, np.float64])
def test_hash_join_probe_typed_key_images(mode, np_dtype, rng):
    """Real column dtypes through the exact u64 key image (the images
    the exec wiring feeds the kernels): negative ints and floats
    (incl. -0.0 == 0.0) keep exact equality semantics."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.ops.sortops import u64_key_image
    nb, ns = 100, 150
    if np_dtype is np.float64:
        vals = rng.integers(-20, 20, nb).astype(np.float64)
        vals[0] = -0.0
        svals = rng.integers(-20, 20, ns).astype(np.float64)
        svals[0] = 0.0
        coldt = dt.FLOAT64
    else:
        vals = rng.integers(-20, 20, nb).astype(np_dtype)
        svals = rng.integers(-30, 30, ns).astype(np_dtype)
        coldt = dt.INT64 if np_dtype is np.int64 else dt.INT32
    bv = rng.random(nb) < 0.9
    sv = rng.random(ns) < 0.9
    bcol = DeviceColumn(coldt, jnp.asarray(vals), jnp.asarray(bv))
    scol = DeviceColumn(coldt, jnp.asarray(svals), jnp.asarray(sv))
    T = pk.hash_table_size(nb)
    counts, _bs, _bp = pk.hash_join_probe(
        u64_key_image(bcol), jnp.asarray(bv),
        u64_key_image(scol), jnp.asarray(sv), T, mode=mode)
    groups, ocounts = _join_oracle(vals, bv, svals, sv)
    np.testing.assert_array_equal(np.asarray(counts), ocounts)


@pytest.mark.parametrize("mode", MODES)
def test_hash_group_ids_matches_oracle(mode, rng):
    import jax.numpy as jnp
    n = 300
    keys = rng.integers(0, 40, n).astype(np.uint64)
    valid = rng.random(n) < 0.85
    gid, ng, rep = pk.hash_group_ids(
        [jnp.asarray(keys)], jnp.asarray(valid),
        pk.hash_table_size(n), mode=mode)
    gid = np.asarray(gid)
    rep = np.asarray(rep)
    uniq = sorted(set(keys[valid]))
    assert int(ng) == len(uniq)
    seen = {}
    for i in range(n):
        if not valid[i]:
            assert gid[i] == -1
            continue
        if keys[i] in seen:
            assert gid[i] == seen[keys[i]]
        else:
            seen[keys[i]] = gid[i]
    assert sorted(seen.values()) == list(range(int(ng)))
    for k, g in seen.items():
        first = min(i for i in range(n) if valid[i] and keys[i] == k)
        assert rep[g] == first  # rep row = first occurrence


@pytest.mark.parametrize("mode", ["interpret"])
def test_hash_group_ids_skew_and_empty(mode, rng):
    import jax.numpy as jnp
    # single group (maximum skew)
    keys = np.full(128, 3, np.uint64)
    gid, ng, rep = pk.hash_group_ids(
        [jnp.asarray(keys)], jnp.ones((128,), bool),
        pk.hash_table_size(128), mode=mode)
    assert int(ng) == 1 and set(np.asarray(gid).tolist()) == {0}
    assert int(np.asarray(rep)[0]) == 0
    # nothing valid at all
    gid, ng, _rep = pk.hash_group_ids(
        [jnp.asarray(keys)], jnp.zeros((128,), bool),
        pk.hash_table_size(128), mode=mode)
    assert int(ng) == 0 and set(np.asarray(gid).tolist()) == {-1}


def test_hash_kernels_mode_env(monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS", "interpret")
    assert pk.hash_kernels_mode() == "interpret"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS", "auto")
    assert pk.hash_kernels_mode() == "off"
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS", "0")
    assert pk.hash_kernels_mode() == "off"


def test_requested_kernel_that_does_not_compile_raises(monkeypatch):
    """SPARK_RAPIDS_TPU_PALLAS=1 means the compiled kernels or an error,
    never the twin in their place: a family the backend's compiler
    refuses raises at first use, with the compiler's message. (XLA:CPU
    refuses every non-interpret pallas_call, which stands in for Mosaic
    refusing one.)"""
    monkeypatch.setattr(pk, "_mode", lambda: "pallas")
    monkeypatch.setattr(pk, "_probe_verdicts", {})
    keep = np.arange(64) % 3 == 0
    for use in (lambda: pk.dual_prefix_counts(keep), pk.hash_kernels_mode):
        for _ in range(2):  # the verdict is cached, the refusal is not
            with pytest.raises(pk.PallasKernelRefused,
                               match="interpret mode"):
                use()
    assert set(pk._probe_verdicts) == {"compaction", "hash_table"}


def test_hash_kernels_exec_wiring_interpret(monkeypatch, session, rng):
    """End-to-end coverage of the exec GLUE, not just the kernel
    primitives: under SPARK_RAPIDS_TPU_PALLAS=interpret a real join
    (key-image assembly, _key_valid masking, the counts/bstart/bperm
    handoff into join_expand) and a fused count-distinct (aggfuse's
    image + validity-bit null handling) must match the CPU oracle. The
    mode is read per partitions() call, so the env flip needs no
    reimport."""
    import pandas as pd
    from spark_rapids_tpu.sql import functions as F
    monkeypatch.setenv("SPARK_RAPIDS_TPU_PALLAS", "interpret")
    n = 400
    left = pd.DataFrame({"k": rng.integers(0, 12, n).astype(np.int64),
                         "v": rng.uniform(0, 1, n)})
    left.loc[rng.random(n) < 0.1, "k"] = None
    left["k"] = left["k"].astype("Int64")
    right = pd.DataFrame({"k": rng.integers(0, 15, 60).astype(np.int64),
                          "w": rng.integers(0, 5, 60)})

    def both(q, sort_cols):
        session.set_conf("spark.rapids.sql.enabled", True)
        tpu = q.collect().sort_values(sort_cols).reset_index(drop=True)
        session.set_conf("spark.rapids.sql.enabled", False)
        cpu = q.collect().sort_values(sort_cols).reset_index(drop=True)
        session.set_conf("spark.rapids.sql.enabled", True)
        pd.testing.assert_frame_equal(tpu, cpu, check_dtype=False)
        return tpu

    l = session.create_dataframe(left, 2)
    r = session.create_dataframe(right, 1)
    out = both(l.join(r, on="k", how="inner"), ["k", "v", "w"])
    assert len(out) > 0
    both(l.join(r, on="k", how="leftanti"), ["k", "v"])
    dd = session.create_dataframe(pd.DataFrame({
        "g": rng.integers(0, 3, n).astype(np.int64),
        "d": rng.integers(0, 25, n).astype(np.int64)}), 2)
    out = both(dd.group_by("g").agg(F.count_distinct("d").alias("cd")),
               ["g"])
    assert (out["cd"] > 0).all()


# ---------------------------------------------------------------------------
# One-pass grouped aggregation over the slot table (docs/hashagg.md):
# counts/rep/accumulators against a plain python dict oracle. Interpret
# mode runs the REAL accumulate-in-kernel body.
# ---------------------------------------------------------------------------

def _agg_oracle(keys, valid, jobs):
    """Slot-free oracle: per distinct live key — first row, row count,
    and per-job (n_eligible, sum/min/max over eligible rows)."""
    groups = {}
    for i, (k, v) in enumerate(zip(keys, valid)):
        if not v:
            continue
        g = groups.setdefault(k, {"rep": i, "count": 0,
                                  "jobs": [[0, None] for _ in jobs]})
        g["count"] += 1
        for j, (kind, data, elig) in enumerate(jobs):
            if not elig[i]:
                continue
            slot = g["jobs"][j]
            slot[0] += 1
            x = data[i]
            slot[1] = x if slot[1] is None else (
                slot[1] + x if kind == "sum"
                else min(slot[1], x) if kind == "min" else max(slot[1], x))
    return groups


def _check_grouped_agg(keys, valid, jobs, mode):
    import jax.numpy as jnp
    T = pk.hash_table_size(len(keys))
    counts, rep, accs, nels = pk.hash_grouped_aggregate(
        [jnp.asarray(keys)], jnp.asarray(valid),
        [(k, jnp.asarray(d), jnp.asarray(e)) for k, d, e in jobs],
        T, mode=mode)
    counts, rep = np.asarray(counts), np.asarray(rep)
    accs = [np.asarray(a) for a in accs]
    nels = [np.asarray(x) for x in nels]
    oracle = _agg_oracle(keys, valid, jobs)
    used = np.nonzero(counts > 0)[0]
    assert len(used) == len(oracle)
    seen = set()
    for s in used:
        k = keys[rep[s]]
        assert k not in seen  # one slot per distinct key
        seen.add(k)
        g = oracle[k]
        assert rep[s] == g["rep"]  # first-arrival row
        assert counts[s] == g["count"]
        for j, (kind, data, _elig) in enumerate(jobs):
            nel, expect = g["jobs"][j]
            assert nels[j][s] == nel
            if nel:  # acc undefined where n_eligible == 0
                if np.issubdtype(data.dtype, np.floating):
                    np.testing.assert_allclose(accs[j][s], expect,
                                               rtol=1e-12)
                else:
                    assert accs[j][s] == expect, (kind, s)


@pytest.mark.parametrize("mode", MODES)
def test_hash_grouped_aggregate_matches_oracle(mode, rng):
    n = 500
    keys = rng.integers(0, 40, n).astype(np.uint64)
    valid = rng.random(n) < 0.9
    jobs = [
        ("sum", rng.integers(-50, 50, n).astype(np.int64),
         rng.random(n) < 0.8),
        ("sum", rng.random(n), np.ones(n, bool)),
        ("min", rng.integers(-1000, 1000, n).astype(np.int32),
         rng.random(n) < 0.7),
        ("max", rng.random(n) * 100 - 50, rng.random(n) < 0.9),
        # count_valid spelling: sum of the eligibility indicator
        ("sum", np.ones(n, np.int64), rng.random(n) < 0.5),
    ]
    _check_grouped_agg(keys, valid, jobs, mode)


@pytest.mark.parametrize("mode", ["interpret"])
def test_hash_grouped_aggregate_skew_and_all_invalid(mode, rng):
    # maximum skew: every live row the same key -> one slot holds all
    n = 128
    keys = np.full(n, 9, np.uint64)
    jobs = [("sum", np.arange(n, dtype=np.int64), np.ones(n, bool)),
            ("max", np.arange(n, dtype=np.int64), np.ones(n, bool))]
    _check_grouped_agg(keys, np.ones(n, bool), jobs, mode)
    # nothing live: no used slots at all
    _check_grouped_agg(keys, np.zeros(n, bool), jobs, mode)


@pytest.mark.parametrize("mode", MODES)
def test_hash_grouped_aggregate_multi_image_keys(mode, rng):
    import jax.numpy as jnp
    n = 300
    k1 = rng.integers(0, 6, n).astype(np.uint64)
    k2 = rng.integers(0, 6, n).astype(np.uint64)
    valid = rng.random(n) < 0.85
    data = rng.integers(0, 100, n).astype(np.int64)
    T = pk.hash_table_size(n)
    counts, rep, accs, _nels = pk.hash_grouped_aggregate(
        [jnp.asarray(k1), jnp.asarray(k2)], jnp.asarray(valid),
        [("sum", jnp.asarray(data), jnp.asarray(np.ones(n, bool)))],
        T, mode=mode)
    counts, rep = np.asarray(counts), np.asarray(rep)
    acc = np.asarray(accs[0])
    from collections import defaultdict
    osum = defaultdict(int)
    for i in range(n):
        if valid[i]:
            osum[(k1[i], k2[i])] += data[i]
    used = np.nonzero(counts > 0)[0]
    got = {(k1[rep[s]], k2[rep[s]]): acc[s] for s in used}
    assert got == dict(osum)


def test_hash_grouped_aggregate_large_falls_back_to_jnp(rng, monkeypatch):
    # above _PALLAS_MAX_TABLE the pallas spelling must quietly take the
    # jnp twin (VMEM bound) — same results either way
    import jax.numpy as jnp
    n = 64
    keys = rng.integers(0, 8, n).astype(np.uint64)
    jobs = [("sum", jnp.asarray(np.ones(n, np.int64)),
             jnp.ones((n,), jnp.bool_))]
    big_T = pk._PALLAS_MAX_TABLE * 2
    counts, _rep, accs, _ = pk.hash_grouped_aggregate(
        [jnp.asarray(keys)], jnp.ones((n,), jnp.bool_), jobs, big_T,
        mode="pallas")
    assert int(jnp.sum(jnp.asarray(counts) > 0)) == 8
    assert int(jnp.sum(accs[0])) == n
