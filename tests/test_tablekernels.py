"""Kernel-level tests of ops/tablekernels (compaction, the hash-aggregate
table) and of the probes and grouping every join and aggregate of a default
plan runs (ops/joins.join_probe, join_probe_dense, ops/groupby.group_rows),
each against a numpy or plain python dict/set oracle."""

import numpy as np
import pytest

from spark_rapids_tpu.ops import tablekernels as tk


@pytest.mark.parametrize("n", [1, 7, 128, 2048, 2049, 5000])
def test_dual_prefix_counts_match_numpy(n, rng):
    keep = rng.random(n) < 0.4
    import jax.numpy as jnp
    kex, dex, tot = tk.dual_prefix_counts(jnp.asarray(keep))
    k = keep.astype(np.int64)
    np.testing.assert_array_equal(np.asarray(kex), np.cumsum(k) - k)
    np.testing.assert_array_equal(np.asarray(dex),
                                  np.cumsum(1 - k) - (1 - k))
    assert int(tot) == int(k.sum())


def _check_compaction(keep):
    import jax.numpy as jnp
    perm, total = tk.compact_permutation(jnp.asarray(keep))
    expect = np.concatenate([np.flatnonzero(keep), np.flatnonzero(~keep)])
    np.testing.assert_array_equal(np.asarray(perm), expect)
    assert int(total) == int(keep.sum())


@pytest.mark.parametrize("n", [64, 2048, 2050, 4096])
def test_compact_permutation_matches_numpy(n, rng):
    """A stable partition: kept rows first in order, then the rest."""
    _check_compaction(rng.random(n) < 0.55)
    _check_compaction(np.ones(n, bool))
    _check_compaction(np.zeros(n, bool))


def test_compact_permutation_stable(rng):
    _check_compaction(rng.random(300) < 0.3)


# ---------------------------------------------------------------------------
# The probes behind every join: the union-sort probe (join_probe) and the
# dense direct-index probe (join_probe_dense), against plain python dict
# semantics. Batches carry dead padding rows whose keys WOULD match: a
# row past num_rows never joins.
# ---------------------------------------------------------------------------

def _join_oracle(bk, bv, sk, sv):
    from collections import defaultdict
    groups = defaultdict(list)
    for i, (k, v) in enumerate(zip(bk, bv)):
        if v:
            groups[k].append(i)
    counts = np.asarray([len(groups[k]) if v else 0
                         for k, v in zip(sk, sv)])
    return groups, counts


def _key_batch(keys, valid):
    """One DeviceBatch of key columns, padded to a power-of-two capacity
    with dead rows that repeat the live keys."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import DeviceBatch, DeviceColumn, Schema
    from spark_rapids_tpu.columnar import dtypes as dt
    n = len(valid)
    cap = 16
    while cap < n + 3:
        cap <<= 1
    pad = np.arange(cap) % max(n, 1)
    cols = []
    for k in keys:
        coldt = dt.FLOAT64 if k.dtype.kind == "f" else dt.INT64
        data = k[pad] if n else np.zeros(cap, k.dtype)
        cols.append(DeviceColumn(
            coldt, jnp.asarray(data.astype(coldt.np_dtype)),
            jnp.asarray(np.concatenate([valid, np.ones(cap - n, bool)]))))
    return DeviceBatch(Schema([f"k{i}" for i in range(len(cols))],
                              [c.dtype for c in cols]),
                       cols, jnp.asarray(n, jnp.int32))


def _check_probe(got, nb, ns, groups, ocounts, skeys):
    counts, bstart, bperm = (np.asarray(x) for x in got)
    np.testing.assert_array_equal(counts[:ns], ocounts)
    assert not counts[ns:].any()  # dead stream rows match nothing
    assert sorted(bperm.tolist()) == list(range(len(bperm)))  # permutation
    for i in range(ns):
        if counts[i]:
            got_rows = sorted(
                bperm[bstart[i]:bstart[i] + counts[i]].tolist())
            assert got_rows == sorted(groups[skeys[i]]), i
            assert max(got_rows) < nb


def _check_join(bk, bv, sk, sv):
    from spark_rapids_tpu.ops.joins import join_probe
    groups, ocounts = _join_oracle(bk, bv, sk, sv)
    got = join_probe(_key_batch([bk], bv), _key_batch([sk], sv), [0], [0])
    _check_probe(got, len(bk), len(sk), groups, ocounts, sk)


def _check_join_dense(bk, bv, sk, sv, compact):
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.joins import join_probe_dense
    groups, ocounts = _join_oracle(bk, bv, sk, sv)
    live = bk[bv] if bv.any() else np.zeros(1, np.int64)
    lo, table_size = int(live.min()), 1024
    assert int(live.max()) - lo < table_size
    *got, ok = join_probe_dense(
        _key_batch([bk], bv), _key_batch([sk], sv), 0, 0,
        jnp.asarray(lo, jnp.int64), table_size, compact)
    assert bool(ok)
    _check_probe(got, len(bk), len(sk), groups, ocounts, sk)


def _random_keys(rng):
    nb, ns = 257, 400
    bk = rng.integers(-10, 50, nb).astype(np.int64)
    bv = rng.random(nb) < 0.85
    sk = rng.integers(-30, 70, ns).astype(np.int64)  # some keys absent
    sv = rng.random(ns) < 0.9
    return bk, bv, sk, sv


def _skewed_keys():
    # every build row the same key: one giant group, contiguous in bperm
    nb = 64
    return (np.full(nb, 7, np.int64), np.ones(nb, bool),
            np.asarray([7, 8, 7], np.int64), np.ones(3, bool))


def _null_and_empty_keys(rng):
    # SQL: null keys never match — an all-invalid build yields zero
    # counts, and so does an all-invalid stream
    nb, ns = 32, 16
    bk = rng.integers(0, 4, nb).astype(np.int64)
    sk = rng.integers(0, 4, ns).astype(np.int64)
    return [(bk, np.zeros(nb, bool), sk, np.ones(ns, bool)),
            (bk, np.ones(nb, bool), sk, np.zeros(ns, bool))]


@pytest.mark.parametrize("seed", [42, 7])
def test_join_probe_matches_oracle(seed):
    _check_join(*_random_keys(np.random.default_rng(seed)))


def test_join_probe_skewed_single_key():
    _check_join(*_skewed_keys())


def test_join_probe_all_null_and_empty(rng):
    for case in _null_and_empty_keys(rng):
        _check_join(*case)


def test_join_probe_multi_key(rng):
    from collections import defaultdict

    from spark_rapids_tpu.ops.joins import join_probe
    nb, ns = 120, 200
    b1 = rng.integers(0, 6, nb).astype(np.int64)
    b2 = rng.integers(0, 6, nb).astype(np.int64)
    bv = rng.random(nb) < 0.9
    s1 = rng.integers(0, 7, ns).astype(np.int64)
    s2 = rng.integers(0, 7, ns).astype(np.int64)
    sv = rng.random(ns) < 0.9
    groups = defaultdict(list)
    for i in range(nb):
        if bv[i]:
            groups[(b1[i], b2[i])].append(i)
    skeys = list(zip(s1, s2))
    ocounts = np.asarray([
        len(groups[skeys[i]]) if sv[i] else 0 for i in range(ns)])
    got = join_probe(_key_batch([b1, b2], bv), _key_batch([s1, s2], sv),
                     [0, 1], [0, 1])
    _check_probe(got, nb, ns, groups, ocounts, skeys)


@pytest.mark.parametrize("np_dtype", [np.int64, np.float64])
def test_join_probe_typed_key_images(np_dtype, rng):
    """Real column dtypes through the exact u64 key image: negative ints
    and floats (incl. -0.0 == 0.0) keep exact equality semantics."""
    nb, ns = 100, 150
    vals = rng.integers(-20, 20, nb).astype(np_dtype)
    svals = rng.integers(-30, 30, ns).astype(np_dtype)
    if np_dtype is np.float64:
        vals[0], svals[0] = -0.0, 0.0
    _check_join(vals, rng.random(nb) < 0.9, svals, rng.random(ns) < 0.9)


def _dense_case(case, rng):
    """(build keys, build validity, stream keys, stream validity)."""
    if case == "random":          # some stream keys outside the table
        return _random_keys(rng)
    if case == "duplicate_build_keys":   # an FK-side build: one big run
        return _skewed_keys()
    if case in ("all_null_build", "all_null_stream"):
        return _null_and_empty_keys(rng)[case == "all_null_stream"]
    if case == "empty_build":
        _bk, _bv, sk, sv = _random_keys(rng)
        return np.zeros(0, np.int64), np.zeros(0, bool), sk, sv
    # int64 keys beyond int32: the table is indexed by key - lo
    bk, bv, sk, sv = _random_keys(rng)
    return bk + (1 << 40), bv, sk + (1 << 40), sv


@pytest.mark.parametrize("compact", [False, True],
                         ids=["packed", "compact"])
@pytest.mark.parametrize("case", [
    "random", "duplicate_build_keys", "all_null_build", "all_null_stream",
    "empty_build", "int64_far_from_zero", "build_key_outside_the_table"])
def test_join_probe_dense_matches_oracle(case, compact, rng):
    """Both forms of the dense table (the packed [start, count] rows and
    the compact prefix sum) give the sort probe's contract; a valid build
    key outside the table reports ``ok`` False."""
    if case != "build_key_outside_the_table":
        _check_join_dense(*_dense_case(case, rng), compact)
        return
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.joins import join_probe_dense
    bk, bv, sk, sv = _random_keys(rng)
    bk[0], bv[0] = 5000, True
    ok = join_probe_dense(_key_batch([bk], bv), _key_batch([sk], sv), 0, 0,
                          jnp.asarray(-10, jnp.int64), 1024, compact)[3]
    assert not bool(ok)


# ---------------------------------------------------------------------------
# The grouping behind every sort-based aggregate (ops/groupby.group_rows):
# the group of each row, the number of groups and a representative row.
# ---------------------------------------------------------------------------

def _group_rows(keys, valid):
    """(gid of each original row or -1, number of groups, rep row of each
    group) from GroupInfo."""
    from spark_rapids_tpu.ops.groupby import group_rows
    n = len(keys)
    batch = _key_batch([keys], np.ones(n, bool))
    live = np.concatenate([valid, np.zeros(batch.capacity - n, bool)])
    import jax.numpy as jnp
    info = group_rows(batch, [0], live=jnp.asarray(live))
    perm = np.asarray(info.perm)
    gid = np.full(batch.capacity, -1)
    gid[perm] = np.where(live[perm], np.asarray(info.group_id_sorted), -1)
    return gid[:n], int(info.num_groups), np.asarray(info.rep_rows)


def test_group_rows_matches_oracle(rng):
    n = 300
    keys = rng.integers(0, 40, n).astype(np.int64)
    valid = rng.random(n) < 0.85
    gid, ng, rep = _group_rows(keys, valid)
    assert ng == len(set(keys[valid]))
    seen = {}
    for i in range(n):
        if not valid[i]:
            assert gid[i] == -1
        else:
            assert gid[i] == seen.setdefault(keys[i], gid[i])
    assert sorted(seen.values()) == list(range(ng))
    for k, g in seen.items():
        first = min(i for i in range(n) if valid[i] and keys[i] == k)
        assert rep[g] == first  # rep row = first occurrence


def test_group_rows_matches_oracle_negative_keys(rng):
    n = 200
    keys = rng.integers(-25, 25, n).astype(np.int64)
    valid = rng.random(n) < 0.6
    gid, ng, _rep = _group_rows(keys, valid)
    assert ng == len(set(keys[valid]))
    for k in set(keys[valid]):
        assert len(set(gid[valid & (keys == k)])) == 1


def test_group_rows_skew_and_empty():
    # single group (maximum skew)
    keys = np.full(128, 3, np.int64)
    gid, ng, rep = _group_rows(keys, np.ones(128, bool))
    assert ng == 1 and set(gid.tolist()) == {0} and int(rep[0]) == 0
    # nothing live at all
    gid, ng, _rep = _group_rows(keys, np.zeros(128, bool))
    assert ng == 0 and set(gid.tolist()) == {-1}


# ---------------------------------------------------------------------------
# Grouped aggregation over the slot table (docs/hashagg.md):
# counts/rep/accumulators against a plain python dict oracle.
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


def _check_grouped_agg(keys, valid, jobs):
    import jax.numpy as jnp
    T = tk.hash_table_size(len(keys))
    counts, rep, accs, nels = tk.hash_grouped_aggregate(
        [jnp.asarray(keys)], jnp.asarray(valid),
        [(k, jnp.asarray(d), jnp.asarray(e)) for k, d, e in jobs],
        T)
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


def test_hash_grouped_aggregate_matches_oracle(rng):
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
    _check_grouped_agg(keys, valid, jobs)


def test_hash_grouped_aggregate_skew_and_all_invalid(rng):
    # maximum skew: every live row the same key -> one slot holds all
    n = 128
    keys = np.full(n, 9, np.uint64)
    jobs = [("sum", np.arange(n, dtype=np.int64), np.ones(n, bool)),
            ("max", np.arange(n, dtype=np.int64), np.ones(n, bool))]
    _check_grouped_agg(keys, np.ones(n, bool), jobs)
    # nothing live: no used slots at all
    _check_grouped_agg(keys, np.zeros(n, bool), jobs)


def test_hash_grouped_aggregate_multi_image_keys(rng):
    import jax.numpy as jnp
    n = 300
    k1 = rng.integers(0, 6, n).astype(np.uint64)
    k2 = rng.integers(0, 6, n).astype(np.uint64)
    valid = rng.random(n) < 0.85
    data = rng.integers(0, 100, n).astype(np.int64)
    T = tk.hash_table_size(n)
    counts, rep, accs, _nels = tk.hash_grouped_aggregate(
        [jnp.asarray(k1), jnp.asarray(k2)], jnp.asarray(valid),
        [("sum", jnp.asarray(data), jnp.asarray(np.ones(n, bool)))],
        T)
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


# ---------------------------------------------------------------------------
# Page-decode expanders against numpy: the reassembly of PLAIN words and the
# char slab's packing (tests/test_parquet_decode.py covers them end to end).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,np_dtype", [
    ("i32", np.int32), ("i64", np.int64), ("f32", np.float32),
    ("f64", np.float64), ("bool", np.bool_)])
def test_plain_fixed_matches_numpy(kind, np_dtype, rng):
    import jax.numpy as jnp
    n = 77
    if kind == "bool":
        vals = rng.random(n) < 0.4
        raw = np.packbits(vals, bitorder="little").tobytes()
    else:
        vals = (rng.normal(0, 1e6, n) if np_dtype in (np.float32, np.float64)
                else rng.integers(-2**31, 2**31, n)).astype(np_dtype)
        raw = vals.tobytes()
    raw += b"\0" * (-len(raw) % 8 + 8)  # the upload pads to whole words
    words = jnp.asarray(np.frombuffer(raw, np.uint32))
    got = np.asarray(tk.plain_fixed(words, kind, n))
    assert got.dtype == np_dtype
    np.testing.assert_array_equal(got, vals)


def test_slab_pack_matches_np_build_slab(rng):
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.column import np_build_slab
    cap, stride = 16, 24
    lens = rng.integers(0, stride + 1, cap)
    lens[3] = 0
    offsets = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
    chars = rng.integers(1, 256, int(offsets[-1])).astype(np.uint8)
    want, _ = np_build_slab(chars, offsets, cap, stride)
    padded = np.concatenate([chars, np.zeros(stride, np.uint8)])
    got = tk.slab_pack(jnp.asarray(padded), jnp.asarray(offsets[:-1]),
                       jnp.asarray(lens.astype(np.int32)), cap, stride)
    np.testing.assert_array_equal(np.asarray(got), want)
