"""Distributed execution over a device mesh: the ICI shuffle path.

This is the TPU-native replacement for the reference's UCX peer-to-peer
shuffle (shuffle-plugin/.../ucx/, SURVEY.md section 2.4): instead of
tag-matched RDMA endpoint pairs, partitions live as shards of a
``jax.sharding.Mesh`` and the shuffle exchange is a single
``jax.lax.all_to_all`` collective riding ICI — one fused SPMD program for
(partial aggregate -> hash partition -> exchange -> merge) per stage, with
XLA overlapping compute and communication.

Validated on a virtual 8-device CPU mesh in tests
(tests/test_distributed.py, tests/test_mesh_exec.py).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes
from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.ops import rowops, sortops
from spark_rapids_tpu.ops.aggregate import aggregate_merge, aggregate_update
from spark_rapids_tpu.ops.groupby import row_hashes

#: static stats of recent mesh exchanges, for tests asserting the
#: funnel-free property (no device array ever holds the whole dataset):
#: [{"input_shard_caps": [...], "common_cap": int}, ...]. Bounded so a
#: long-lived session doesn't accumulate entries forever.
exchange_stats_log: list = []
_EXCHANGE_STATS_CAP = 64


def _shard_on(arr, dev):
    """The addressable block of a global array resident on ``dev``."""
    for s in arr.addressable_shards:
        if s.device == dev:
            return s.data
    raise AssertionError(f"no addressable shard on {dev}")


def pick_bounds_from_samples(samples, k: int, n: int):
    """n-1 lexicographic upper bounds from per-partition operand samples
    (the shared core of both the device-side and mesh range exchanges;
    GpuRangePartitioner.scala:42-120). ``samples``: list of (k, m)
    uint64 operand matrices."""
    if samples:
        all_s = np.concatenate(samples, axis=1)
        order = np.lexsort(all_s[::-1])
        all_s = all_s[:, order]
        total = all_s.shape[1]
        picks = [max(int((i + 1) * total / n) - 1, 0) for i in range(n - 1)]
        return [all_s[j, picks].astype(np.uint64) for j in range(k)]
    return [np.zeros((n - 1,), np.uint64) for _ in range(k)]


def data_parallel_mesh(n_devices: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n_devices]), ("dp",))


def _hash_pid(batch: DeviceBatch, key_idx: Sequence[int], n: int):
    h1, _ = row_hashes(batch, key_idx)
    return (h1 % jnp.uint64(n)).astype(jnp.int32)


def _send_buffers(batch: DeviceBatch, pid: jnp.ndarray, n: int):
    """Partition a batch's rows into n destination buckets of fixed
    capacity (the all-to-all analogue of Table.contiguousSplit,
    GpuPartitioning.scala:41-75) given a per-row destination ``pid``.
    Returns per-column send buffers plus (n,) counts. Fixed-width columns
    ride as ("fixed", (n,cap) data, (n,cap) validity); string columns as
    ("string", (n,cap) lens, (n,cap) validity, (n,char_cap) char slab,
    (n,) char counts) — rows sorted by destination make each
    destination's chars contiguous, so the slab is one masked gather."""
    cap = batch.capacity
    pid = jnp.where(batch.row_mask(), pid, n)
    perm = jnp.argsort(pid, stable=True).astype(jnp.int32)
    sorted_batch = rowops.gather_batch(batch, perm, batch.num_rows)
    counts = jnp.zeros((n + 1,), jnp.int32).at[pid].add(1)[:n]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts).astype(jnp.int32)])
    # dest d's rows live at sorted positions [offsets[d], offsets[d]+counts[d])
    j = jnp.arange(cap, dtype=jnp.int32)
    idx = offsets[:n, None] + j[None, :]              # (n, cap)
    live = j[None, :] < counts[:, None]
    idx = jnp.clip(idx, 0, cap - 1)
    buffers = []
    for col in sorted_batch.columns:
        if col.dtype.is_string:
            lens = (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)
            row_lens = jnp.where(live, lens[idx], 0)
            char_start = col.offsets[offsets[:n]].astype(jnp.int32)
            char_cnt = (col.offsets[offsets[1:]].astype(jnp.int32)
                        - char_start)
            ccap = col.data.shape[0]
            k = jnp.arange(ccap, dtype=jnp.int32)
            cidx = jnp.clip(char_start[:, None] + k[None, :], 0, ccap - 1)
            slab = jnp.where(k[None, :] < char_cnt[:, None],
                             col.data[cidx], 0).astype(jnp.uint8)
            buffers.append(("string", row_lens, col.validity[idx] & live,
                            slab, char_cnt))
        else:
            buffers.append(("fixed", col.data[idx],
                            col.validity[idx] & live))
    return buffers, counts


def _a2a_exchange(buffers, counts):
    """all_to_all every send buffer over the dp axis. Returns (received
    buffers, received counts) in the same per-column tagged layout."""
    a2a = functools.partial(jax.lax.all_to_all, axis_name="dp",
                            split_axis=0, concat_axis=0, tiled=False)
    received = []
    for buf in buffers:
        if buf[0] == "string":
            _, row_lens, validity, slab, char_cnt = buf
            received.append((
                "string", a2a(row_lens), a2a(validity), a2a(slab),
                jax.lax.all_to_all(char_cnt, "dp", split_axis=0,
                                   concat_axis=0, tiled=True)))
        else:
            received.append(("fixed", a2a(buf[1]), a2a(buf[2])))
    rcounts = jax.lax.all_to_all(counts, "dp", split_axis=0,
                                 concat_axis=0, tiled=True)
    return received, rcounts


def _compact_received(dtypes_, received, rcounts, n):
    """Flatten per-source (n, cap) received buffers into one compacted
    local batch. Stable liveness sorts keep source-major order, so row
    buffers and char slabs stay aligned after their separate compactions."""
    from spark_rapids_tpu.ops.tablekernels import compact_permutation
    shard_cap = received[0][1].shape[1]
    rcap = n * shard_cap
    live = (jnp.arange(shard_cap, dtype=jnp.int32)[None, :]
            < rcounts[:, None]).reshape(rcap)
    perm, _ = compact_permutation(live)
    total = rcounts.sum().astype(jnp.int32)
    cols = []
    for dt, buf in zip(dtypes_, received):
        if buf[0] == "string":
            _, rlens, rvalid, rslab, rcc = buf
            lens_flat = rlens.reshape(rcap)[perm]
            new_offsets = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 jnp.cumsum(lens_flat).astype(jnp.int32)])
            ccap = rslab.shape[1]
            ck = jnp.arange(n * ccap, dtype=jnp.int32)
            clive = (ck % ccap) < rcc[ck // ccap]
            cperm, _ = compact_permutation(clive)
            chars = rslab.reshape(n * ccap)[cperm]
            v = (rvalid.reshape(rcap) & live)[perm]
            cols.append(DeviceColumn(dt, chars, v, new_offsets))
        else:
            d = buf[1].reshape(rcap)[perm]
            v = (buf[2].reshape(rcap) & live)[perm]
            cols.append(DeviceColumn(dt, d, v))
    return cols, total


def mesh_collect_shards(mesh: Mesh, schema: Schema,
                        per_shard_lists: Sequence[Sequence[DeviceBatch]],
                        growth: float = 1.0) -> List[DeviceBatch]:
    """Place shard i's batches on mesh device i and concatenate them THERE
    (jit follows committed inputs) — the funnel-free collection step: no
    device ever receives another shard's rows. Upstream stages that
    already placed their output on the shard device (scans do, exchange
    outputs do) make the device_put a no-op."""
    from spark_rapids_tpu.exec.tpu import _concat_device
    devs = list(mesh.devices.flat)
    out: List[DeviceBatch] = []
    for i, batches in enumerate(per_shard_lists):
        placed = [jax.device_put(b, devs[i]) for b in batches]
        if not placed:
            out.append(jax.device_put(DeviceBatch.empty(schema), devs[i]))
        elif len(placed) == 1:
            out.append(placed[0])
        else:
            out.append(_concat_device(placed, schema, growth))
    return out


def _make_local(schema: Schema, n: int, pid_fn):
    """The shard_map body shared by every mesh exchange kind: rebuild the
    local batch from its flat buffers, partition rows by ``pid_fn``,
    all_to_all, compact. The LAST output is this shard's (n,) send-row
    counts — the device-side MapStatus.partition_sizes the ICI backend
    folds into MapOutputStatistics (shuffle/manager.py)."""
    def local(*args):
        it = iter(args[:-1])
        rows = args[-1][0]
        cols = []
        for dt in schema.dtypes:
            if dt.is_string:
                lens, validity, slab = next(it), next(it), next(it)
                lens, validity, slab = lens[0], validity[0], slab[0]
                offsets = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int32),
                     jnp.cumsum(lens).astype(jnp.int32)])
                cols.append(DeviceColumn(dt, slab, validity, offsets))
            else:
                data, validity = next(it)[0], next(it)[0]
                cols.append(DeviceColumn(dt, data, validity))
        local_batch = DeviceBatch(Schema(schema.names, schema.dtypes),
                                  cols, rows)
        buffers, counts = _send_buffers(local_batch, pid_fn(local_batch), n)
        received, rcounts = _a2a_exchange(buffers, counts)
        out_cols, total = _compact_received(schema.dtypes, received,
                                            rcounts, n)
        out = [total[None]]
        for c in out_cols:
            out.append(c.data[None])
            out.append(c.validity[None])
            if c.dtype.is_string:
                out.append(c.offsets[None])
        out.append(counts[None])
        return tuple(out)
    return local


def mesh_exchange_parts(mesh: Mesh, schema: Schema,
                        shard_batches: Sequence[DeviceBatch],
                        pid_fn, stats_out: Optional[dict] = None
                        ) -> List[DeviceBatch]:
    """Distributed exchange over already-sharded inputs: shard i's batch
    lives on mesh device i (mesh_collect_shards), the global (n, cap)
    operand arrays are assembled from the per-device blocks with
    ``jax.make_array_from_single_device_arrays`` — no device ever holds
    the whole dataset (VERDICT r2 item 4) — and ONE fused shard_map
    program partitions rows by ``pid_fn`` and exchanges them with an ICI
    ``all_to_all``. The TPU-native replacement for the reference's UCX
    peer-to-peer shuffle serving every exchange kind
    (RapidsShuffleInternalManager.scala:186-362,
    GpuShuffleExchangeExec.scala:60-215). Returns one DeviceBatch per
    mesh device, each committed to its device."""
    n = mesh.devices.size
    devs = list(mesh.devices.flat)
    assert len(shard_batches) == n, (len(shard_batches), n)
    cap = max(b.capacity for b in shard_batches)
    sidx = [i for i, dt in enumerate(schema.dtypes) if dt.is_string]
    char_caps = tuple(max(b.columns[i].data.shape[0] for b in shard_batches)
                      for i in sidx)
    if len(exchange_stats_log) < _EXCHANGE_STATS_CAP:
        exchange_stats_log.append(
            {"input_shard_caps": [b.capacity for b in shard_batches],
             "common_cap": cap})

    def prep(b: DeviceBatch):
        # normalize this shard to the common (cap, char_caps) layout and
        # flatten to the wire buffer list; leading length-1 axis is the
        # shard's block of the global (n, ...) array
        if b.capacity == cap and all(
                b.columns[i].data.shape[0] == char_caps[j]
                for j, i in enumerate(sidx)):
            cols = b.columns
            rows = b.num_rows
        else:
            idx = jnp.arange(cap, dtype=jnp.int32)
            perm = jnp.clip(idx, 0, b.capacity - 1)
            rows = jnp.minimum(b.num_rows, jnp.int32(cap))
            live = idx < rows
            cols = rowops.gather_columns(b.columns, perm, live, char_caps)
        out = []
        for c in cols:
            if c.dtype.is_string:
                lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int32)
                out.extend([lens[None], c.validity[None], c.data[None]])
            else:
                out.extend([c.data[None], c.validity[None]])
        out.append(rows[None].astype(jnp.int32))
        return tuple(out)

    flat_per_shard = [jax.jit(prep)(b) for b in shard_batches]

    # --- assemble global arrays from the per-device blocks ---
    row_sh = NamedSharding(mesh, P("dp", None))
    vec_sh = NamedSharding(mesh, P("dp"))
    args, in_specs = [], []
    for bi in range(len(flat_per_shard[0])):
        blocks = [flat_per_shard[i][bi] for i in range(n)]
        shape = (n,) + blocks[0].shape[1:]
        sh = row_sh if len(shape) == 2 else vec_sh
        args.append(jax.make_array_from_single_device_arrays(
            shape, sh, blocks))
        in_specs.append(P("dp", None) if len(shape) == 2 else P("dp"))

    # +1: the trailing (n, n) send-count matrix (_make_local's last
    # output) — per-source-shard device-side partition sizes
    n_out = 1 + sum(3 if dt.is_string else 2 for dt in schema.dtypes) + 1
    out_specs = tuple([P("dp")] + [P("dp", None)] * (n_out - 1))
    fn = jax.jit(shard_map(_make_local(schema, n, pid_fn), mesh=mesh,
                           in_specs=tuple(in_specs), out_specs=out_specs))
    outs = fn(*args)
    if stats_out is not None:
        # global (n_src, n_dst) row counts; left as a device array — the
        # caller fetches when (and if) it folds MapOutputStatistics
        stats_out["send_counts"] = outs[-1]

    # unstack: each mesh device's addressable block -> one committed
    # DeviceBatch, staying resident on its device
    block = _shard_on
    results: List[DeviceBatch] = []
    for i in range(n):
        dev = devs[i]
        pos = 1
        cols = []
        for dt in schema.dtypes:
            if dt.is_string:
                cols.append(DeviceColumn(
                    dt, block(outs[pos], dev)[0],
                    block(outs[pos + 1], dev)[0],
                    block(outs[pos + 2], dev)[0]))
                pos += 3
            else:
                cols.append(DeviceColumn(
                    dt, block(outs[pos], dev)[0],
                    block(outs[pos + 1], dev)[0]))
                pos += 2
        results.append(DeviceBatch(schema, cols, block(outs[0], dev)[0]))
    return results


def mesh_range_bounds(shard_batches: Sequence[DeviceBatch],
                      key_idx: Sequence[int], ascending: Sequence[bool],
                      nulls_first: Sequence[bool], n: int):
    """Sample each shard's sort-key operand vectors ON ITS OWN device,
    then pick n-1 lexicographic upper bounds host-side — the distributed
    analogue of GpuRangePartitioner.scala:42-120's driver-side sample.
    Returns one (n-1,) np.uint64 vector per operand."""
    key_idx, ascending, nulls_first = (list(key_idx), list(ascending),
                                       list(nulls_first))

    def samp(b):
        return jnp.stack([o.astype(jnp.uint64) for o in
                          sortops.sort_key_operands(b, key_idx, ascending,
                                                    nulls_first)])

    sampler = jax.jit(samp)
    fetched = jax.device_get([(b.num_rows, sampler(b))
                              for b in shard_batches])
    k = int(jax.eval_shape(sampler, shard_batches[0]).shape[0])
    samples = []
    for rows, ops in fetched:
        rows = int(rows)
        if rows == 0:
            continue
        ops = np.asarray(ops)
        take = min(rows, 128)
        sel = np.linspace(0, rows - 1, take).astype(np.int64)
        samples.append(ops[:, sel])
    return pick_bounds_from_samples(samples, k, n)


def mesh_broadcast(mesh: Mesh, batch: DeviceBatch) -> List[DeviceBatch]:
    """Replicate a batch onto every mesh device with ONE device_put onto a
    fully-replicated NamedSharding (XLA moves it as a broadcast over ICI)
    — the collective analogue of the reference's executor-side broadcast
    rebuild (GpuBroadcastExchangeExec.scala:230-436). Returns one
    committed per-device view per mesh device."""
    repl = jax.device_put(batch, NamedSharding(mesh, P()))
    return [jax.tree.map(lambda a, dev=dev: _shard_on(a, dev), repl)
            for dev in mesh.devices.flat]


def mesh_exchange_hash(mesh: Mesh, schema: Schema, key_idx: Sequence[int],
                       batch: DeviceBatch) -> List[DeviceBatch]:
    """Hash-partition one batch's rows across the dp axis (compatibility
    wrapper over mesh_exchange_parts for callers holding a single
    unsharded batch; the engine's exchange feeds per-shard lists via
    mesh_collect_shards instead)."""
    n = mesh.devices.size
    key_idx = list(key_idx)
    shards = mesh_collect_shards(
        mesh, schema, [[batch]] + [[] for _ in range(n - 1)])
    return mesh_exchange_parts(mesh, schema, shards,
                               lambda b: _hash_pid(b, key_idx, n))


def distributed_hash_aggregate_step(mesh: Mesh, schema: Schema,
                                    key_exprs, update_inputs,
                                    update_reductions, merge_reductions,
                                    partial_schema: Schema, capacity: int):
    """Builds the SPMD step: per-shard partial agg, all-to-all exchange by
    key hash, per-shard merge. Returns a jitted fn over (n, capacity)
    sharded column arrays."""
    n = mesh.devices.size
    num_keys = len(key_exprs)

    def local_step(*cols_and_counts):
        *flat_cols, num_rows = cols_and_counts
        # shard_map keeps the sharded mesh axis with local extent 1 — strip
        # it to per-shard vectors, restore on output
        flat_cols = [a[0] for a in flat_cols]
        num_rows = num_rows[0]
        cols = []
        it = iter(flat_cols)
        for dt in schema.dtypes:
            if dt.is_string:
                chars, validity, offs = next(it), next(it), next(it)
                cols.append(DeviceColumn(dt, chars, validity, offs))
            else:
                data, validity = next(it), next(it)
                cols.append(DeviceColumn(dt, data, validity))
        batch = DeviceBatch(schema, cols, num_rows)
        partial = aggregate_update(batch, key_exprs, update_inputs,
                                   update_reductions, partial_schema)
        # exchange: hash-partition partial rows across the mesh
        buffers, counts = _send_buffers(
            partial, _hash_pid(partial, list(range(num_keys)), n), n)
        received, rcounts = _a2a_exchange(buffers, counts)
        cols2, total = _compact_received(partial_schema.dtypes, received,
                                         rcounts, n)
        rbatch = DeviceBatch(partial_schema, cols2, total)
        merged = aggregate_merge(rbatch, num_keys, merge_reductions,
                                 partial_schema)
        out = [merged.num_rows[None]]
        for c in merged.columns:
            out.append(c.data[None, :])
            out.append(c.validity[None, :])
            if c.dtype.is_string:
                out.append(c.offsets[None, :])
        return tuple(out)

    def _arrays_per_col(sch: Schema) -> int:
        return sum(3 if dt.is_string else 2 for dt in sch.dtypes)

    in_specs = tuple([P("dp", None)] * _arrays_per_col(schema) + [P("dp")])
    out_specs = tuple([P("dp")]
                      + [P("dp", None)] * _arrays_per_col(partial_schema))
    fn = shard_map(local_step, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs)
    return jax.jit(fn)


def dryrun_session_mesh(n_devices: int) -> None:
    """Engine-integrated mesh execution: a group-by aggregate, a shuffled
    hash join, a global sort (range exchange: per-shard sample -> bounds
    -> all_to_all), a limit over that sort (one running count carried
    across partitions that live on different devices), and a broadcast
    join (mesh_broadcast replication) run through the *session* API with
    every exchange riding the fused shard_map all_to_all over the dp
    axis, checked against the CPU oracle."""
    import numpy as np
    import pandas as pd
    from spark_rapids_tpu.session import TpuSparkSession
    from spark_rapids_tpu.sql import functions as F

    s = TpuSparkSession.builder().config(
        "spark.rapids.sql.enabled", True).get_or_create()
    s.set_mesh(n_devices)
    saved = dict(s.conf._settings)
    try:
        s.set_conf("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
        rng = np.random.default_rng(7)
        rows = 64 * n_devices
        left = pd.DataFrame({
            "k": rng.integers(0, 9, rows).astype(np.int64),
            "v": rng.random(rows),
        })
        right = pd.DataFrame({"k": np.arange(9, dtype=np.int64),
                              "tag": [f"t{i}" for i in range(9)]})

        def q(sess):
            l = sess.create_dataframe(left, 2)
            r = sess.create_dataframe(right, 2)
            return (l.join(r, on="k", how="inner")
                     .group_by("tag")
                     .agg(F.sum("v").alias("sv"), F.count("*").alias("n")))

        def q_sort(sess):
            return sess.create_dataframe(left, n_devices).order_by("v")

        def q_limit(sess):
            return q_sort(sess).limit(5)

        def q_bcast(sess):
            # small build side under the default broadcast threshold:
            # replicated over the mesh via mesh_broadcast
            l = sess.create_dataframe(left, n_devices)
            r = sess.create_dataframe(right, 1)
            return (l.join(r, on="k", how="inner")
                     .group_by("tag").agg(F.count("*").alias("n")))

        tpu = q(s).collect().sort_values("tag").reset_index(drop=True)
        tpu_sorted = q_sort(s).collect().reset_index(drop=True)
        tpu_limit = q_limit(s).collect().reset_index(drop=True)
        s.conf._settings.pop(
            "spark.rapids.sql.autoBroadcastJoinThreshold", None)
        tpu_b = q_bcast(s).collect().sort_values("tag").reset_index(drop=True)
        s.set_conf("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
        s.set_conf("spark.rapids.sql.enabled", False)
        cpu = q(s).collect().sort_values("tag").reset_index(drop=True)
        cpu_sorted = q_sort(s).collect().reset_index(drop=True)
        cpu_limit = q_limit(s).collect().reset_index(drop=True)
        cpu_b = q_bcast(s).collect().sort_values("tag").reset_index(drop=True)
        assert list(tpu["n"]) == list(cpu["n"]), (tpu, cpu)
        np.testing.assert_allclose(tpu["sv"].to_numpy(dtype=np.float64),
                                   cpu["sv"].to_numpy(dtype=np.float64),
                                   rtol=1e-9)
        np.testing.assert_allclose(
            tpu_sorted["v"].to_numpy(dtype=np.float64),
            cpu_sorted["v"].to_numpy(dtype=np.float64), rtol=1e-9)
        np.testing.assert_allclose(
            tpu_limit["v"].to_numpy(dtype=np.float64),
            cpu_limit["v"].to_numpy(dtype=np.float64), rtol=1e-9)
        assert list(tpu_b["n"]) == list(cpu_b["n"]), (tpu_b, cpu_b)
    finally:
        s.conf._settings = saved
        s.set_mesh(None)


def dryrun_distributed_q1(n_devices: int, rows_per_shard: int = 512) -> None:
    """Multichip validation: a full distributed TPC-H-Q1-shaped
    aggregation step (dp sharding + all-to-all shuffle + merge) on an
    n-device mesh, executed once on tiny shapes."""
    import datetime
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.exprs.core import bind_references
    from spark_rapids_tpu.exec.aggutil import AggPlan
    from spark_rapids_tpu.sql.planner import _bind_non_agg

    from spark_rapids_tpu.columnar.column import DeviceColumn as _DC

    mesh = data_parallel_mesh(n_devices)
    n = n_devices
    rng = np.random.default_rng(3)
    total_rows = n * rows_per_shard

    # lineitem-shaped data grouped by REAL string keys (the returnflag x
    # linestatus combos), exercising the string all-to-all transport
    key_pool = np.array(["A|F", "N|O", "R|F", "A|O", "N|F", "R|O"],
                        dtype=object)
    key_vals = key_pool[rng.integers(0, len(key_pool), total_rows)]
    schema = Schema(
        ["flag_status", "l_quantity", "l_extendedprice", "l_discount",
         "l_tax", "ship_days"],
        [dtypes.STRING, dtypes.FLOAT64, dtypes.FLOAT64, dtypes.FLOAT64,
         dtypes.FLOAT64, dtypes.INT32])
    data = {
        "flag_status": key_vals,
        "l_quantity": rng.integers(1, 51, total_rows).astype(np.float64),
        "l_extendedprice": rng.uniform(900, 105000, total_rows),
        "l_discount": rng.integers(0, 11, total_rows) * 0.01,
        "l_tax": rng.integers(0, 9, total_rows) * 0.01,
        "ship_days": rng.integers(8000, 10600, total_rows).astype(np.int32),
    }

    grouping = [("flag_status",
                 bind_references(F.col("flag_status").expr, schema))]
    disc_price = F.col("l_extendedprice") * (1 - F.col("l_discount"))
    charge = disc_price * (1 + F.col("l_tax"))
    results = [
        ("flag_status", F.col("flag_status").expr),
        ("sum_qty", F.sum("l_quantity").expr),
        ("sum_disc_price", F.sum(disc_price).expr),
        ("sum_charge", F.sum(charge).expr),
        ("avg_disc", F.avg("l_discount").expr),
        ("n", F.count("*").expr),
    ]
    plan = AggPlan(schema, grouping,
                   [(nm, _bind_non_agg(e, schema)) for nm, e in results])
    update_reds = [(kind, idx, idt) for ops in plan.update_plan
                   for kind, idx, idt in ops]
    merge_reds = [(kind, col, idt) for merged in plan.merge_plan
                  for kind, col, idt in merged]

    step = distributed_hash_aggregate_step(
        mesh, schema, [e for _, e in plan.grouping], plan.update_inputs,
        update_reds, merge_reds, plan.partial_schema, rows_per_shard)

    # lay out inputs sharded over dp; string columns ship as stacked
    # per-shard (chars, validity, offsets) buffers with one shared char
    # capacity
    args = []
    shard = NamedSharding(mesh, P("dp", None))
    for name, dt in zip(schema.names, schema.dtypes):
        if dt.is_string:
            vals = data[name].reshape(n, rows_per_shard)
            ccap = 16
            while any(sum(len(v) for v in vals[s]) > ccap for s in range(n)):
                ccap <<= 1
            chs, vs, offs = [], [], []
            for s in range(n):
                c, v, o, _p = _DC.build_host_buffers(
                    vals[s], None, dt, rows_per_shard, char_capacity=ccap)
                chs.append(c)
                vs.append(v)
                offs.append(o)
            args.append(jax.device_put(np.stack(chs), shard))
            args.append(jax.device_put(np.stack(vs), shard))
            args.append(jax.device_put(np.stack(offs), shard))
            continue
        arr = data[name].reshape(n, rows_per_shard)
        args.append(jax.device_put(arr, shard))
        args.append(jax.device_put(
            np.ones((n, rows_per_shard), dtype=np.bool_), shard))
    counts = jax.device_put(np.full((n,), rows_per_shard, dtype=np.int32),
                            NamedSharding(mesh, P("dp")))
    args.append(counts)

    out = step(*args)
    num_rows = np.asarray(out[0])
    # verify: the distributed group count matches a host groupby
    expected_groups = len(np.unique(list(data["flag_status"])))
    got_groups = int(num_rows.sum())
    assert got_groups == expected_groups, (got_groups, expected_groups)
    # map output positions (string columns emit chars/validity/offsets)
    pos, out_map = 1, {}
    for nm, dt in zip(plan.partial_schema.names, plan.partial_schema.dtypes):
        out_map[nm] = pos
        pos += 3 if dt.is_string else 2
    # verify the string keys survive the exchange+merge byte-exact
    kidx = out_map["flag_status"]
    kch, kval, koff = (np.asarray(out[kidx]), np.asarray(out[kidx + 1]),
                       np.asarray(out[kidx + 2]))
    got_keys = set()
    for s in range(n):
        for r in range(int(num_rows[s])):
            got_keys.add(bytes(kch[s][koff[s][r]:koff[s][r + 1]]).decode())
    assert got_keys == set(key_pool), (got_keys, set(key_pool))
    # verify a global sum survives the exchange+merge exactly once
    sum_col_idx = out_map["_agg0"]
    sums = np.asarray(out[sum_col_idx])
    valid = np.asarray(out[sum_col_idx + 1])
    got = sums[valid].sum()
    expected = data["l_quantity"].sum()
    np.testing.assert_allclose(got, expected, rtol=1e-9)
