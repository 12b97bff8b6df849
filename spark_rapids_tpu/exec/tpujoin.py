"""TPU join operators (reference: GpuShuffledHashJoinExec /
GpuBroadcastHashJoinExec / GpuCartesianProductExec,
shims/spark300/.../GpuHashJoin.scala:113-244).

Execution shape matches the reference's hash join: the build side is
concatenated into one device batch and held; stream batches probe it one at
a time. Probe and expand are separately jitted (ops/joins.py) because the
expand specializes on the bucketed output capacity — the single
device->host sync per stream batch that dynamic join cardinality costs.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import DeviceBatch, Schema, bucket_capacity
from spark_rapids_tpu.columnar.column import _char_bucket
from spark_rapids_tpu.exec.base import ExecContext, Partition, PhysicalPlan
from spark_rapids_tpu.obs.metrics import REGISTRY
from spark_rapids_tpu.ops import joins as join_ops
from spark_rapids_tpu.utils.kernelcache import bucket_dim, cached_jit

SUPPORTED_JOIN_TYPES = ("inner", "left", "right", "full", "leftsemi",
                        "leftanti", "cross")
# join types whose residual condition runs on the device: the stream side
# is the left, and no row is null-extended by what the condition decides
CONDITIONED_JOIN_TYPES = ("inner", "leftsemi", "leftanti")
_INPUT_BYTES = REGISTRY.counter("join.inputBytes")
# a conditioned join: the key-equal pairs whose residual was evaluated,
# the piece programs, and the bytes they were handed (each input's rows at
# the least width of the columns the residual reads, each input once, plus
# 4 bytes a pair), counted as join.inputBytes is
_COND_PAIRS = REGISTRY.counter("join.cond.pairs")
_COND_PIECES = REGISTRY.counter("join.cond.pieces")
_COND_INPUT_BYTES = REGISTRY.counter("join.cond.inputBytes")
# stream rows the extent form decided (rows the host knows without a sync)
_COND_EXTENT_ROWS = REGISTRY.counter("join.cond.extentRows")
# stream rows the sort probe took (rows the host knows without a sync); a
# join that probes the dense table adds 0, so the count is never missing
_SORT_ROWS = REGISTRY.counter("join.probe.sortRows")
# stream rows the compact dense table probed, counted the same way; a join
# that sets up a probe adds 0 where it takes another form
_COMPACT_ROWS = REGISTRY.counter("join.probe.compactRows")


def _rounds(batches, row_bytes: int):
    """A join's stream in rounds, each probed and its sizes fetched at
    once: a round takes consecutive batches until its bytes (capacity x
    ``row_bytes``) and those of one more batch as large as its largest
    would pass the collapse bound (exec/tpu._collapse_bound_bytes). The
    next batch is pulled after the round is emitted, so a stream a
    collapse cut into pieces is not drained ahead of its probe; a stream
    of one batch, or whose bytes and its largest batch's stay within the
    bound, is one round."""
    from spark_rapids_tpu.exec.tpu import _collapse_bound_bytes
    bound = _collapse_bound_bytes()
    rnd, size, top = [], 0, 0
    for b in batches:
        nbytes = b.capacity * row_bytes
        rnd.append(b)
        size, top = size + nbytes, max(top, nbytes)
        del b  # the round's batches are the consumer's to drop
        if size + top > bound:
            yield rnd
            rnd, size, top = [], 0, 0
    if rnd:
        yield rnd


def _pull_build(batches, row_bytes: int, swappable: bool):
    """(the build's batches, None); or, where ``swappable`` and the bytes
    of the batches pulled (capacity x ``row_bytes``) pass the collapse
    bound, (those batches, an iterator of the rest): the join then streams
    them (TpuShuffledHashJoinExec._swapped)."""
    from spark_rapids_tpu.exec.tpu import _collapse_bound_bytes
    bound = _collapse_bound_bytes()
    it = iter(batches)
    held, size = [], 0
    for b in it:
        held.append(b)
        size += b.capacity * row_bytes
        if swappable and size > bound:
            return held, it
    return held, None


def _popping(held: list, rest):
    """``held`` then ``rest``, each batch let go of as it is handed on."""
    while held:
        yield held.pop(0)
    yield from rest


def _start_host_copies(arrays) -> None:
    """Begin async device->host transfers so the deferred speculation-
    verification fetch (session._verify_speculation) overlaps the rest of
    the query instead of paying its own round trip at the end.
    Delegates to the shared tree-walking prefetch (columnar/batch.py)."""
    from spark_rapids_tpu.columnar.batch import _start_host_copies_tree
    _start_host_copies_tree(list(arrays))


class TpuBroadcastExchangeExec(PhysicalPlan):
    """Materializes the child once as a single device batch shared by every
    consumer partition (reference: GpuBroadcastExchangeExec.scala:230-436
    re-materializes the broadcast on device per task; here the batch is
    already device-resident so it is simply cached)."""

    columnar_output = True

    def __init__(self, child: PhysicalPlan):
        super().__init__([child])
        self._cache = {}

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        child = self.children[0]
        growth = ctx.conf.capacity_growth

        def materialize():
            from spark_rapids_tpu.exec.tpu import (
                _concat_device, _drain_claimed, _fused_filter_source,
            )
            # a Filter directly below is claimed and run a batch at a time
            # under the drain, as the exchange's collapse does
            src_node, claimed = _fused_filter_source(child, ctx)
            parts = src_node.executed_partitions(ctx)
            batches, masks, _ = _drain_claimed(parts, claimed)
            if not batches:
                return _concat_device(batches, child.output_schema(),
                                      growth, coarse=True)
            out = _concat_device(batches, child.output_schema(), growth,
                                 masks, coarse=True)
            if ctx.metrics_enabled:
                # build-table size on record: the broadcast twin of the
                # exchanges' MapStatus sizes, so a (static or AQE-demoted)
                # broadcast's actual footprint is visible next to the
                # threshold that chose it (obs/events.py event kinds)
                from spark_rapids_tpu.obs.events import EVENTS
                from spark_rapids_tpu.obs.metrics import REGISTRY
                nbytes = out.device_memory_size()
                REGISTRY.gauge("shuffle.broadcast.bytes").set(nbytes)
                REGISTRY.counter("shuffle.broadcast.builds").add(1)
                EVENTS.emit("broadcastMaterialized", bytes=int(nbytes),
                            batches=len(batches))
            return out

        if ctx.session is None:
            def run():
                if "batch" not in self._cache:
                    self._cache["batch"] = materialize()
                yield self._cache["batch"]
            return [run]

        # the broadcast table lives in the spillable BufferCatalog (the
        # reference materializes broadcasts as spillable device buffers,
        # GpuBroadcastExchangeExec.scala:230-436): consumers acquire per
        # use, faulting a spilled table back; OUTPUT_FOR_WRITE band so
        # shuffle output (OUTPUT_FOR_READ) evicts first
        def run_catalog():
            from spark_rapids_tpu.memory.spill import SpillPriorities
            bid = self._cache.get("bid")
            if bid is None or not ctx.session.buffer_catalog.contains(bid):
                # first use, or the entry was swept (query-end transient
                # release / speculation re-execution): re-materialize
                bid = self._cache["bid"] = ctx.session.add_transient_batch(
                    materialize(), SpillPriorities.OUTPUT_FOR_WRITE)
            yield ctx.session.buffer_catalog.acquire_batch(bid)
        return [run_catalog]


class TpuShuffledHashJoinExec(PhysicalPlan):
    columnar_output = True

    @property
    def padded_output(self) -> bool:
        # a semi or anti join compacts hard within its stream's capacity
        return self.join_type in ("leftsemi", "leftanti")

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 join_type: str, left_keys: List[int], right_keys: List[int],
                 exact_long_strings: bool = True, condition=None):
        super().__init__([left, right])
        assert join_type in SUPPORTED_JOIN_TYPES, join_type
        assert condition is None or join_type in CONDITIONED_JOIN_TYPES
        # the residual a key-equal pair must also pass, bound against the
        # combined left+right schema (None: a plain equi-join)
        self.condition = condition
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        # >64-byte string key equality: exact full-length verification
        # (default) vs dual-hash tiebreak (incompat,
        # spark.rapids.sql.join.exactLongStrings=false)
        self.exact_long_strings = exact_long_strings

        # right outer streams the right side against a left-side build so
        # every preserved row is a stream row (the reference flips build
        # side the same way, GpuHashJoin.scala:60-76)
        self._stream_is_left = join_type != "right"
        jt = join_type
        skey = tuple(self.left_keys if self._stream_is_left
                     else self.right_keys)
        bkey = tuple(self.right_keys if self._stream_is_left
                     else self.left_keys)
        sig = f"join|{jt}|{skey}|{bkey}|x{int(exact_long_strings)}"
        self._skey, self._bkey = skey, bkey
        # the probe, totals and expand with the sides as planned, and
        # (built on first use, _swapped) with an inner join's sides
        # exchanged
        self._planned = self._oriented(skey, bkey, not self._stream_is_left,
                                       sig)
        self._probe = self._planned.probe
        self._totals = self._planned.totals
        self._expand = self._planned.expand
        self._swap = None
        # what every ``dispatch.join`` span of this join says of itself

        def span_attrs(*_a):
            return {"type": jt}
        if jt == "full":
            # a lambda of its own: cached_jit names the jitted function
            # after the family, and the module's function keeps its name
            self._match_flags = cached_jit(sig + "|mf", lambda: jax.jit(
                lambda *a: join_ops.build_match_flags(*a)), span_attrs)
            self._unmatched = cached_jit(sig + "|unm", lambda: jax.jit(
                lambda b, m, ss: join_ops.unmatched_build_batch(
                    b, m, ss, swap_sides=False),
                static_argnums=(2,)), span_attrs)
        if jt in ("leftsemi", "leftanti"):
            self._semi = cached_jit(sig + "|semi", lambda: jax.jit(
                lambda s, c: join_ops.semi_anti_filter(
                    s, c, anti=jt == "leftanti")), span_attrs)
        if condition is not None:
            self._init_conditioned(sig)

    def _oriented(self, skey, bkey, swap: bool, sig: str):
        """The probe, totals and expand of this join streaming the side
        whose keys are ``skey`` against a build keyed by ``bkey``;
        ``swap``: the build is the left side, so the expand puts its
        columns first."""
        from types import SimpleNamespace
        jt = self.join_type
        cross = jt == "cross"
        exact = self.exact_long_strings
        outer = jt in ("left", "right", "full")

        def span_attrs(*_a):
            return {"type": jt}
        probe = cached_jit(sig + "|probe", lambda: jax.jit(
            lambda b, s: join_ops.join_probe(
                b, s, bkey, skey, cross=cross, exact_long_strings=exact)),
            lambda *_a: {"type": jt, "form": "sort"})

        def sort_probe(build, stream):
            _SORT_ROWS.add(stream.num_rows_hint())
            return probe(build, stream)

        def expand(build, stream, counts, bstart, bperm, out_cap, s_caps,
                   b_caps):
            adj = (join_ops.outer_adjusted_counts(stream, counts)
                   if outer else counts)
            return join_ops.join_expand(build, stream, counts, adj, bstart,
                                        bperm, out_cap, swap, s_caps, b_caps)

        def totals(build, stream, counts, bstart, bperm):
            adj = (join_ops.outer_adjusted_counts(stream, counts)
                   if outer else counts)
            return join_ops.expand_totals(build, stream, counts, adj, bperm,
                                          bstart)
        return SimpleNamespace(
            sig=sig, skey=skey, bkey=bkey, probe=sort_probe,
            expand=cached_jit(
                sig + "|expand",
                lambda: jax.jit(expand, static_argnums=(5, 6, 7)),
                lambda *a: {"type": jt, "out_cap": a[5]}),
            totals=cached_jit(sig + "|totals", lambda: jax.jit(totals),
                              span_attrs))

    def _swapped(self):
        """The orientation of an unconditioned inner join whose planned
        build, the right side, passed the collapse bound
        (exec/tpu._collapse_bound_bytes): the right side streams, in the
        pieces its collapse cut it into, against a build of the left, and
        the output keeps the left side's columns first."""
        if self._swap is None:
            sk, bk = tuple(self.right_keys), tuple(self.left_keys)
            self._swap = self._oriented(
                sk, bk, True, f"join|{self.join_type}|{sk}|{bk}"
                f"|x{int(self.exact_long_strings)}|swap")
        return self._swap

    def _extent_plan(self, n_left: int):
        """(op, build column, stream column) where the extent form decides
        this join: a semi or anti join whose residual is one ``<>``, ``<``,
        ``<=``, ``>`` or ``>=`` of a build column with a stream column, in
        either order (``op`` is read as ``build <op> stream``), both of
        integer kind and compared without a scaling (a date against a
        timestamp is); else None."""
        from spark_rapids_tpu.columnar import dtype as dtypes
        from spark_rapids_tpu.sql.exprs import predicates as P
        from spark_rapids_tpu.sql.exprs.core import BoundRef
        ops = {P.Neq: ("ne", "ne"), P.Lt: ("lt", "gt"), P.Le: ("le", "ge"),
               P.Gt: ("gt", "lt"), P.Ge: ("ge", "le")}
        c = self.condition
        if self.join_type == "inner" or type(c) not in ops or not all(
                isinstance(x, BoundRef) for x in c.children):
            return None
        left, right = (x.index for x in c.children)
        if left >= n_left > right:
            op, b, s = ops[type(c)][0], left - n_left, right
        elif right >= n_left > left:
            op, b, s = ops[type(c)][1], right - n_left, left
        else:
            return None
        dts = (self.children[1].output_schema().dtypes[b],
               self.children[0].output_schema().dtypes[s])
        scaled = dts[0] != dts[1] and bool(
            {dtypes.DATE32, dtypes.TIMESTAMP_US} & set(dts))
        if scaled or not all(map(join_ops.cond_packable, dts)):
            return None
        return op, b, s

    def _init_conditioned(self, sig: str) -> None:
        """The programs of the residual's evaluation, family ``cjoin``:
        ``layout`` once a stream batch (the one fetch's sizes); then, in
        the extent form (``_extent_plan``), one ``extent`` program a
        stream batch; else ``prep`` (the words and the pairs' slots) and
        one ``piece`` (semi, anti) or ``pairs`` (inner) program a piece of
        at most ``join_ops.COND_PIECE_PAIRS`` pairs."""
        from spark_rapids_tpu.sql.exprs.core import BoundRef, walk
        from spark_rapids_tpu.utils.kernelcache import expr_signature
        jt = self.join_type
        n_left = len(self.children[0].output_schema().names)
        extent = self._extent_plan(n_left)
        self._cform = "pieces" if extent is None else "extent"
        refs = sorted({e.index for e in walk(self.condition)
                       if isinstance(e, BoundRef)})
        # the columns the residual reads, stream (left) side first, and
        # the condition rebound to that compact layout
        cs = self._cs = tuple(i for i in refs if i < n_left)
        cb = self._cb = tuple(i - n_left for i in refs if i >= n_left)
        pos = {i: j for j, i in enumerate(refs)}

        def rebind(e):
            if isinstance(e, BoundRef):
                return BoundRef(pos[e.index], e._dtype, e.name)
            return e.map_children(rebind)
        cond = rebind(self.condition)
        ls = self.children[0].output_schema()
        rs = self.children[1].output_schema()
        names = tuple(f"r{j}" for j in range(len(refs)))
        dts = self._cdts = tuple(
            ls.dtypes[i] if i < n_left else rs.dtypes[i - n_left]
            for i in refs)
        csig = f"c{sig}|{expr_signature(self.condition)}"
        self._cstate = {}

        def attrs(*_a):
            return {"type": jt, "form": self._cform, **self._cstate}
        self._clayout = cached_jit(csig + "|layout", lambda: jax.jit(
            lambda b, s, c, bs, bp: join_ops.cond_layout(
                b, s, c, bs, bp, cb, cs)), attrs)
        if extent is not None:
            op, b_col, s_col = extent
            bkey = self._bkey[0]
            self._cextent = cached_jit(csig + "|extent", lambda: jax.jit(
                lambda b, s, c, bs, bp, low, lo, narrow, table:
                join_ops.cond_extent(b, s, c, bs, bp, low, lo, b_col, s_col,
                                     op, narrow, bkey, table),
                static_argnums=(7, 8)), attrs)
            return
        self._cprep = cached_jit(csig + "|prep", lambda: jax.jit(
            lambda b, s, bp, c, bs, lows, narrow: join_ops.cond_prep(
                b, s, bp, c, bs, lows, cs, cb, narrow),
            static_argnums=(6,)), attrs)
        if jt == "inner":
            self._cpairs = cached_jit(csig + "|pairs", lambda: jax.jit(
                lambda b, s, bp, sr, bw, bo, incl, base, total, lows,
                narrow, pc, caps, s_caps, b_caps: join_ops.cond_piece_pairs(
                    b, s, bp, sr, bw, bo, incl, base, total, lows,
                    cond, names, dts, cs, narrow, pc, caps, s_caps, b_caps),
                static_argnums=(10, 11, 12, 13, 14)), attrs)
        else:
            self._cpiece = cached_jit(csig + "|piece", lambda: jax.jit(
                lambda s, sr, bw, bo, incl, acc, base, total, lows,
                narrow, pc, caps: join_ops.cond_piece_counts(
                    s, sr, bw, bo, incl, acc, base, total, lows, cond,
                    names, dts, cs, narrow, pc, caps),
                static_argnums=(9, 10, 11)), attrs)

    def fingerprint_extra(self) -> str:
        if self.condition is None:
            return super().fingerprint_extra()
        from spark_rapids_tpu.utils.kernelcache import expr_signature
        return expr_signature(self.condition)

    def output_schema(self) -> Schema:
        ls = self.children[0].output_schema()
        rs = self.children[1].output_schema()
        if self.join_type in ("leftsemi", "leftanti"):
            return ls
        return Schema(list(ls.names) + list(rs.names),
                      list(ls.dtypes) + list(rs.dtypes))

    def describe(self) -> str:
        cond = (f", cond={self.condition!r}" if self.condition is not None
                else "")
        return (f"TpuShuffledHashJoinExec({self.join_type}, "
                f"l={self.left_keys}, r={self.right_keys}{cond})")

    def _sides(self):
        """(stream_child_idx, build_child_idx)."""
        return (0, 1) if self._stream_is_left else (1, 0)

    # dense-key fast path: direct-index probe over a bounded key range
    # (ops/joins.join_probe_dense). Applicable to single-int-key equi
    # joins whose build key has scan-derived advisory bounds small enough
    # to table. The reference's equivalent is cuDF's hash build+probe;
    # here the "hash table" is the identity map over the key range.
    # The table costs 0.55 ns a slot on a v5e (a 2^26-slot cumsum 0.037 s)
    # and a stream row one 12 ns gather, where the union sort costs 122 ns
    # a slot of build and stream alike (PERF.md, PR 28): the table wins
    # under any batch a scan hands over, so the cap is what the table may
    # hold of HBM: its bytes a slot times its slots stay within 2 GB. The
    # packed table (s32 counts and starts, and their stack) takes 16 bytes
    # a slot, so 2^27 slots; past that the compact one (one s32 prefix sum
    # and the counts it is summed from) takes 8, so 2^28. The packed row
    # gather reads a stream row in 12-20 ns at 2^26 slots, the compact
    # pair in 30-45 (PERF.md, section 6): a table that fits packed stays so.
    _DENSE_TABLE_BYTES = 2 << 30
    _PACKED_SLOT_BYTES = 16
    _COMPACT_SLOT_BYTES = 8

    def _dense_plan(self, ctx, build_schema, k=None):
        """(lo, table_size) when the dense path applies to orientation
        ``k`` (_oriented; the planned one where None), else None."""
        k = k or self._planned
        if self.join_type == "cross" or len(k.bkey) != 1:
            return None
        if ctx.session is None:
            return None
        bk = k.bkey[0]
        dt = build_schema.dtypes[bk]
        if dt.is_string or not jnp.issubdtype(
                jnp.dtype(dt.np_dtype), jnp.integer):
            return None
        # resolve the build key's name through the rename-alias map to
        # scan stats; union bounds over every candidate source (multiple
        # sources only loosen — the device verification catches any
        # residual mismatch)
        from spark_rapids_tpu.exec.statsutil import int_bounds_for_names
        got = int_bounds_for_names(ctx.session, {build_schema.names[bk]})
        if got is None:
            return None
        lo, hi = got
        rng = hi - lo + 1
        if rng <= 0:
            return None
        table_size = 1024
        while table_size < rng:
            table_size <<= 1
        table_size = bucket_dim(table_size)
        if table_size * self._COMPACT_SLOT_BYTES > self._DENSE_TABLE_BYTES:
            return None
        return lo, table_size

    def _dense_kernel(self, k, table_size: int):
        """The dense probe of a ``table_size``-slot table: packed where it
        fits the table's HBM, else compact (ops/joins.join_probe_dense)."""
        bk, sk = k.bkey[0], k.skey[0]
        compact = (table_size * self._PACKED_SLOT_BYTES
                   > self._DENSE_TABLE_BYTES)
        kern = cached_jit(
            f"{k.sig}|dense{table_size}" + ("|compact" if compact else ""),
            lambda: jax.jit(
                lambda b, s, lo: join_ops.join_probe_dense(
                    b, s, bk, sk, lo, table_size, compact)),
            lambda *_a: {"type": self.join_type, "form": "dense",
                         "slots": table_size})
        if not compact:
            return kern

        def compact_probe(build, stream, lo):
            _COMPACT_ROWS.add(stream.num_rows_hint())
            return kern(build, stream, lo)
        return compact_probe

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        si, bi = self._sides()
        stream_parts = self.children[si].executed_partitions(ctx)
        build_parts = self.children[bi].executed_partitions(ctx)
        growth = ctx.conf.capacity_growth
        build_schema = self.children[bi].output_schema()
        if len(stream_parts) != len(build_parts):
            # broadcast build side: one build partition shared by every
            # stream partition (full outer never broadcasts — the unmatched-
            # build scan must see all stream rows, planner guarantees this)
            assert len(build_parts) == 1 and self.join_type != "full", \
                "join children must be co-partitioned or build broadcast"
            mesh = getattr(ctx.session, "mesh", None) if ctx.session else None
            if mesh is not None:
                # replicate the build table over the mesh with ONE
                # collective device_put (parallel/distributed.mesh_broadcast
                # — GpuBroadcastExchangeExec.scala:230-436's executor-side
                # rebuild); stream partition i probes the copy resident on
                # ITS device, so the probe kernel never crosses devices
                orig_bp = build_parts[0]
                n_dev = mesh.devices.size
                bstate: dict = {}

                def views():
                    if "v" not in bstate:
                        from spark_rapids_tpu.exec.tpu import _concat_device
                        from spark_rapids_tpu.parallel.distributed import (
                            mesh_broadcast,
                        )
                        build0 = _concat_device(list(orig_bp()),
                                                build_schema, growth,
                                                coarse=True)
                        bstate["v"] = mesh_broadcast(mesh, build0)
                    return bstate["v"]

                def mk_view(i: int) -> Partition:
                    return lambda: iter([views()[i % n_dev]])
                build_parts = [mk_view(i) for i in range(len(stream_parts))]
            else:
                build_parts = build_parts * len(stream_parts)
        jt = self.join_type
        # rows of every stream batch handed to a probe, by what the host
        # knows without a sync (the row count where fetched, else capacity)
        stream_rows = REGISTRY.counter("join.stream.rows", type=jt)
        # the out_cap every expand was dispatched with
        expand_rows = REGISTRY.counter("join.expand.outRows", type=jt)
        # rows of the build and of every stream batch, counted the same
        # way, at the least width of a row of each schema, each input once
        from spark_rapids_tpu.exec.tpu import _row_bytes
        stream_row_bytes = _row_bytes(self.children[si].output_schema())
        build_row_bytes = _row_bytes(build_schema)

        def count_streams(streams, row_bytes=stream_row_bytes):
            rows = sum(s.num_rows_hint() for s in streams)
            stream_rows.add(rows)
            _INPUT_BYTES.add(rows * row_bytes)

        dense = None

        # adaptive capacity speculation (spark.rapids.sql.adaptiveCapacity.
        # enabled): the expansion-size fetch below is the ONE unavoidable
        # device->host sync dynamic join cardinality costs (module
        # docstring) — one blocking round trip per join, so a 6-join plan
        # pays six of them in steady state.
        # The session remembers each (join, partition)'s sizes keyed by
        # the structural plan fingerprint (data-uid-stamped, base.py) and
        # later executions expand straight into the remembered buckets;
        # the exact device-side sizes are still computed and verified in
        # ONE deferred fetch at query end (session._verify_speculation),
        # which transparently re-executes the query without speculation on
        # any miss. Capacities only pad — a covered speculation is EXACT.
        spec_fp = None

        def spec_key(idx: int) -> Optional[str]:
            nonlocal spec_fp
            if not getattr(ctx, "speculate", False):
                return None
            if spec_fp is None:
                from spark_rapids_tpu.exec.base import plan_fingerprint
                from spark_rapids_tpu.exec.reuse import subtree_deterministic
                # a nondeterministic input (rand() filter) changes sizes
                # every run: speculation would alternate learn/miss and
                # re-execute every other query
                spec_fp = (plan_fingerprint(self)
                           if subtree_deterministic(self) else False)
            if spec_fp is False:
                return None
            return f"{spec_fp}|g{growth}|part{idx}"

        def make(sp: Partition, bp: Partition, pidx: int) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                from spark_rapids_tpu.exec.tpu import _concat_device
                # out-of-core: when the measured working set (build +
                # stream batches) exceeds the budget, grace-hash-
                # partition both sides onto the spill store and join
                # bucket by bucket (exec/outofcore.py) instead of
                # holding one giant build table
                from spark_rapids_tpu.exec import outofcore as ooc
                sp_local, bp_local = sp, bp
                if ooc.join_applicable(ctx, self):
                    # streaming probe on BOTH sides (never materializes
                    # past the budget): the build side is consumed up to
                    # the budget; if it fits, the stream side gets the
                    # remainder; on engagement the unconsumed tails flow
                    # straight into the grace driver's staging pass
                    import itertools
                    budget = ooc.working_set_budget(ctx)
                    bpre, brest, bover = ooc.split_stream_on_budget(
                        ctx, iter(bp()), budget)
                    if bover:
                        yield from ooc.grace_join(
                            ctx, self, itertools.chain(bpre, brest),
                            sp(), growth)
                        return
                    bbytes = ooc.total_batch_bytes(bpre)
                    spre, srest, sover = ooc.split_stream_on_budget(
                        ctx, iter(sp()), max(budget - bbytes, 1))
                    if sover:
                        yield from ooc.grace_join(
                            ctx, self, bpre,
                            itertools.chain(spre, srest), growth)
                        return
                    bp_local = lambda bl=bpre: iter(bl)  # noqa: E731
                    sp_local = lambda sl=spre: iter(sl)  # noqa: E731
                # an unconditioned inner join whose build passes the
                # collapse bound streams that side instead, in the pieces
                # its collapse cut it into, against a build of the other
                # (_swapped); any other join concatenates its build whole
                held, rest = _pull_build(
                    bp_local(), build_row_bytes,
                    jt == "inner" and self.condition is None)
                nonlocal dense
                if rest is None:
                    k, s_row, b_row = (self._planned, stream_row_bytes,
                                       build_row_bytes)
                    build = _concat_device(held, build_schema, growth,
                                           coarse=True)
                    stream_src = sp_local()
                    if dense is None:
                        dense = self._dense_plan(ctx,
                                                 build_schema) or False
                    dplan = dense
                else:
                    k, s_row, b_row = (self._swapped(), build_row_bytes,
                                       stream_row_bytes)
                    left_schema = self.children[si].output_schema()
                    build = _concat_device(list(sp_local()), left_schema,
                                           growth, coarse=True)
                    stream_src = _popping(held, rest)
                    dplan = self._dense_plan(ctx, left_schema, k) or False
                del held
                _INPUT_BYTES.add(build.num_rows_hint() * b_row)
                matched_acc = None
                emitted = False
                _COMPACT_ROWS.add(0)
                if dplan:
                    lo_arr = jnp.asarray(dplan[0], jnp.int64)
                    dkern = self._dense_kernel(k, dplan[1])
                else:
                    _SORT_ROWS.add(0)
                if self.condition is not None:
                    for streams in _rounds(stream_src, s_row):
                        count_streams(streams)
                        for out in self._conditioned(
                                build, streams,
                                dplan and (dkern, lo_arr, dplan[1])):
                            emitted = True
                            yield out
                    if not emitted:
                        yield DeviceBatch.empty(self.output_schema())
                    return
                key = spec_key(pidx)
                if key is not None and rest is not None:
                    key += "|swap"
                cache = (ctx.session.capacity_cache
                         if key is not None else None)

                def round_key(r: int) -> Optional[str]:
                    # the speculation cache's n and sizes are a round's
                    return key if r == 0 or key is None else f"{key}|r{r}"

                def semi_round(streams, key):
                    # probe every batch first, ONE ok-flag fetch for all
                    # of them (a per-batch device_get would block on a
                    # full round trip each)
                    nonlocal emitted
                    count_streams(streams)
                    raw = [dkern(build, s, lo_arr) for s in streams]
                    oks_d = [r[3] for r in raw]
                    entry = cache.get(key) if cache is not None else None
                    if (entry is not None and entry.get("dense_ok")
                            and entry.get("n") == len(streams)):
                        # speculate: last run's advisory bounds held;
                        # defer the ok-flag check to query end
                        _start_host_copies(oks_d)
                        ctx.session.capacity_spec_hits += 1
                        ctx.spec_pending.append((key, [], [], oks_d, None))
                        oks = [True] * len(streams)
                    else:
                        oks = jax.device_get(oks_d)
                        if cache is not None:
                            cache[key] = {"dense_ok": all(map(bool, oks)),
                                          "n": len(streams)}
                    for i, ok in enumerate(oks):
                        stream, counts = streams[i], raw[i][0]
                        streams[i] = raw[i] = None
                        if not bool(ok):
                            counts = self._probe(build, stream)[0]
                        emitted = True
                        yield self._semi(stream, counts)

                def expand_round(streams, key):
                    # probe EVERY stream batch of the round first (dispatch
                    # is async and nearly free), then fetch all expansion
                    # totals in ONE device->host round trip — a per-batch
                    # fetch would block dispatch on every batch.
                    # NB: exec/outofcore.py _join_bucket is this loop's
                    # simplified per-bucket twin (one fetch a batch, no
                    # rounds: a bucket is within the budget by
                    # construction) — semantic changes to the
                    # probe/totals/expand contract must be mirrored there
                    nonlocal emitted, matched_acc
                    count_streams(streams, s_row)
                    oks_d = []
                    if dplan:
                        raw = [dkern(build, s, lo_arr) for s in streams]
                        probes = [r[:3] for r in raw]
                        oks_d = [r[3] for r in raw]
                        del raw  # or probes[i]=None below frees nothing
                    else:
                        probes = [k.probe(build, s) for s in streams]
                    totals_d = [k.totals(build, s, *pr)
                                for s, pr in zip(streams, probes)]
                    entry = cache.get(key) if cache is not None else None
                    spec_hit = (
                        entry is not None and entry.get("n") == len(streams)
                        and entry.get("dense_ok", True)
                        and entry.get("sizes") is not None)
                    if spec_hit:
                        # speculate: expand into last run's buckets; the
                        # async host copies overlap the expand dispatches
                        # so the deferred verification fetch is ~free
                        sizes_all = entry["sizes"]
                        _start_host_copies(totals_d + oks_d)
                        ctx.session.capacity_spec_hits += 1
                        caps_used: list = []
                        ctx.spec_pending.append(
                            (key, totals_d, caps_used, oks_d, None))
                    elif dplan:
                        fetch = jax.device_get(
                            list(zip(totals_d, oks_d)))
                        sizes_all = []
                        all_ok = True
                        for bi_, (sizes_d, ok) in enumerate(fetch):
                            if bool(ok):
                                sizes_all.append(sizes_d)
                                continue
                            all_ok = False
                            # advisory bounds were wrong for this build:
                            # exact sort probe, one extra fetch (rare)
                            pr = k.probe(build, streams[bi_])
                            probes[bi_] = pr
                            sizes_all.append(jax.device_get(
                                k.totals(build, streams[bi_], *pr)))
                        if cache is not None:
                            cache[key] = {
                                "dense_ok": all_ok, "n": len(streams),
                                "sizes": [[int(x) for x in s]
                                          for s in sizes_all]}
                    else:
                        sizes_all = jax.device_get(totals_d)
                        if cache is not None:
                            cache[key] = {
                                "n": len(streams),
                                "sizes": [[int(x) for x in s]
                                          for s in sizes_all]}
                    for bi_, (stream, (counts, bstart, bperm),
                              sizes_d) in enumerate(
                            zip(streams, probes, sizes_all)):
                        # free consumed inputs as the loop advances: with
                        # many large stream batches, holding every batch +
                        # probe triple for the whole emission loop would
                        # grow peak HBM from O(batch) to O(round)
                        streams[bi_] = probes[bi_] = None
                        sizes = [int(x) for x in sizes_d]
                        total = sizes[0]
                        if jt == "full":
                            flags = self._match_flags(build, counts, bstart,
                                                      bperm)
                            matched_acc = (flags if matched_acc is None
                                           else matched_acc | flags)
                        if total == 0:
                            if spec_hit:
                                # asserted-empty: verification requires
                                # the actual total to be 0 as well
                                caps_used.append(None)
                            continue
                        n_s = sum(1 for d in stream.schema.dtypes
                                  if d.is_string)
                        s_caps = tuple(_char_bucket(c)
                                       for c in sizes[1:1 + n_s])
                        b_caps = tuple(_char_bucket(c)
                                       for c in sizes[1 + n_s:])
                        out_cap = bucket_dim(
                            bucket_capacity(total, growth))
                        if spec_hit:
                            caps_used.append((out_cap, s_caps, b_caps))
                        emitted = True
                        expand_rows.add(out_cap)
                        expanded = k.expand(build, stream, counts,
                                                bstart, bperm, out_cap,
                                                s_caps, b_caps)
                        from spark_rapids_tpu.memory.device import (
                            TpuDeviceManager,
                        )
                        dm = TpuDeviceManager.current()
                        if dm is not None:
                            dm.meter_batch(expanded)
                        yield expanded

                # a stream past the collapse bound comes as pieces, taken
                # a round at a time (_rounds); a full join's matched flags
                # are ORed across every round
                if jt in ("leftsemi", "leftanti") and not dplan:
                    for stream in stream_src:
                        emitted = True
                        count_streams([stream])
                        yield self._semi(stream,
                                         self._probe(build, stream)[0])
                else:
                    emit = (semi_round if jt in ("leftsemi", "leftanti")
                            else expand_round)
                    for r, streams in enumerate(_rounds(stream_src, s_row)):
                        yield from emit(streams, round_key(r))
                if jt == "full":
                    if matched_acc is None:
                        matched_acc = jnp.zeros((build.capacity,), jnp.bool_)
                    stream_schema = self.children[si].output_schema()
                    tail = self._unmatched(build, matched_acc, stream_schema)
                    if tail.num_rows_host() > 0 or not emitted:
                        emitted = True
                        yield tail
                if not emitted:
                    yield DeviceBatch.empty(self.output_schema())
            return run
        return [make(sp, bp, i)
                for i, (sp, bp) in enumerate(zip(stream_parts, build_parts))]

    def _words_plan(self, bounds):
        """(narrow flags, lows) from the residual's value bounds: a column
        narrows to one word when its valid values span less than 2^32 - 2
        (NULL takes the last word, the extent form's mark the one before);
        an all-NULL one too."""
        import numpy as np
        pairs = list(zip(bounds[0::2], bounds[1::2]))
        narrow = tuple(hi < lo or hi - lo < 0xFFFFFFFE for lo, hi in pairs)
        lows = np.asarray([lo if hi >= lo else 0 for lo, hi in pairs] or [0],
                          np.int64)
        return narrow, lows

    def _count_input(self, build, streams) -> None:
        from spark_rapids_tpu.exec.tpu import _row_bytes
        dts, n_s = self._cdts, len(self._cs)

        def row(side):
            return _row_bytes(Schema([f"r{i}" for i in range(len(side))],
                                     list(side)))
        _COND_INPUT_BYTES.add(build.num_rows_hint() * row(dts[n_s:])
                              + sum(s.num_rows_hint() for s in streams)
                              * row(dts[:n_s]))

    def _conditioned(self, build: DeviceBatch, streams, dense
                     ) -> Iterator[DeviceBatch]:
        """The join with a residual: probe every stream batch as the
        plain join does, fetch every batch's sizes in ONE round trip, then
        decide each stream row from its key's extremes (the extent form)
        or evaluate the residual over every key-equal pair in pieces. No
        capacity speculation: the pieces need the pair counts."""
        import numpy as np
        jt = self.join_type
        inner = jt == "inner"
        if dense:
            dkern, lo_arr, table = dense
            raw = [dkern(build, s, lo_arr) for s in streams]
            probes, oks = [r[:3] for r in raw], [r[3] for r in raw]
            del raw
        else:
            probes, oks = [self._probe(build, s) for s in streams], []
        layouts = [self._clayout(build, s, *pr)
                   for s, pr in zip(streams, probes)]
        totals = ([self._totals(build, s, *pr)
                   for s, pr in zip(streams, probes)] if inner else [])
        sizes, oks, totals = jax.device_get(
            ([lay[0] for lay in layouts], oks, totals))
        for i, ok in enumerate(oks):
            if not bool(ok):
                # the advisory bounds missed this build: the exact sort
                # probe, one more fetch (rare)
                probes[i] = self._probe(build, streams[i])
                layouts[i] = self._clayout(build, streams[i], *probes[i])
                sizes[i] = jax.device_get(layouts[i][0])
                if inner:
                    totals[i] = jax.device_get(
                        self._totals(build, streams[i], *probes[i]))
        dts = self._cdts
        n_pk = sum(1 for d in dts if join_ops.cond_packable(d))
        strs = [d for d in dts if d.is_string]
        self._count_input(build, streams)
        for i, stream in enumerate(streams):
            _counts, bstart, bperm = probes[i]
            _sizes_d, b_other, counts, zeros = layouts[i]
            streams[i] = probes[i] = layouts[i] = None
            sz = [int(x) for x in sizes[i]]
            pairs = sz[0]
            narrow, lows = self._words_plan(sz[1:1 + 2 * n_pk])
            _COND_PAIRS.add(pairs)
            _COND_INPUT_BYTES.add(4 * pairs)
            if self._cform == "extent":
                _COND_EXTENT_ROWS.add(stream.num_rows_hint())
                passes = zeros
                if pairs:
                    # the build's column is the residual's last; the runs
                    # are the dense table's where this batch probed it
                    on_table = bool(dense) and bool(oks[i])
                    passes = self._cextent(
                        build, stream, counts, bstart, bperm, lows[-1],
                        lo_arr if on_table else np.int64(0), narrow[-1],
                        table if on_table else 0)
                yield self._semi(stream, passes)
                continue
            at = 1 + 2 * n_pk
            chars = [(sz[at + 2 * j], sz[at + 2 * j + 1])
                     for j in range(len(strs))]
            pair_cap = min(join_ops.COND_PIECE_PAIRS,
                           1 << max(3, (pairs - 1).bit_length()))
            if pairs + pair_cap >= 1 << 31:
                raise NotImplementedError(
                    f"a conditioned join of {pairs} pairs in one stream "
                    "batch (int32 slots)")
            pieces = -(-pairs // pair_cap)
            _COND_PIECES.add(pieces)
            self._cstate = {"pieces": pieces, "pair_cap": pair_cap}
            # chars of a piece's string columns the residual reads, its
            # stream sides then its build sides
            piece_caps = tuple(_char_bucket(min(t, pair_cap * m))
                               for m, t in chars)
            if not pieces:
                if not inner:
                    yield self._semi(stream, zeros)
                continue
            acc = zeros
            s_rows, b_words, incl = self._cprep(
                build, stream, bperm, counts, bstart, lows, narrow)
            if not inner:
                for k in range(pieces):
                    acc = self._cpiece(stream, s_rows, b_words, b_other,
                                       incl, acc,
                                       np.int32(k * pair_cap),
                                       np.int32(pairs), lows, narrow,
                                       pair_cap, piece_caps)
                yield self._semi(stream, acc)
                continue
            tot = [int(x) for x in totals[i]]
            n_str = sum(1 for d in stream.schema.dtypes if d.is_string)
            s_caps = tuple(_char_bucket(c) for c in tot[1:1 + n_str])
            b_caps = tuple(_char_bucket(c) for c in tot[1 + n_str:])
            for k in range(pieces):
                yield self._cpairs(build, stream, bperm, s_rows, b_words,
                                   b_other, incl,
                                   np.int32(k * pair_cap), np.int32(pairs),
                                   lows, narrow, pair_cap, piece_caps,
                                   s_caps, b_caps)


class TpuBroadcastHashJoinExec(TpuShuffledHashJoinExec):
    """Equi-join streaming against a broadcast build batch (reference:
    GpuBroadcastHashJoinExec, shims/spark300). The probe/expand machinery is
    TpuShuffledHashJoinExec's; the distinct class carries its own rule/conf
    key, like the reference's separate exec."""


class TpuCartesianProductExec(TpuShuffledHashJoinExec):
    """Unconditioned cross product (reference: GpuCartesianProductExec.scala,
    disabled by default there as well)."""

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan):
        super().__init__(left, right, "cross", [], [])

    def describe(self) -> str:
        return "TpuCartesianProductExec"


class TpuBroadcastNestedLoopJoinExec(PhysicalPlan):
    """Condition (non-equi) join: device cross product of each stream batch
    with the broadcast build batch, then one fused condition-filter kernel
    over the combined row (reference:
    execution/GpuBroadcastNestedLoopJoinExec.scala:258, inner/cross,
    disabled by default)."""

    columnar_output = True

    def __init__(self, left: PhysicalPlan, right: PhysicalPlan,
                 join_type: str, condition):
        super().__init__([left, right])
        assert join_type in ("inner", "cross"), join_type
        self.join_type = join_type
        self.condition = condition
        self._cross = TpuShuffledHashJoinExec(left, right, "cross", [], [])
        if condition is not None:
            from spark_rapids_tpu.ops import rowops
            from spark_rapids_tpu.sql.exprs.evalbridge import (
                make_context, to_device_column,
            )

            def fkernel(batch):
                ctx = make_context(batch)
                pred = to_device_column(ctx, condition.eval_device(ctx))
                keep = pred.data & pred.validity
                return rowops.filter_batch(batch, keep)
            from spark_rapids_tpu.utils.kernelcache import (
                cached_jit, expr_signature,
            )
            self._filter = cached_jit(
                "bnlj|" + expr_signature(condition),
                lambda: jax.jit(fkernel))
        else:
            self._filter = None

    def output_schema(self) -> Schema:
        return self._cross.output_schema()

    def describe(self) -> str:
        return f"TpuBroadcastNestedLoopJoinExec({self.join_type})"

    def partitions(self, ctx: ExecContext) -> List[Partition]:
        # keep the cross exec's children in sync with post-transition
        # children (TransitionOverrides rewrites self.children)
        self._cross.children = list(self.children)
        cross_parts = self._cross.partitions(ctx)

        def make(part: Partition) -> Partition:
            def run() -> Iterator[DeviceBatch]:
                for batch in part():
                    yield (self._filter(batch) if self._filter is not None
                           else batch)
            return run
        return [make(p) for p in cross_parts]
