"""Out-of-core join: a thin wrapper over the unified spill-tiered shuffle.

Reference analog: the byte-chunked streaming shuffle
(arrow/arrow_all_to_all.cpp:83-141) exists precisely so tables larger than
one node's memory can move through fixed-size buffers. This module used to
carry its own Grace-style spill rounds (bucket_pack + hand-sliced host
arenas + a private dag) that saw none of the chunked engine's header
fusion, byte budgets, lane packing or skew splitting. Per Exoshuffle
(PAPERS.md) — and ROADMAP item 2 — spill is POLICY of the one shuffle
composition, not a second engine, so the join is now three thin pieces
over ``parallel/spill.py``:

ingest
    Each host-staged chunk is uploaded, stamped with a rider sub-bucket
    lane (high murmur bits, the same family every shuffle uses — bucket
    assignment is consistent across chunks and across the two inputs),
    and pushed through the SAME ``_shuffle_many`` engine with a
    :class:`_BucketSink`: rows hash-route to their owner shard through
    the chunked, header-fused, budget-bounded rounds (inheriting lane
    packing and skew-adaptive splitting for free) and each received
    round streams into per-(bucket, shard) host arenas. Device footprint
    per chunk: the chunk plus the engine's bounded round buffers.
join
    After both streams drain, bucket b of the left joins bucket b of the
    right (equal hash => co-partitioned, and already shard-co-located by
    the ingest shuffle, so the bucket join's own exchange moves ~nothing).
    One-ahead staging + a bounded drain thread double-buffer the phase:
    at most two bucket pairs + two undrained results device-resident.
sink
    Results leave the device through the spill-aware lane fetch into ONE
    preallocated :class:`~cylon_tpu.parallel.spill.HostArena` sized from
    each result's already-known counts — no per-bucket host concat, and
    peak host bytes ride the ``shuffle.spill.host_bytes`` gauge.

Device memory is bounded by max(chunk + round buffers, one bucket pair +
its result), never by table size: with K buckets a table of N rows needs
~2N/K device rows at the join stage, so any table fits by raising K.
"""
from __future__ import annotations

import concurrent.futures
import threading
import time
from typing import Dict, Iterable, List, Optional

import jax.numpy as jnp
import numpy as np

from ..column import Column
from ..dtypes import DataType, Type
from ..engine import get_kernel
from ..fault import errors as _flt
from ..ops import partition as _p
from ..table import Table, _ShuffleSpec, _shuffle_many
from ..utils.tracing import bump, span
from . import spill as _spill

__all__ = ["OutOfCoreJoin", "HostSink"]

#: rider lane carrying each row's grace sub-bucket through the exchange
_SUBPART = "__cylon_subpart"


def _promote(a: np.dtype, b: np.dtype) -> np.dtype:
    """Common decoded dtype of two batches (object dominates — decoded
    dictionary values / nullable bools are object arrays)."""
    if a == np.dtype(object) or b == np.dtype(object):
        return np.dtype(object)
    return np.promote_types(a, b)


class _BucketSink:
    """Ingestion sink for one side: rows arrive from ``_shuffle_many``
    already hash-routed to their owner shard; this sink bins them by the
    rider sub-bucket lane into per-(bucket, shard) arenas. Values are
    stored DECODED (each chunk encodes its own dictionary, so logical
    values — not codes — are the stable host representation; bucket
    staging re-encodes and re-unifies)."""

    def __init__(self, k: int, world: int, backing: int) -> None:
        self.k = k
        self.world = world
        self.backing = backing
        self.arenas: Dict[tuple, _spill.HostArena] = {}
        self.names: Optional[List[str]] = None
        self.device_rows_peak = 0  # engine-reported ingest residency
        self.fetch_s = 0.0

    def accept(self, table, shard_cols, counts) -> None:
        t0 = time.perf_counter()
        names = table.column_names
        si = names.index(_SUBPART)
        keep = [ci for ci in range(len(names)) if ci != si]
        if self.names is None:
            self.names = [names[ci] for ci in keep]
        meta = [table._columns[n] for n in names]
        for s in range(self.world):
            n = int(counts[s])
            if not n or shard_cols[s] is None:
                continue
            cols = shard_cols[s]
            sub = np.asarray(cols[si][0][:n])
            order = np.argsort(sub, kind="stable")
            bc = np.bincount(sub, minlength=self.k)[: self.k]
            offs = np.concatenate([[0], np.cumsum(bc)]).astype(np.int64)
            decoded = [
                meta[ci].decode_host(
                    np.asarray(cols[ci][0][:n]),
                    None if cols[ci][1] is None else cols[ci][1][:n],
                )[order]
                for ci in keep
            ]
            for b in range(self.k):
                lo, hi = int(offs[b]), int(offs[b + 1])
                if hi <= lo:
                    continue
                arena = self.arenas.get((b, s))
                if arena is None:
                    arena = self.arenas[(b, s)] = _spill.HostArena(
                        [
                            (nm, d.dtype, False)
                            for nm, d in zip(self.names, decoded)
                        ],
                        backing=self.backing,
                    )
                batch = []
                for ci, d in enumerate(decoded):
                    want = _promote(arena.schema[ci][1], d.dtype)
                    arena.promote(ci, want)
                    batch.append((d[lo:hi].astype(want, copy=False), None))
                arena.append_batch(batch)
        self.fetch_s += time.perf_counter() - t0

    def bucket_shards(self, b: int):
        """Per-shard logical column dicts of bucket ``b`` (dtypes unified
        across shards), or None when the bucket is empty."""
        if self.names is None:
            return None
        got = [self.arenas.get((b, s)) for s in range(self.world)]
        total = sum(a.rows for a in got if a is not None)
        if total == 0:
            return None
        dtypes = []
        for ci in range(len(self.names)):
            dt = np.dtype(np.int8)
            first = True
            for a in got:
                if a is None:
                    continue
                dt = a.schema[ci][1] if first else _promote(dt, a.schema[ci][1])
                first = False
            dtypes.append(dt)
        shards = []
        for s in range(self.world):
            a = got[s]
            cols = a.columns() if a is not None else None
            od = {}
            for ci, nm in enumerate(self.names):
                if cols is None:
                    od[nm] = np.empty((0,), dtypes[ci])
                else:
                    od[nm] = cols[ci][0].astype(dtypes[ci], copy=False)
            shards.append(od)
        return shards

    def release(self, b: int) -> None:
        """Free bucket ``b``'s arenas as the join consumes them."""
        for s in range(self.world):
            a = self.arenas.pop((b, s), None)
            if a is not None:
                a.close()

    def close(self) -> None:
        for a in self.arenas.values():
            a.close()
        self.arenas.clear()


class HostSink:
    """Arena-backed result sink: every result chunk leaves the device
    through the spill-aware lane fetch into ONE preallocated host arena
    (``reserve`` sized from the result's already-known counts — the
    per-bucket host concat the old sink paid at ``result_pydict()`` is
    gone; reads are zero-copy views). ``RootOp.result()``-style device
    concat is deliberately unavailable."""

    def __init__(self, op_id: str = "host_sink", backing: int = _spill.TIER_HOST):
        self.rows = 0
        self.fetch_s = 0.0  # cost split: result device->host download wall
        self._backing = backing
        self._arena: Optional[_spill.HostArena] = None
        self._names: Optional[List[str]] = None

    def process(self, table: Table, edge: int = 0) -> None:
        t0 = time.perf_counter()
        counts = np.asarray(table.row_counts, np.int64)
        n = int(counts.sum())
        if n:
            if self._arena is not None:
                self._arena.reserve(n)
            _spill.stage_table(self, table, counts)
        self.rows += n
        self.fetch_s += time.perf_counter() - t0

    def accept(self, table, shard_cols, counts) -> None:
        """Spill-sink contract: decode each shard's physical rows and
        append shard-major (the same global order ``to_pydict`` yields)."""
        meta = [table._columns[n] for n in table.column_names]
        batches = []
        for s in range(len(counts)):
            n = int(counts[s])
            if not n or shard_cols[s] is None:
                continue
            cols = shard_cols[s]
            batches.append(
                [
                    meta[ci].decode_host(
                        np.asarray(d[:n]), None if v is None else v[:n]
                    )
                    for ci, (d, v) in enumerate(cols)
                ]
            )
        if not batches:
            return
        merged = [
            np.concatenate([b[ci] for b in batches])
            if len(batches) > 1
            else batches[0][ci]
            for ci in range(len(meta))
        ]
        if self._arena is None:
            self._names = table.column_names
            self._arena = _spill.HostArena(
                [(nm, m.dtype, False) for nm, m in zip(self._names, merged)],
                backing=self._backing,
            )
        out = []
        for ci, m in enumerate(merged):
            want = _promote(self._arena.schema[ci][1], m.dtype)
            self._arena.promote(ci, want)
            out.append((m.astype(want, copy=False), None))
        self._arena.append_batch(out)

    def result(self) -> Table:  # pragma: no cover - guard
        raise RuntimeError(
            "HostSink keeps results on the host; use result_pydict()"
        )

    def result_pydict(self) -> Dict[str, np.ndarray]:
        if self._arena is None:
            return {}
        # the result read-back rides the spill retry ladder (ISSUE 14):
        # a tier-2 EIO retries, then fails TYPED with the arena closed —
        # never a raw OSError with leaked arena bytes
        try:
            cols = _spill._retry_io("ooc result read", self._arena.columns)
        except _spill.SpillIOError:
            self.close()
            raise
        return {nm: col for nm, (col, _v) in zip(self._names, cols)}

    def close(self) -> None:
        if self._arena is not None:
            self._arena.close()
            self._arena = None


class OutOfCoreJoin:
    """Join two chunk streams whose totals exceed device capacity.

    ``execute(left_chunks, right_chunks)`` accepts iterables of host
    column-dicts (the host-staged chunk source); returns the HostSink. K
    buckets bound the device-resident bucket size to ~total/K rows. The
    partitioning, byte budgeting and (under skew) relay splitting all run
    through the unified ``_shuffle_many`` planner — this class owns only
    chunk ingestion and the result sink.
    """

    def __init__(self, ctx, on, how: str = "inner", num_buckets: int = 8,
                 byte_budget: Optional[int] = None, **join_kwargs):
        if how != "inner":
            # outer joins need null-extension for one-sided buckets, which
            # the skip-empty-bucket logic would silently drop
            raise NotImplementedError(
                "OutOfCoreJoin supports how='inner' only"
            )
        keys = on if isinstance(on, (list, tuple)) else [on]
        self.ctx = ctx
        self.on = on
        self.keys = list(keys)
        self.k = int(num_buckets)
        self.byte_budget = byte_budget
        self.join_kwargs = join_kwargs
        backing = (
            _spill.TIER_DISK
            if _spill.forced_tier() == _spill.TIER_DISK
            else _spill.TIER_HOST
        )
        world = ctx.world_size
        self.lp = _BucketSink(self.k, world, backing)
        self.rp = _BucketSink(self.k, world, backing)
        self.sink = HostSink(backing=backing)
        self._ingest_cap = 0   # chunk-upload residency (per shard rows)
        self._join_cap = 0     # bucket-join residency (per shard rows)
        self.stage_s = 0.0     # cost split: bucket staging (host->device)
        self.join_s = 0.0      # cost split: bucket join dispatch+sync wall
        self.drain_s = 0.0     # cost split: result download wall (drain thread)

    # -- ingestion -----------------------------------------------------
    def _with_subpart(self, t: Table) -> Table:
        """Stamp the grace sub-bucket lane: HIGH murmur bits (hash_shift)
        so the ingest shuffle's low-bit routing stays independent — the
        same split the old bucket_pack spill used, now riding the unified
        exchange as a plain int32 column."""
        kflat = tuple(t._key_hash_cols(self.keys))
        key = (
            "ooc_subpart",
            tuple(str(d.dtype) for d, _v in kflat),
            self.k,
        )
        k = self.k

        def build():
            def kern(dp, rep):
                (kc, counts) = dp
                n = counts[0]
                pid = _p.hash_partition_ids(
                    list(kc), n, k, hash_shift=16
                )
                # padding rows map to bucket k; clamp into range so the
                # host bincount stays dense (live counts gate the slices)
                return jnp.minimum(pid, k - 1).astype(jnp.int32)

            return kern

        pid = get_kernel(self.ctx, key, build)((kflat, t.counts_dev), ())
        return t.add_column(
            _SUBPART, Column(pid, DataType(Type.INT32), None, None)
        )

    def _ingest(self, sink: _BucketSink, chunk: Dict[str, np.ndarray]) -> None:
        t = Table.from_pydict(self.ctx, dict(chunk))
        if t.row_count == 0:
            return
        t2 = self._with_subpart(t)
        self._ingest_cap = max(self._ingest_cap, 2 * t2.shard_cap)
        if self.ctx.world_size == 1:
            # no mesh to route over: the chunk IS its own shard — stage it
            # straight into the sink through the same lane fetch
            _spill.stage_table(sink, t2, np.asarray(t2.row_counts))
            return
        spec = _ShuffleSpec(
            t2, "hash", tuple(self.keys),
            byte_budget=self.byte_budget, sink=sink,
        )
        _shuffle_many([spec])

    # -- bucket joins --------------------------------------------------
    def _bucket_table(self, bsink: _BucketSink, b: int) -> Optional[Table]:
        shards = bsink.bucket_shards(b)
        if shards is None:
            return None
        t0 = time.perf_counter()
        t = Table.from_shards(self.ctx, shards)
        self.stage_s += time.perf_counter() - t0
        return t

    def _stage_pair(self, b: int):
        """Upload bucket pair ``b``, or None if either side is empty
        (inner join of an empty side is empty)."""
        if b >= self.k:
            return None
        lt = self._bucket_table(self.lp, b)
        rt = self._bucket_table(self.rp, b)
        self.lp.release(b)
        self.rp.release(b)
        if lt is None or rt is None:
            return None
        return lt, rt

    def _join_buckets(self) -> None:
        # one-ahead staging + threaded result drain: pair b+1's device
        # uploads are dispatched BEFORE pair b's join blocks on its count
        # fetch, and result downloads run on a single drainer thread (jax
        # device_get is thread-safe) bounded by a 2-slot semaphore — both
        # transfers ride under the NEXT join's device work instead of
        # serializing with it (the overlap the old hand-built BucketJoinOp
        # measured as a ~100x ooc throughput cliff on remote-attached
        # devices). Device residency: TWO bucket pairs + at most TWO
        # undrained results — still ~total/K, just double-buffered.
        drain_slots = threading.Semaphore(2)
        fut_caps: List[tuple] = []
        ex = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="ooc_drain"
        )

        def drain(out):
            t0 = time.perf_counter()
            try:
                self.sink.process(out)
            finally:
                self.drain_s += time.perf_counter() - t0
                drain_slots.release()

        try:
            staged = self._stage_pair(0)
            for b in range(self.k):
                cur, staged = staged, self._stage_pair(b + 1)
                undrained = sum(c for f, c in fut_caps if not f.done())
                resident = sum(
                    t.shard_cap
                    for pair in (cur, staged) if pair for t in pair
                )
                if cur is None:
                    self._join_cap = max(
                        self._join_cap, resident + undrained
                    )
                    continue
                lt, rt = cur
                del cur
                t0 = time.perf_counter()
                out = lt.distributed_join(rt, on=self.on, **self.join_kwargs)
                self.join_s += time.perf_counter() - t0
                cap_out = out.shard_cap
                self._join_cap = max(
                    self._join_cap, resident + undrained + cap_out
                )
                del lt, rt
                drain_slots.acquire()  # bound undrained device results
                fut_caps.append((ex.submit(drain, out), cap_out))
                del out
        finally:
            # collect EVERY future before shutdown: raising on the first
            # failure would skip the rest and leak the drainer thread
            errs = []
            for f, _cap in fut_caps:
                try:
                    f.result()
                except Exception as e:  # noqa: BLE001 - re-raised below
                    errs.append(e)
            ex.shutdown(wait=True)
            if errs:
                raise errs[0]

    def execute(
        self,
        left_chunks: Iterable[Dict[str, np.ndarray]],
        right_chunks: Iterable[Dict[str, np.ndarray]],
    ) -> HostSink:
        li, ri = iter(left_chunks), iter(right_chunks)
        # stream: at most ONE chunk per source resident per quantum — the
        # host-staged source is pull-based, so the whole input is never
        # resident anywhere at once
        exhausted = [False, False]
        try:
            with span("shuffle.spill.ooc_ingest"):
                while not all(exhausted):
                    for i, (it, sink) in enumerate(
                        ((li, self.lp), (ri, self.rp))
                    ):
                        if exhausted[i]:
                            continue
                        try:
                            chunk = next(it)
                        except StopIteration:
                            exhausted[i] = True
                            continue
                        self._ingest(sink, chunk)
            bump("shuffle.spill.ooc_joins")
            with span("shuffle.spill.ooc_join"):
                self._join_buckets()
        except BaseException as e:
            # the failure-model invariant (cylon_tpu/fault): a failed
            # out-of-core join releases its RESULT arena too and leaves
            # as a typed, query-scoped error — the spill.read/write
            # seams on these caller-owned arenas have no in-line retry
            # ladder, so a raw OSError is typed here at the boundary
            self.sink.close()
            if isinstance(e, OSError) and not isinstance(e, _flt.CylonError):
                raise _spill.SpillIOError(
                    "out-of-core join spill I/O failed", e
                ) from e
            raise
        finally:
            # close on failure too: leaked arenas would pin tier-2 memmap
            # files and keep _ARENA_LIVE_BYTES inflated for later shuffles
            self.lp.close()
            self.rp.close()
        return self.sink

    # -- observability -------------------------------------------------
    @property
    def max_device_cap(self) -> int:
        """Largest per-shard device row residency any stage reached —
        the out-of-core guarantee is max_device_cap << total rows. The
        ingest term comes from the unified engine's own accounting
        (chunk + bounded round buffers + the <=2-round staging window)."""
        engine_peak = max(
            self.lp.device_rows_peak, self.rp.device_rows_peak
        )
        return max(self._ingest_cap + engine_peak, self._join_cap)

    @property
    def join_phase_device_cap(self) -> int:
        """Peak residency of the bucket-join phase alone — the ~total/K
        quantity num_buckets controls (ingest residency is chunk-sized
        and bucket-count-independent)."""
        return self._join_cap

    @property
    def cost_split(self) -> Dict[str, float]:
        """Per-phase wall seconds (which phases a slow host link inflates):
        spill_fetch covers the ingest-side device->host staging, stage
        the bucket re-uploads, join the bucket-join dispatch+sync, and
        drain_fetch the result downloads. Overlapped phases can sum past
        the end-to-end wall — each number is that phase's own clock."""
        return {
            "spill_fetch_s": round(self.lp.fetch_s + self.rp.fetch_s, 3),
            "stage_upload_s": round(self.stage_s, 3),
            "join_s": round(self.join_s, 3),
            "drain_fetch_s": round(self.sink.fetch_s, 3),
            "drain_thread_s": round(self.drain_s, 3),
        }
