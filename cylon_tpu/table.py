"""Table: the product — a distributed, device-resident relational table.

Reference analog: ``cylon::Table`` and its free-function op suite
(cpp/src/cylon/table.hpp:46-208 class; Join/DistributedJoin :258-270,
Union/Subtract/Intersect + Distributed* :279-330, Shuffle :339, HashPartition
:348, Sort :358, DistributedSort :394, Select :413, Project :423, Unique :433)
plus the pycylon Cython surface (python/pycylon/data/table.pyx).

TPU-native representation (SURVEY.md §7): a struct-of-columns of fixed-capacity
jax Arrays, row-sharded over the context mesh (PartitionSpec('dp')). Each of
the P shards owns ``shard_cap`` physical rows of every column, of which the
first ``row_counts[i]`` are live (front-packed); the rest are padding. All
relational kernels are static-shaped jit programs under shard_map; data-
dependent output sizes use a single dispatch with a static bound where one
exists (filter/set ops/unique/groupby; joins speculate, falling back to the
exact count->emit two-phase on overflow). Single-dispatch ops DEFER their
output-count fetch: the result Table carries the device count lane and the
host sync happens at result materialization (``_materialize_counts``), so an
eager op chain dispatches end-to-end with zero host syncs and ONE fetch at
the end — the dispatch-async discipline graft-lint's L3 sync budgets pin
(analysis/contracts.py SYNC_SITE_BUDGETS).

"Local" ops act independently per shard (== per MPI rank in the reference);
"distributed_*" ops are collective over the mesh.
"""
from __future__ import annotations

import contextlib
import numbers
import threading
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .column import Column, unify_dictionaries
from .context import CylonContext
from .dtypes import DataType, Type
from . import engine as _engine
from .engine import get_kernel, round_cap, shard_caps
from . import ordering as _ord
from .ordering import Ordering
from .ops import groupby as _g
from .ops import join as _j
from .ops import partition as _p
from .ops import setops as _s
from .ops import gather as _g_pack
from .ops import quant as _quant
from .ops import sketch as _sketch
from .ops import sort as _sort_mod
from .ops import stats as _st
from .fault import errors as _fault_errors
from .parallel import shuffle as _sh
from .parallel import spill as _spill
from .parallel import topo as _topo
from .obs import prof as _prof
from .obs import resource as _obsres
from .obs import stages as _stages
from .obs import store as _obsstore
from .obs import trace as _obstrace
from .plan import feedback as _feedback
from .utils.tracing import annotate_add, bump, gauge, span

KeyCol = Tuple[jax.Array, Optional[jax.Array]]

import operator as _op
import time as _time

from .utils import envgate as _eg


def _speculative_join() -> bool:
    """Single-dispatch speculative join gate (see Table.join);
    CYLON_TPU_EXACT_JOIN=1 forces the exact two-phase count->emit path.
    Read per call (not at import) so a mid-process flip takes effect: the
    two paths dispatch under distinct key suffixes ('spec' vs
    'probe'/'emit'), so the flip can never alias compiled programs."""
    # lint: key=CYLON_TPU_EXACT_JOIN -- dispatch-path selection between
    # distinctly-keyed programs (see envgate.EXACT_JOIN.keyed_via)
    return _eg.EXACT_JOIN.get() != "1"


def _bump_ride(cols: Sequence[KeyCol]) -> None:
    """Count, at dispatch, what rides the sort that orders ``cols``
    (:func:`ops.sort.ride_census`): ``sort.ride_lanes`` (rows= the 32-bit
    lanes) and ``sort.ride_batches`` (rows= the sorts that carry them; 1
    when they fit one)."""
    lanes, batches = _sort_mod.ride_census(
        [a.dtype for a in _sort_mod.flatten_cols(cols)]
    )
    bump("sort.ride_lanes", rows=lanes)
    bump("sort.ride_batches", rows=batches)


def _bump_pack_ride(plan_sig, wire, n_pt: int) -> None:
    """The same count at a pack dispatch, over the lane plan: the int32
    lanes (the wire plan's words when there is one) and the float64
    passthrough columns that ride the pack's sort by destination
    (:func:`parallel.shuffle.pack_by_sort`): ``shuffle.pack.ride_lanes``
    and ``shuffle.pack.ride_batches``."""
    n_lanes = (
        wire.n_words if wire is not None
        else sum(nl + bool(hv) for _tag, nl, hv in plan_sig)
    )
    lanes, batches = _sort_mod.ride_census(
        [np.int32] * n_lanes + [np.float64] * n_pt
    )
    bump("shuffle.pack.ride_lanes", rows=lanes)
    bump("shuffle.pack.ride_batches", rows=batches)


#: what an aggregate without keys is refused with on the other path
_KEYLESS_OPS = (
    "an aggregate without keys takes sum, count, min, max and mean "
    "(the dense path's ops); group by a key for any other"
)


def _agg_specs(agg) -> List[Tuple[str, int, str]]:
    """An ``agg`` mapping (value column -> op or ops) as the flat list of
    ``(column, op id, op name)`` the group-by kernels take."""
    specs: List[Tuple[str, int, str]] = []
    for col, ops in agg.items():
        ops_list = ops if isinstance(ops, (list, tuple)) else [ops]
        for o in ops_list:
            oid = _g.agg_op_id(o)
            specs.append((col, oid, o if isinstance(o, str) else _agg_name(oid)))
    return specs


class _PartialAgg(NamedTuple):
    """A group-by's aggregates as partial state (``ops.groupby.
    partial_states``): what :meth:`Table.distributed_groupby` has each
    shard reduce its rows to, ship, and finish the aggregates from."""

    specs: tuple   # the caller's (column, op id, op name)
    ops: tuple     # the same as (op id, position in the distinct columns)
    states: tuple  # (op id, position, widen) a state column
    reads: tuple   # the state columns each of ``ops`` is finished from

    @property
    def columns(self) -> list:
        """The distinct value columns, in the caller's order."""
        return list(dict.fromkeys(c for c, _o, _n in self.specs))

    @classmethod
    def of(cls, table: "Table", specs) -> "_PartialAgg":
        names = cls(tuple(specs), (), (), ()).columns
        ops = tuple((oid, names.index(c)) for c, oid, _n in specs)
        wide = [
            table._columns[c].data.dtype == _sort_mod.wide_float()
            for c in names
        ]
        states, reads = _g.partial_states(ops, wide)
        return cls(tuple(specs), ops, states, reads)

    def state_specs(self) -> list:
        """The pre-combine's ``(column, op id, name)``: a state column is
        named ``<column>_<op>``, a widened sum ``<column>_fsum``."""
        names = self.columns
        return [
            (names[j], op, ("f" if widen else "") + _agg_name(op))
            for op, j, widen in self.states
        ]

    def merge_specs(self) -> list:
        """The combine's ``(state column, op id, name)`` over the partial
        table that :meth:`state_specs` names."""
        columns = [f"{c}_{n}" for c, _o, n in self.state_specs()]
        return [
            (columns[i], op, _agg_name(op))
            for op, i in _g.combine_ops(self.states)
        ]

    def pre_combine(self, table, key_names, mask=None, _sorted=False):
        """``table``'s own rows, shard by shard, as one partial row a group
        (stage ``groupby.partial``)."""
        return table._groupby_segment(
            key_names, self.state_specs(), _sorted=_sorted,
            stage=_stages.GROUPBY_PARTIAL,
            widen=[widen for _op, _j, widen in self.states], mask=mask,
        )

    def combine(self, partials, key_names):
        """The partial rows each shard holds (after their exchange: every
        row of its groups) as the caller's aggregates (stage
        ``groupby.merge``)."""
        return partials._groupby_segment(
            key_names, self.merge_specs(), stage=_stages.GROUPBY_MERGE,
            merge=self,
        )


def _scalar(x) -> jax.Array:
    """Per-shard [1] arrays carry scalars through shard_map."""
    return x.reshape(1) if hasattr(x, "reshape") else jnp.asarray([x])


@jax.jit
def _as_i32(x):
    """Dtype-normalize a deferred count lane on device (no host sync)."""
    return x.astype(jnp.int32)


def _fetch(arr, site: str) -> np.ndarray:
    """Device->host fetch that works under multi-process ``jax.distributed``:
    a global array's remote shards are not addressable from this host, so
    ``np.asarray`` alone would raise — allgather across processes first
    (the reference's equivalent host boundary is each rank owning only its
    partition, table.cpp:791-829).

    The ONE place a fetch is counted, timed and named. ``site`` is a
    literal of ``obs.stages.FETCH_SITES``: a count sync bumps the census
    counter ``host_sync``; every fetch is the rollup span
    ``host_sync.<site>`` (the host's wait, with a histogram a site), a
    line of the public call's record, and inside a ``jax.profiler``
    session a host event of that name on the device trace's clock
    (``obs.trace.fetch_wait``)."""
    if _stages.FETCH_SITES[site]:
        bump("host_sync")
    with _obstrace.fetch_wait(site):
        if jax.process_count() > 1 and hasattr(arr, "is_fully_addressable"):
            if not arr.is_fully_addressable:
                from jax.experimental import multihost_utils

                return np.asarray(
                    multihost_utils.process_allgather(arr, tiled=True)
                )
        return np.asarray(arr)


class Row:
    """Read-only cursor over one table row — the reference's ``cylon::Row``
    (cpp/src/cylon/row.hpp:24-52), used by the row-UDF Select path
    (:meth:`Table.select_rows`). Values are decoded host values (strings are
    strings, nulls are None)."""

    __slots__ = ("_cols", "_i")

    def __init__(self, cols: Dict[str, np.ndarray], i: int):
        self._cols = cols
        self._i = i

    def __getitem__(self, name: str):
        return self._cols[name][self._i]

    def get(self, name: str):
        return self._cols[name][self._i]

    def keys(self):
        return self._cols.keys()

    @property
    def row_index(self) -> int:
        return self._i


def _dict_insert(dic: np.ndarray, value) -> Tuple[np.ndarray, int, bool]:
    """Insert ``value`` into a sorted dictionary, WIDENING the unicode dtype
    first — np.insert into a '<U1' array would silently truncate a longer
    value. Returns (dictionary, code position, whether an insert happened)."""
    pos = int(np.searchsorted(dic, value))
    if pos < len(dic) and dic[pos] == value:
        return dic, pos, False
    wide = np.result_type(dic.dtype, np.asarray([value]).dtype)
    return np.insert(dic.astype(wide), pos, value), pos, True


def _host_col_like(
    table: "Table",
    phys: np.ndarray,
    valid: Optional[np.ndarray],
    dtype: DataType,
    dictionary: Optional[np.ndarray],
) -> Column:
    """Stage a host column (live-row order, one value per live row) into a
    device Column matching ``table``'s padded per-shard layout."""
    world, cap = table.world_size, table._shard_cap
    counts = table._row_counts
    offs = np.concatenate([[0], np.cumsum(counts)])
    block = np.zeros((world, cap), phys.dtype)
    vblock = None if valid is None else np.ones((world, cap), bool)
    for i in range(world):
        c = int(counts[i])
        block[i, :c] = phys[offs[i] : offs[i] + c]
        if vblock is not None:
            vblock[i, :c] = valid[offs[i] : offs[i] + c]
    data_dev = jax.device_put(block.reshape(-1), table.ctx.sharding)
    valid_dev = (
        None if vblock is None else jax.device_put(vblock.reshape(-1), table.ctx.sharding)
    )
    return Column(data_dev, dtype, valid_dev, dictionary)


@jax.jit
def _minmax_kernel(d, ok, big, small):
    """Both bounds in one program: XLA fuses the two masked reductions into a
    single pass and the result pair comes back in one host fetch."""
    return jnp.stack(
        [
            jnp.min(jnp.where(ok, d, big)),
            jnp.max(jnp.where(ok, d, small)),
        ]
    )


class Table:
    """See module docstring. Construct via the ``from_*`` factories."""

    def __init__(
        self,
        ctx: CylonContext,
        columns: "OrderedDict[str, Column]",
        row_counts: np.ndarray,
        shard_cap: int,
        index_name: Optional[str] = None,
        ordering: Optional[Ordering] = None,
    ):
        self.ctx = ctx
        self._columns: "OrderedDict[str, Column]" = columns
        # row_counts may be a HOST array (known counts) or a DEVICE [P]
        # per-shard count lane still in flight: single-dispatch eager ops
        # (filter/groupby/set-ops/unique/fused join+sum) hand their count
        # output straight through, DEFERRING the device->host sync to
        # result materialization (_materialize_counts) — the dispatch-
        # async property the graft-lint L3 sync budgets pin (filter/
        # project/groupby = 0 host syncs at dispatch time).
        self._counts_fut = None
        self._counts_host = None
        self._mat_lock = None
        if isinstance(row_counts, jax.Array):
            self._counts_fut = row_counts
            self._mat_lock = threading.Lock()
        else:
            self._counts_host = np.asarray(row_counts, np.int64)
        self._shard_cap = int(shard_cap)
        self._counts_dev = None
        # sortedness metadata (cylon_tpu/ordering.py): None unless an op
        # that provably establishes order attached a validated descriptor —
        # the conservative default, so a missed propagation is only a
        # missed optimization
        self._ordering = _ord.validate(ordering, columns.keys())
        # column range stats (ops/stats.py): name -> ColStat bounds of the
        # orderable encoding over live rows. Same conservative default as
        # ordering: empty unless a kernel that touched the data attached
        # bounds (shuffle count pass, ensure_stats) — a missed propagation
        # only costs a lane-packing opportunity, never correctness
        self._stats: Dict[str, "_st.ColStat"] = {}
        # pandas-style index: None == RangeIndex; else the named column is
        # the index (reference Set_Index/ResetIndex, table.hpp + indexing/)
        self.index_name = index_name if index_name in (columns.keys() | {None}) else None
        # resource ledger: register this table's device buffers (weakref
        # finalizer observes the free). One enabled() check when no ops
        # surface is on; never a sync — nbytes is a shape property
        # (graft-lint pins obs.resource.note_table at 0 sync sites)
        _obsres.note_table(self)

    # ------------------------------------------------------------------
    # basic properties
    # ------------------------------------------------------------------
    @property
    def column_names(self) -> List[str]:
        return list(self._columns.keys())

    @property
    def column_count(self) -> int:
        return len(self._columns)

    @property
    def row_count(self) -> int:
        return int(self._row_counts.sum())

    @property
    def shape(self) -> Tuple[int, int]:
        return (self.row_count, self.column_count)

    @property
    def shard_cap(self) -> int:
        return self._shard_cap

    @property
    def row_counts(self) -> np.ndarray:
        return self._row_counts

    # -- deferred-count plumbing (the L3 sync-freedom refactor) --------
    @property
    def _row_counts(self) -> np.ndarray:
        """Host per-shard live-row counts; materializes a deferred count
        lane on first access (THE one host sync of a dispatched chain)."""
        if self._counts_host is None:
            self._materialize_counts()
        return self._counts_host

    @_row_counts.setter
    def _row_counts(self, value) -> None:
        self._counts_host = np.asarray(value, np.int64)
        self._counts_fut = None
        self._counts_dev = None

    @property
    def _counts_raw(self):
        """Counts WITHOUT forcing materialization: the host array when
        known, else the in-flight device lane. Pass this (never
        ``_row_counts``) when handing counts to a new Table so a deferred
        chain stays sync-free."""
        return self._counts_host if self._counts_host is not None else self._counts_fut

    def _rows_hint(self) -> Optional[int]:
        """``row_count`` when already host-known, else None. Tracing spans
        use this so observability never forces the materialization sync."""
        return (
            None if self._counts_host is None else int(self._counts_host.sum())
        )

    def _materialize(self) -> "Table":
        """Force the deferred count fetch (no-op when counts are known)."""
        if self._counts_host is None:
            self._materialize_counts()
        return self

    def _materialize_counts(self) -> None:
        """THE deferred device->host sync of the dispatch-async eager ops:
        fetch the per-shard count lane recorded at dispatch time, then
        apply the overshoot compaction the op would have applied eagerly
        (round the capacity down when the static bound overshot the
        realized max shard count by >= 4x — the ``_maybe_compact``
        policy, applied in place so every holder of this handle sees the
        compacted buffers)."""
        with self._mat_lock:
            if self._counts_host is not None:
                return  # lost the race: the other thread materialized
            got = _fetch(
                self._counts_fut, "table.counts"
            ).reshape(-1).astype(np.int64)
            tight = round_cap(int(got.max()) if got.size else 0)
            if tight * 4 <= self._shard_cap:
                compacted = self._compact(tight)
                self._columns = compacted._columns
                self._shard_cap = compacted._shard_cap
                self._counts_dev = None
                # the in-place buffer swap must re-register with the
                # resource ledger: the old buffers are dead, and the
                # wrapper's finalizer must not steal the live ones
                _obsres.note_rebuffer(self)
            # publish LAST: the lock-free fast paths (_row_counts /
            # _materialize / _rows_hint) key on _counts_host, so it must
            # never be visible while the in-place compaction is still
            # swapping _columns/_shard_cap — and _counts_fut is cleared
            # only after, so _counts_raw never observes both None
            self._counts_host = got
            self._counts_fut = None
            # deferred span-end resolution rides THIS fetch: stamp the
            # device-resolved end time of any trace pending on this
            # result and feed the fingerprint latency histogram — zero
            # additional syncs (obs.trace.resolve_table owns a 0-site
            # budget in analysis/contracts.py)
            _obstrace.resolve_table(self)

    @property
    def world_size(self) -> int:
        return self.ctx.world_size

    def __len__(self) -> int:
        return self.row_count

    @property
    def ordering(self) -> Optional[Ordering]:
        """The table's order property (sortedness descriptor) or None —
        see :mod:`cylon_tpu.ordering` for the exact semantics. Set by ops
        that provably establish order (``sort``/``distributed_sort``,
        ``groupby``, the key-order join emit, ...), carried by
        row-subset/rename ops, dropped by anything that reroutes rows."""
        return self._ordering

    def with_ordering(self, ordering: Optional[Ordering]) -> "Table":
        """Explicitly (re)declare this table's order property — validated
        against the schema; the caller vouches for the actual sortedness
        (the ``pipeline_groupby`` contract generalized)."""
        t = self._replace()
        t._ordering = _ord.validate(ordering, self._columns.keys())
        return t

    def _attach_ordering(self, ordering: Optional[Ordering]) -> "Table":
        """Internal propagation: attach if still valid for this schema,
        silently drop otherwise (never raise on a lapsed descriptor)."""
        if ordering is not None and all(
            k in self._columns for k in ordering.keys
        ):
            self._ordering = ordering
        return self

    @property
    def column_stats(self) -> Dict[str, "_st.ColStat"]:
        """The table's known column range stats (ops/stats.py): name ->
        conservative [lo, hi] bounds of the column's orderable encoding
        over live rows. May be empty — use :meth:`ensure_stats` to
        measure on demand."""
        return dict(self._stats)

    def _attach_stats(
        self, stats: Optional[Dict[str, "_st.ColStat"]],
        rename: Optional[Dict[str, str]] = None,
    ) -> "Table":
        """Internal propagation: carry conservative range bounds onto this
        table for every column that still exists with the same encoding
        class (row-subset/permutation/rename ops — bounds stay sound).
        Never raises; a lapsed entry is silently dropped."""
        if not stats:
            return self
        out = {}
        for name, stat in stats.items():
            if stat is None:
                continue
            name = (rename or {}).get(name, name)
            col = self._columns.get(name)
            if col is None:
                continue
            if _st.enc_class(col.data.dtype) != stat.cls:
                continue
            out[name] = stat
        if out:
            self._stats = {**self._stats, **out}
        return self

    def _fusion_specs(
        self, names: Sequence[str], ascending: Optional[Sequence[bool]] = None
    ) -> Optional[list]:
        """Per-key ``(enc_class, field_bits, has_valid, ascending)`` specs
        for :func:`cylon_tpu.ops.sort.plan_lane_fusion`, or None when any
        key lacks measurable stats — the ONE copy of the
        ensure_stats -> spec sequence shared by sort and groupby (the join
        builds its own from the pair's MERGED stats)."""
        stats = self.ensure_stats(names)
        specs = []
        for i, kn in enumerate(names):
            stat = stats.get(kn)
            if stat is None:
                return None
            specs.append((
                stat.cls, _st.field_bits(stat),
                self._columns[kn].valid is not None,
                bool(ascending[i]) if ascending is not None else True,
            ))
        return specs or None

    def ensure_stats(
        self, names: Sequence[str]
    ) -> Dict[str, Optional["_st.ColStat"]]:
        """Column range stats for ``names``, measured on demand and cached
        on this table (the ``Ordering``-style descriptor lifecycle: cleared
        by in-place mutation, absent on fresh handles). Columns with no
        packable encoding (f64, 64-bit without X64) map to None. One cheap
        elementwise kernel + one tiny fetch covers every missing column;
        tables that came through a shuffle already carry bounds (the count
        pass measured them) and pay nothing here. Returns {} when the
        CYLON_TPU_NO_LANE_PACK kill switch is on."""
        # lint: key=CYLON_TPU_NO_LANE_PACK -- the gate short-circuits BEFORE
        # any kernel dispatch (no stats kernel runs at all when off); the
        # stats kernel body itself is gate-independent, and every consumer
        # keys its derived fuse/wire plan (None when stats are absent)
        if not _st.enabled():
            return {}
        out: Dict[str, Optional["_st.ColStat"]] = {}
        missing = []
        for n in names:
            col = self._columns[n]
            cls = _st.enc_class(col.data.dtype)
            if cls is None:
                out[n] = None
                continue
            got = self._stats.get(n)
            if got is not None and got.cls == cls:
                out[n] = got
            else:
                missing.append((n, cls))
        if missing:
            flat = tuple(
                (self._columns[n].data, self._columns[n].valid)
                for n, _c in missing
            )
            key = ("col_stats", tuple(str(d.dtype) for d, _v in flat))

            def build():
                def kern(dp, rep):
                    (cols, counts) = dp
                    n0 = counts[0]
                    return jnp.concatenate(
                        [_st.stat_words(c, n0) for c in cols]
                    )

                return kern

            with span("stats.measure", rows=self._rows_hint()):
                got = get_kernel(self.ctx, key, build)(
                    (flat, self.counts_dev), ()
                )
                bump("lane_pack.stats_kernel")
                w = _fetch(got, "stats.measure").reshape(
                    self.world_size, len(missing), 4
                )
            for i, (n, cls) in enumerate(missing):
                stat = _st.fold_stat_words(w[:, i, :], cls)
                self._stats[n] = stat
                out[n] = stat
        return out

    def column(self, name: str) -> Column:
        return self._columns[name]

    def dtype_of(self, name: str) -> DataType:
        return self._columns[name].dtype

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------
    @classmethod
    def from_encoded_shards(
        cls,
        ctx: CylonContext,
        shards: Sequence[Optional["OrderedDict[str, Tuple]"]],
        counts: Optional[np.ndarray] = None,
    ) -> "Table":
        """Per-shard ingest with NO global host buffer: ``shards[i]`` maps
        column name -> (physical data, valid, dtype, sorted dictionary) for
        shard i's rows. Each shard's padded block is staged to its own device
        (``jax.make_array_from_single_device_arrays``), so peak host memory
        is O(one shard), not O(global table) — the analog of each MPI rank
        reading only its partition (reference table.cpp:791-829).

        Under multi-host ``jax.distributed``, entries for non-addressable
        devices may be None; ``counts`` (global, [world]) is then required.
        Dictionaries must already be unified across shards
        (see :func:`unify_encoded_shards`).
        """
        world = ctx.world_size
        if len(shards) != world:
            raise ValueError(f"need {world} shards, got {len(shards)}")
        devices = list(ctx.mesh.devices.flat)
        local = [i for i, d in enumerate(devices) if d.process_index == jax.process_index()]
        if counts is None:
            if any(shards[i] is None for i in local):
                raise ValueError("counts required when local shard data is absent")
            counts = np.zeros(world, np.int64)
            for i in local:
                s = shards[i]
                counts[i] = len(next(iter(s.values()))[0]) if s else 0
            if len(local) != world:
                raise ValueError("counts (global) required under multi-host")
        counts = np.asarray(counts, np.int64)
        cap = round_cap(int(counts.max()) if world else 0)
        ref = next(shards[i] for i in local if shards[i] is not None)
        names = list(ref.keys())
        cols: "OrderedDict[str, Column]" = OrderedDict()
        for name in names:
            dtype = ref[name][2]
            dictionary = ref[name][3]
            if len(local) == world:
                # single-host: cheap data-dependent choices are safe
                phys_dt = np.result_type(
                    *[shards[i][name][0].dtype for i in local if shards[i] is not None]
                )
                has_valid = any(
                    shards[i][name][1] is not None for i in local if shards[i] is not None
                )
            else:
                # multi-host: every process must make IDENTICAL choices or the
                # global-array construction diverges across hosts (hang /
                # dtype mismatch), so derive both from the declared DataType,
                # never from this host's local data
                phys_dt = dtype.physical_dtype
                has_valid = True
            blocks, vblocks = [], []
            for i in local:
                phys, valid, dt, _dic = shards[i][name]
                if dt.type != dtype.type:
                    raise ValueError(
                        f"shard dtype mismatch for {name!r}: {dt} vs {dtype}"
                    )
                if len(phys) != counts[i]:
                    raise ValueError("column lengths disagree with counts")
                block = np.zeros((cap,), dtype=phys_dt)
                block[: len(phys)] = phys
                blocks.append(jax.device_put(block, devices[i]))
                # drop the host block immediately AND wait for the transfer:
                # device_put is async and holds the source buffer alive, so
                # without the barrier several staging blocks coexist and the
                # O(one shard) peak-host-memory guarantee silently degrades
                blocks[-1].block_until_ready()
                del block
                if has_valid:
                    vb = np.ones((cap,), bool)
                    if valid is not None:
                        vb[: len(valid)] = valid
                    vblocks.append(jax.device_put(vb, devices[i]))
                    vblocks[-1].block_until_ready()
                    del vb
            data_dev = jax.make_array_from_single_device_arrays(
                (world * cap,), ctx.sharding, blocks
            )
            valid_dev = (
                jax.make_array_from_single_device_arrays(
                    (world * cap,), ctx.sharding, vblocks
                )
                if has_valid
                else None
            )
            cols[name] = Column(data_dev, dtype, valid_dev, dictionary)
        return cls(ctx, cols, counts, cap)

    @classmethod
    def from_encoded(
        cls,
        ctx: CylonContext,
        encoded: Dict[str, Tuple[np.ndarray, Optional[np.ndarray], DataType, Optional[np.ndarray]]],
        counts: Optional[np.ndarray] = None,
    ) -> "Table":
        """Build a table from already-encoded host columns
        (physical data, valid, dtype, sorted dictionary) — the direct ingest
        path for the native CSV codec. ``counts=None`` splits rows evenly;
        otherwise row blocks of sizes ``counts[i]`` go to shard i. Delegates
        to :meth:`from_encoded_shards` via zero-copy slices."""
        world = ctx.world_size
        n = len(next(iter(encoded.values()))[0]) if encoded else 0
        for name, (phys, *_rest) in encoded.items():
            if len(phys) != n:
                raise ValueError("all columns must have equal length")
        if counts is None:
            counts, _cap = shard_caps(n, world)
        else:
            counts = np.asarray(counts, np.int64)
            if len(counts) != world or counts.sum() != n:
                raise ValueError("bad shard counts")
        offs = np.concatenate([[0], np.cumsum(counts)])
        shards = []
        for i in range(world):
            lo, hi = int(offs[i]), int(offs[i + 1])
            shards.append(
                OrderedDict(
                    (
                        name,
                        (
                            phys[lo:hi],
                            None if valid is None else valid[lo:hi],
                            dtype,
                            dictionary,
                        ),
                    )
                    for name, (phys, valid, dtype, dictionary) in encoded.items()
                )
            )
        return cls.from_encoded_shards(ctx, shards, counts=counts)

    @classmethod
    def from_pydict(cls, ctx: CylonContext, data: Dict[str, Any]) -> "Table":
        """Build a row-sharded table from host columnar data (dict of
        name -> array-like). Mirrors pycylon ``Table.from_pydict``
        (data/table.pyx:768-909)."""
        arrays = {k: np.asarray(v) if not isinstance(v, np.ndarray) else v for k, v in data.items()}
        n = len(next(iter(arrays.values()))) if arrays else 0
        for k, v in arrays.items():
            if len(v) != n:
                raise ValueError("all columns must have equal length")
        encoded = OrderedDict(
            (name, Column.encode_host(np.asarray(values)))
            for name, values in arrays.items()
        )
        return cls.from_encoded(ctx, encoded)

    @classmethod
    def from_pandas(cls, ctx: CylonContext, df) -> "Table":
        return cls.from_pydict(ctx, {str(c): df[c].to_numpy() for c in df.columns})

    @classmethod
    def from_numpy(cls, ctx: CylonContext, names: Sequence[str], arrays) -> "Table":
        return cls.from_pydict(ctx, dict(zip(names, arrays)))

    @classmethod
    def from_list(
        cls, ctx: CylonContext, names: Sequence[str], data_list: Sequence
    ) -> "Table":
        """Column-per-list construction (reference pycylon Table.from_list,
        data/table.pyx:829). Values re-infer their encoding like pydict."""
        return cls.from_pydict(
            ctx,
            {
                n: np.asarray(col, dtype=object)
                if any(isinstance(v, str) for v in col)
                else np.asarray(col)
                for n, col in zip(names, data_list)
            },
        )

    @classmethod
    def from_arrow(cls, ctx: CylonContext, atable) -> "Table":
        """From a pyarrow.Table, typed (reference Table::FromArrowTable,
        table.hpp:67; arrow_builder.cpp raw-buffer ingest analog): dictionary
        arrays keep their codes (remapped onto a sorted dictionary), integer
        columns with nulls stay integral (no pandas float64 bounce), validity
        bitmaps become the mask column."""
        encoded = OrderedDict(
            (name, _encode_arrow_array(atable.column(name)))
            for name in atable.column_names
        )
        return cls.from_encoded(ctx, encoded)

    @classmethod
    def from_shards(cls, ctx: CylonContext, shards: Sequence[Dict[str, Any]]) -> "Table":
        """Per-shard construction: shard i's rows come from ``shards[i]`` —
        the analog of each MPI rank loading its own ``csv1_{RANK}.csv``
        (reference cpp/test/join_test.cpp:21-24). Each shard is encoded
        independently (O(shard) peak host memory), then dictionaries are
        unified across shards by remapping codes."""
        world = ctx.world_size
        if len(shards) != world:
            raise ValueError(f"need {world} shards, got {len(shards)}")
        names = list(shards[0].keys())
        enc_shards = []
        for s in shards:
            enc_shards.append(
                OrderedDict(
                    (name, Column.encode_host(np.asarray(s[name]))) for name in names
                )
            )
        unify_encoded_shards(enc_shards)
        return cls.from_encoded_shards(ctx, enc_shards)

    def _replace(self, columns=None, row_counts=None, shard_cap=None) -> "Table":
        # _counts_raw, not _row_counts: replacing columns/metadata on a
        # deferred-count handle must not force the materialization sync
        return Table(
            self.ctx,
            self._columns if columns is None else columns,
            self._counts_raw if row_counts is None else row_counts,
            self._shard_cap if shard_cap is None else shard_cap,
            index_name=self.index_name,
        )

    # ------------------------------------------------------------------
    # host conversion
    # ------------------------------------------------------------------
    def _host_physical(self, name: str):
        """Concatenated live rows of a column in physical encoding:
        (data ndarray, valid ndarray | None)."""
        col = self._columns[name]
        world, cap = self.ctx.world_size, self._shard_cap
        data = _fetch(col.data, "to_numpy").reshape(world, cap)
        valid = (
            None if col.valid is None
            else _fetch(col.valid, "to_numpy.valid").reshape(world, cap)
        )
        parts, vparts = [], []
        for i in range(world):
            c = int(self._row_counts[i])
            parts.append(data[i, :c])
            if valid is not None:
                vparts.append(valid[i, :c])
        data_np = np.concatenate(parts) if parts else np.empty((0,), data.dtype)
        valid_np = np.concatenate(vparts) if valid is not None else None
        return data_np, valid_np

    def _host_physical_shard(self, name: str, shard: int):
        """One shard's live rows in physical encoding, fetched WITHOUT
        gathering the global array (per-rank IO path: only shard ``shard``'s
        device buffer crosses to the host)."""
        col = self._columns[name]
        cap = self._shard_cap
        c = int(self._row_counts[shard])

        def block_of(arr):
            for s in arr.addressable_shards:
                start = s.index[0].start if s.index[0].start is not None else 0
                if start == shard * cap:
                    return np.asarray(s.data)
            raise ValueError(f"shard {shard} not addressable from this host")

        data = block_of(col.data)[:c]
        valid = None if col.valid is None else block_of(col.valid)[:c]
        return data, valid

    def _host_column(self, name: str):
        data_np, valid_np = self._host_physical(name)
        return self._columns[name].decode_host(data_np, valid_np)

    def to_pydict(self) -> Dict[str, np.ndarray]:
        return {name: self._host_column(name) for name in self.column_names}

    def to_pandas(self):
        import pandas as pd

        return pd.DataFrame(self.to_pydict())

    def to_numpy(self, order: str = "F") -> np.ndarray:
        cols = [np.asarray(v, dtype=np.float64 if v.dtype == object else None)
                for v in self.to_pydict().values()]
        return np.stack(cols, axis=1) if cols else np.empty((0, 0))

    def to_arrow(self, shard: Optional[int] = None):
        """Typed pyarrow.Table (no pandas bounce): dictionary columns export
        as pa.DictionaryArray (codes + dictionary), validity masks as null
        bitmaps, integers stay integral. ``shard=i`` exports only shard i's
        rows, fetched without a global gather (per-rank IO)."""
        import pyarrow as pa

        arrays, names = [], []
        for name in self.column_names:
            col = self._columns[name]
            if shard is None:
                data, valid = self._host_physical(name)
            else:
                data, valid = self._host_physical_shard(name, shard)
            mask = None if valid is None else ~valid
            if col.dtype.is_dictionary:
                codes = pa.array(np.asarray(data, np.int32), mask=mask)
                arr = pa.DictionaryArray.from_arrays(
                    codes, pa.array(col.dictionary.astype(object))
                )
            elif col.dtype.type == Type.TIMESTAMP:
                arr = pa.array(data.astype("datetime64[ns]"), mask=mask)
            elif col.dtype.type == Type.DURATION:
                arr = pa.array(data.astype("timedelta64[ns]"), mask=mask)
            else:
                arr = pa.array(data, mask=mask)
            arrays.append(arr)
            names.append(name)
        return pa.Table.from_arrays(arrays, names=names)

    def __repr__(self):
        head = self.to_pandas()
        return f"cylon_tpu.Table[{self.row_count} rows x {self.column_count} cols, P={self.world_size}]\n{head}"

    # ------------------------------------------------------------------
    # kernel plumbing
    # ------------------------------------------------------------------
    @property
    def counts_dev(self) -> jax.Array:
        if self._counts_dev is None:
            fut = self._counts_fut
            if fut is not None:
                # deferred counts already live on the device: feed them
                # straight into the next kernel — device->device, NO sync
                self._counts_dev = (
                    fut if fut.dtype == jnp.int32 else _as_i32(fut)
                )
            else:
                self._counts_dev = jax.device_put(
                    self._row_counts.astype(np.int32), self.ctx.sharding
                )
        return self._counts_dev

    def _flat_cols(self, names: Optional[Sequence[str]] = None) -> List[KeyCol]:
        names = self.column_names if names is None else names
        return [(self._columns[n].data, self._columns[n].valid) for n in names]

    def _rebuild_cols(
        self, names: Sequence[str], flat, row_counts, cap, dicts: Optional[Dict[str, np.ndarray]] = None
    ) -> "Table":
        """Reassemble a Table from kernel output (data, valid) pairs keeping
        dtype/dictionary metadata of the named source columns."""
        cols: "OrderedDict[str, Column]" = OrderedDict()
        for (out_name, src_col), (data, valid) in zip(names, flat):
            dic = (dicts or {}).get(out_name, src_col.dictionary)
            cols[out_name] = Column(data, src_col.dtype, valid, dic)
        # row-subset ops (filter/sort/unique/loc) keep the index; ops that
        # rename it away (join suffixes) drop it, like pandas
        idx = self.index_name if self.index_name in cols else None
        return Table(self.ctx, cols, row_counts, cap, index_name=idx)

    def _maybe_compact(self, counts: np.ndarray, factor: int = 4) -> "Table":
        """Single-sourced overshoot policy: slice the physical capacity down
        when the speculative/static cap exceeded the realized max shard count
        by >= ``factor`` (one cheap jitted slice, no host sync)."""
        tight = round_cap(int(counts.max()))
        if tight * factor <= self._shard_cap:
            return self._compact(tight)
        return self

    def _compact(self, new_cap: int) -> "Table":
        """Slice every column's physical buffer down to ``new_cap`` rows per
        shard (all live rows must fit). One cheap jitted slice, no host sync."""
        if new_cap >= self._shard_cap:
            return self
        flat = self._flat_cols()
        key = ("compact", len(flat))

        def build():
            def kern(dp, rep):
                (cols,) = dp
                (dummy,) = rep
                co = dummy.shape[0]
                return [
                    (d[:co], None if v is None else v[:co]) for d, v in cols
                ]

            return kern

        out = get_kernel(self.ctx, key, build)(
            (flat,), (jnp.zeros((new_cap,), jnp.int8),)
        )
        return self._rebuild_cols(
            list(zip(self.column_names, self._columns.values())),
            out,
            self._counts_raw,
            new_cap,
        )

    # ------------------------------------------------------------------
    # column-level ops (no shard_map needed: elementwise / global reduce)
    # ------------------------------------------------------------------
    def project(self, columns: Sequence[Union[str, int]]) -> "Table":
        """Reference Project (table.cpp:831-850)."""
        names = [self.column_names[c] if isinstance(c, int) else c for c in columns]
        cols = OrderedDict((n, self._columns[n]) for n in names)
        # rows untouched: sortedness survives on the longest key prefix kept
        return self._replace(columns=cols)._attach_ordering(
            _ord.truncate_to(self._ordering, names)
        )._attach_stats(self._stats)

    def rename(self, mapping: Union[Dict[str, str], Sequence[str]]) -> "Table":
        if isinstance(mapping, dict):
            new_names = [mapping.get(n, n) for n in self.column_names]
        else:
            new_names = list(mapping)
        cols = OrderedDict(zip(new_names, self._columns.values()))
        ren = dict(zip(self.column_names, new_names))
        return self._replace(columns=cols)._attach_ordering(
            _ord.rename(self._ordering, ren)
        )._attach_stats(self._stats, rename=ren)

    def drop(self, columns: Sequence[str]) -> "Table":
        drop = set(columns)
        cols = OrderedDict((n, c) for n, c in self._columns.items() if n not in drop)
        return self._replace(columns=cols)._attach_ordering(
            _ord.truncate_to(self._ordering, cols.keys())
        )._attach_stats(self._stats)

    def add_prefix(self, prefix: str) -> "Table":
        """Prefix every column name (reference table.pyx:1943-1970).
        A pure rename — no host/device movement; a set index follows its
        renamed column."""
        out = self.rename([prefix + n for n in self.column_names])
        if self.index_name is not None:
            out.index_name = prefix + self.index_name
        return out

    def add_suffix(self, suffix: str) -> "Table":
        """Suffix every column name (reference table.pyx:1972-2000)."""
        out = self.rename([n + suffix for n in self.column_names])
        if self.index_name is not None:
            out.index_name = self.index_name + suffix
        return out

    def to_string(self, row_limit: int = 10) -> str:
        """Head/tail string render with an elision row past ``row_limit``
        rows (reference table.pyx:1660-1690). Elision is delegated to
        pandas' ``max_rows`` renderer rather than slicing rendered text
        lines: wide frames wrap into multiple column blocks, and a line
        slice would cut mid-block and drop later blocks entirely."""
        df = self.to_pandas()
        if self.row_count <= row_limit:
            return df.to_string()
        return df.to_string(max_rows=max(2 * (row_limit // 2), 2)) + "\n"

    def show(self, row1: int = -1, row2: int = -1, col1: int = -1, col2: int = -1) -> None:
        """Print the table, optionally a [row1:row2, col1:col2] window
        (reference table.pyx:115-128 / C++ Table::Print)."""
        if (row1, row2, col1, col2) == (-1, -1, -1, -1):
            print(self.to_pandas().to_string())
            return
        df = self.to_pandas()
        r1 = 0 if row1 == -1 else row1
        r2 = len(df) if row2 == -1 else row2
        c1 = 0 if col1 == -1 else col1
        c2 = df.shape[1] if col2 == -1 else col2
        print(df.iloc[r1:r2, c1:c2].to_string())

    def dropna(self, axis: int = 0, how: str = "any", inplace: bool = False) -> "Table":
        """Method form of compute.drop_na (reference table.pyx:2144-2216).

        NOTE the reference's Table.dropna axis convention is inverted vs
        pandas: axis=0 drops COLUMNS with nulls, axis=1 drops ROWS (see the
        table.pyx docstring examples). compute.drop_na uses the pandas
        convention, so the method flips the axis before delegating.
        """
        from . import compute as _compute

        if axis not in (0, 1):
            raise ValueError("axis must be 0 or 1")
        out = _compute.drop_na(self, how=how, axis=1 - axis)
        if inplace:
            self._columns = out._columns
            self._row_counts = out._row_counts
            self._shard_cap = out._shard_cap
            self._counts_dev = None
            self._ordering = out._ordering
            self._stats = dict(out._stats)
            # direct mutation bypasses __init__'s dangling-index check and
            # any cached loc index built on the pre-drop rows
            if self.index_name not in self._columns:
                self.index_name = None
            self._built_index = None
            return self
        return out

    def isin(self, values, skip_null: bool = True) -> "Table":
        """Method form of compute.is_in (reference table.pyx:2218-2220)."""
        from . import compute as _compute

        return _compute.is_in(self, values, skip_null=skip_null)

    def add_column(self, name: str, col: Union[Column, np.ndarray, jax.Array]) -> "Table":
        if not isinstance(col, Column):
            raise TypeError("add_column expects a Column; use from_pydict for host data")
        cols = OrderedDict(self._columns)
        cols[name] = col
        return self._replace(columns=cols)

    def _global_rowid_column(self) -> Column:
        """int32 column: each live row's GLOBAL index in table order (shard
        offsets + local position; padding values are don't-care). Carried
        through a shuffle it lets order-sensitive ops (unique keep=first/
        last) recover original order, which multi-round exchanges do not
        preserve. Global ids are int32; the static bound shard_cap * shards
        caps every possible id, so exceeding int32 raises here instead of
        silently wrapping (which would pick the wrong duplicate in
        distributed_unique keep='first'/'last')."""
        cap = self._shard_cap
        counts = self.counts_dev  # [P] sharded
        if cap * self.world_size > 2**31 - 1:
            raise ValueError(
                f"global row ids exceed int32 range (shard_cap={cap} x "
                f"{self.world_size} shards); order-sensitive distributed ops "
                "(unique keep='first'/'last') are limited to 2^31-1 global rows"
            )

        def f(counts):
            offs = jnp.cumsum(counts) - counts
            return (
                offs[:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
            ).reshape(-1).astype(jnp.int32)

        return Column(
            jax.jit(f)(counts), DataType.from_numpy_dtype(np.dtype(np.int32))
        )

    def live_mask(self) -> jax.Array:
        """Public [P*cap] bool device mask of live rows (False = padding).

        The ML-handoff companion of ``Column.data``: when feeding the sharded
        column buffers straight into a jitted model (see
        examples/etl_logreg.py), use this as the sample-weight mask so
        padding rows contribute zero. Same sharding as the columns."""
        return self._live_mask()

    def _live_mask(self) -> jax.Array:
        """Global [P*cap] bool mask of live rows."""
        cap = self._shard_cap
        counts = self.counts_dev  # [P] sharded

        def f(counts):
            return (jnp.arange(cap, dtype=jnp.int32)[None, :] < counts[:, None]).reshape(-1)

        return jax.jit(f)(counts)

    # ------------------------------------------------------------------
    # filtering / row selection
    # ------------------------------------------------------------------
    def _as_mask(self, mask) -> jax.Array:
        """Normalize a Table / Column / array boolean row mask to a [P*cap]
        device bool array (null mask entries count as False, like pandas)."""
        if isinstance(mask, Table):
            mask = next(iter(mask._columns.values()))
        if isinstance(mask, Column):
            m = mask.data
            if mask.valid is not None:
                m = m & mask.valid
            return m
        if isinstance(mask, (list, tuple)):
            mask = np.asarray(mask, bool)
        if isinstance(mask, np.ndarray):
            # host-order mask over live rows -> physical padded layout
            world, cap = self.world_size, self._shard_cap
            full = np.zeros((world, cap), bool)
            offs = np.concatenate([[0], np.cumsum(self._row_counts)])
            for i in range(world):
                full[i, : int(self._row_counts[i])] = mask[offs[i] : offs[i + 1]]
            return jax.device_put(full.reshape(-1), self.ctx.sharding)
        return mask

    @_obstrace.op("filter")
    def filter(self, mask: Union["Table", Column, jax.Array]) -> "Table":
        """Keep rows where mask is True. The vectorized analog of the
        reference's UDF Select (table.cpp:504-529) and of pycylon's boolean
        __getitem__ (data/table.pyx:1066-1223).

        **Capacity of the result.** The table's own: the rows kept are a
        subset, so ``shard_cap`` is an exact upper bound known without a
        count, and the filter is one dispatch with no fetch (the sync
        budget of a filter is 0). The count rides the result on the device;
        whoever first asks for it pays the fetch, and the result is then
        sliced down where the capacity overshot the rows four times or
        more (``_materialize_counts``). So a filter that keeps little still
        compacts every column over the whole capacity, and what follows
        runs at that capacity until a count is fetched. The planner avoids
        both where it can: a filter under an aggregate rides it as a row
        mask (``filter_as_mask``), one under an inner join rides the join
        (``join_mask``; ``Table.join`` has the rule for what follows), and
        in neither case does this method run."""
        m = self._as_mask(mask)
        names = self.column_names
        flat = self._flat_cols()
        # Single-dispatch, sync-free: the output is a subset of the input
        # rows, so cap_out = shard_cap is a static exact upper bound (the
        # set-op/groupby design) — no count phase, no fetch at all; the
        # count lane rides the result and materializes on first access,
        # compacting the overshoot then (L3 sync budget: filter = 0).
        cap_out = self._shard_cap
        key = ("filter", len(flat), "fused")

        def build_emit():
            def kern(dp, rep):
                (m, cols, counts) = dp
                n = counts[0]
                cap = m.shape[0]
                live = jnp.arange(cap, dtype=jnp.int32) < n
                idx, total = _s.compact_mask(m & live, cap)
                out, _ = _g_pack.pack_gather(list(cols), idx)
                return out, _scalar(total)

            return kern

        out, nout = get_kernel(self.ctx, key, build_emit)(
            (m, flat, self.counts_dev), ()
        )
        # a row-subset in input order: the sortedness descriptor survives
        # (and range bounds stay conservative over any subset)
        return self._rebuild_cols(
            list(zip(names, self._columns.values())), out, nout, cap_out
        )._attach_ordering(self._ordering)._attach_stats(self._stats)

    def _with_arrays(self, arrays: Dict[str, KeyCol]) -> "Table":
        """This table with one column a ``name -> (data, valid | None)``
        entry, arrays in the columns' padded layout (the planner's computed
        projection), a name that exists replaced in place. Rows untouched:
        ordering and stats survive on the columns that were not replaced."""
        cols = OrderedDict(self._columns)
        for n, (d, v) in arrays.items():
            cols[n] = Column(d, DataType.from_numpy_dtype(d.dtype), v, None)
        kept = [n for n in self.column_names if n not in arrays]
        res = self._replace(columns=cols)
        res._counts_dev = self.counts_dev  # the rows are the same
        return res._attach_ordering(
            _ord.truncate_to(self._ordering, kept)
        )._attach_stats({n: self._stats.get(n) for n in kept})

    def select(self, predicate) -> "Table":
        """Row filter by a vectorized predicate over a dict of column arrays.
        (Reference Select takes a row UDF, table.cpp:504-529; here the
        predicate is jit-compiled over whole columns — TPU-native.)"""
        env = {n: self._columns[n].data for n in self.column_names}
        mask = predicate(env)
        return self.filter(mask)

    def select_rows(self, predicate) -> "Table":
        """Row filter by an arbitrary Python row UDF — the reference's exact
        Select capability (table.cpp:504-529 with a ``Row`` cursor,
        row.hpp:24-52). The UDF receives a :class:`Row` per live row and runs
        on the HOST (decoded values), so this is the escape hatch for
        predicates that cannot be vectorized; prefer :meth:`select`."""
        host = self.to_pydict()
        n = self.row_count
        mask = np.fromiter(
            (bool(predicate(Row(host, i))) for i in range(n)), bool, count=n
        )
        return self.filter(mask)

    def take(self, indices: np.ndarray) -> "Table":
        """Gather rows by global (live-row-order) indices — a real device
        gather (reference copy_array_by_indices, util/copy_arrray.cpp), not a
        pandas round-trip. Cross-shard reads become XLA-inserted collectives;
        output rows are re-split evenly."""
        world, cap_in = self.world_size, self._shard_cap
        idx = np.asarray(indices, np.int64).reshape(-1)
        n_total = self.row_count
        idx = np.where(idx < 0, idx + n_total, idx)
        if len(idx) and (idx.min() < 0 or idx.max() >= n_total):
            raise IndexError("take index out of range")
        counts = self._row_counts
        if world == 1 or (
            len(counts) and counts.max() == counts.min() and counts[0] > 0
        ):
            # uniform shards: a global index is already per-shard local
            # (shard = idx // c, offset = idx - shard * c) — skip the host
            # searchsorted over the shard offsets (O(n log P) per call on
            # the hot iloc/limit path)
            c = max(int(counts[0]), 1) if world > 1 else max(n_total, 1)
            src_shard = idx // c
            phys = (src_shard * cap_in + (idx - src_shard * c)).astype(
                np.int32
            )
        else:
            offs = np.concatenate([[0], np.cumsum(counts)])
            src_shard = np.searchsorted(offs[1:], idx, side="right")
            phys = (src_shard * cap_in + (idx - offs[src_shard])).astype(np.int32)
        counts, cap_out = shard_caps(len(idx), world)
        full = np.zeros(world * cap_out, np.int32)
        o = np.concatenate([[0], np.cumsum(counts)])
        for i in range(world):
            full[i * cap_out : i * cap_out + counts[i]] = phys[o[i] : o[i + 1]]
        idx_dev = jax.device_put(full, self.ctx.sharding)
        # one cached jitted gather per context (a fresh jax.jit each call
        # would retrace + recompile every take()); published under the
        # context cache lock like every other _jit_cache entry
        cache = self.ctx.__dict__.setdefault("_jit_cache", {})
        gather = cache.get(("take_gather",))
        if gather is None:
            with _engine.cache_lock(self.ctx):
                gather = cache.get(("take_gather",))
                if gather is None:
                    gather = _engine.TimedProgram(
                        jax.jit(
                            lambda d, i: d[i],
                            out_shardings=self.ctx.sharding,
                        ),
                        "take_gather",
                    )
                    cache[("take_gather",)] = gather
        cols: "OrderedDict[str, Column]" = OrderedDict()
        for n, c in self._columns.items():
            d = gather(c.data, idx_dev)
            v = None if c.valid is None else gather(c.valid, idx_dev)
            cols[n] = Column(d, c.dtype, v, c.dictionary)
        return Table(self.ctx, cols, counts, cap_out, index_name=self.index_name)

    # ------------------------------------------------------------------
    # sort
    # ------------------------------------------------------------------
    @_obstrace.op("sort")
    def sort(
        self,
        order_by: Union[str, int, Sequence[Union[str, int]]],
        ascending: Union[bool, Sequence[bool]] = True,
    ) -> "Table":
        """Per-shard sort (reference local Sort, table.cpp:291-328).

        Order-property reuse (cylon_tpu/ordering.py): when the table's
        ordering descriptor already guarantees the full requested spec
        identity-exactly, the sort is a no-op; when it guarantees a proper
        mask-free key PREFIX, only the suffix keys are sorted — the prefix
        collapses into a single run-id lane (ops.sort.prefix_run_lane),
        eliding one chained sort pass per prefix lane."""
        names = self._resolve_cols(order_by)
        asc = self._resolve_asc(ascending, len(names))
        all_names = self.column_names
        key_idx = tuple(all_names.index(n) for n in names)

        m = _ord.matches_sort_spec(self._ordering, names, asc)
        if m == len(names):
            bump("ordering.sort_elided")
            # a fresh handle, not `self`: in-place mutation of the "sorted
            # result" must never write through to the source table
            return self._replace()._attach_ordering(self._ordering)
        # the suffix path needs mask-free prefix columns: run adjacency and
        # run ORDER must agree with the lexsort comparator, which orders
        # null-key rows by their masked payload (ordering.py module doc)
        use_prefix = 0 < m < len(names) and all(
            self._columns[n].valid is None for n in names[:m]
        )
        if not use_prefix:
            m = 0

        flat = self._flat_cols()
        # bit-width-adaptive sort-word fusion (ops/stats.py + ops/sort.py):
        # measured key ranges bit-pack the suffix key lanes (+ null flags,
        # prefix run lane and padding class) into the fewest physical sort
        # words — a 3-key lexsort whose keys fit 12+16+20 bits runs as ONE
        # fused pass. The QUANTIZED plan (never the raw bounds) is part of
        # the kernel cache key; CYLON_TPU_NO_LANE_PACK=1 disables.
        fuse = None
        if _st.enabled():
            specs = self._fusion_specs(names[m:], asc[m:])
            if specs:
                fuse = _sort_mod.plan_lane_fusion(
                    specs, pad_bits=2,
                    prefix_bits=(
                        (self._shard_cap + 1).bit_length() if m else 0
                    ),
                    allow64=bool(jax.config.jax_enable_x64),
                )
        key = ("sort", key_idx, asc, len(flat), m, fuse)

        def build():
            def kern(dp, rep):
                (cols, counts) = dp
                n = counts[0]
                cap = cols[0][0].shape[0]
                keys = [cols[i] for i in key_idx[m:]]
                with jax.named_scope(_stages.SORT_KEYS):
                    prefix_lane = (
                        _sort_mod.prefix_run_lane(
                            [cols[i] for i in key_idx[:m]], n, cap
                        )
                        if m
                        else None
                    )
                # every column (data and validity lanes, 64-bit ones as
                # their two 32-bit halves) RIDES the sort as a payload, and
                # no order is carried to gather by: 17 ms where sort and
                # gathers took 90 (sort-w1; PERF.md section 6, PR 30)
                return _sort_mod.unflatten_cols(
                    cols,
                    _sort_mod.lexsort_rows_payload(
                        keys, n, cap, _sort_mod.flatten_cols(cols),
                        ascending=list(asc[m:]), prefix_lane=prefix_lane,
                        fuse=fuse,
                    ),
                )

            return kern

        if m:
            bump("ordering.sort_suffix")
        if fuse is not None:
            bump("lane_pack.sort_fused",
                 rows=fuse.n_plain - fuse.n_words)
        _bump_ride(flat)
        with span("sort", rows=self._rows_hint()):
            out = get_kernel(self.ctx, key, build)(
                (flat, self.counts_dev), ()
            )
        # a sort permutes rows within each shard: counts are unchanged, so
        # a deferred count lane passes straight through (no forced sync)
        res = self._rebuild_cols(
            list(zip(all_names, self._columns.values())), out,
            self._counts_raw, self._shard_cap,
        )._attach_stats(self._stats)
        mask_free = all(self._columns[n].valid is None for n in names)
        return res._attach_ordering(Ordering(
            keys=tuple(names), ascending=asc, nulls_last=True, scope="shard",
            canonical=mask_free and all(asc), lexsort_exact=True,
        ))

    @_obstrace.op("distributed_sort")
    def distributed_sort(
        self,
        order_by: Union[str, int, Sequence[Union[str, int]]],
        ascending: Union[bool, Sequence[bool]] = True,
        num_bins: int = 0,
        num_samples: int = 0,
    ) -> "Table":
        """Global sample-sort (reference DistributedSort, table.cpp:338-382):
        range-partition on the primary key over the mesh, shuffle, then local
        sort. ``num_bins``/``num_samples`` mirror SortOptions
        (table.hpp:388-393); 0 = defaults."""
        names = self._resolve_cols(order_by)
        asc = self._resolve_asc(ascending, len(names))
        if (
            self._ordering is not None
            and self._ordering.scope == "global"
            and _ord.matches_sort_spec(self._ordering, names, asc)
            == len(names)
        ):
            # provably already in the requested global order: the re-sort
            # would reproduce this content in this order (possibly on a
            # different shard split — the only unobservable difference).
            # Fresh handle, same buffers (mutation isolation, like sort)
            bump("ordering.dist_sort_elided")
            return self._replace()._attach_ordering(self._ordering)
        if self.world_size == 1:
            return self.sort(order_by, ascending)
        shuffled = self._shuffle_impl(
            kind="range", key_names=[names[0]], asc0=asc[0], num_bins=num_bins
        )
        res = shuffled.sort(order_by, ascending)
        if res._ordering is not None:
            # range partition on the primary key + full local sort: shard
            # i's rows all precede shard i+1's (equal primary keys share a
            # bin), upgrading the descriptor to global scope
            res._ordering = res._ordering._replace(scope="global")
        return res

    @_obstrace.op("topk")
    def topk(
        self,
        order_by: Union[str, int, Sequence[Union[str, int]]],
        n: int,
        ascending: Union[bool, Sequence[bool]] = True,
    ) -> "Table":
        """The first ``n`` rows of ``distributed_sort(order_by, ascending)``
        (all of them where the table has fewer), in that order: what
        ``sort`` then ``head(n)`` gives, nulls last and ties in row order,
        without sorting the table. Only the key lanes and a row position
        are ordered (stage ``sort.topk``); no other column rides the sort
        and ``n`` rows are gathered by the first ``n`` positions. The
        result's capacity is ``round_cap(n)`` and its count stays on the
        device: nothing is fetched (on a mesh each shard keeps its own
        first ``n``, and those at most ``world * n`` rows take the sort
        and limit that were there, which fetch their counts)."""
        names = self._resolve_cols(order_by)
        asc = self._resolve_asc(ascending, len(names))
        n = int(n)
        if n < 0:
            raise ValueError("topk needs n >= 0")
        res = self._topk_local(names, asc, n)
        if self.world_size == 1:
            return res
        res = res.distributed_sort(order_by, ascending)
        return res.take(
            np.arange(min(n, res.row_count), dtype=np.int64)
        )

    def _topk_local(self, names, asc, n: int) -> "Table":
        """Each shard's own first ``n`` rows by ``names`` (one program, no
        fetch): the whole of :meth:`topk` on one device."""
        all_names = self.column_names
        key_idx = tuple(all_names.index(c) for c in names)
        flat = self._flat_cols()
        cap_out = round_cap(n)
        key = ("topk", key_idx, asc, len(flat))

        def build():
            def kern(dp, rep):
                (cols, counts) = dp
                (dummy, limit) = rep
                k = dummy.shape[0]
                live = counts[0]
                cap = cols[0][0].shape[0]
                with jax.named_scope(_stages.SORT_TOPK):
                    order = _sort_mod.lexsort_rows(
                        [cols[i] for i in key_idx], live, cap, list(asc)
                    )
                    if k <= cap:
                        order = order[:k]
                    else:
                        order = jnp.concatenate(
                            [order, jnp.full((k - cap,), -1, jnp.int32)]
                        )
                    kept = jnp.minimum(live, limit).astype(jnp.int32)
                    idx = jnp.where(
                        jnp.arange(k, dtype=jnp.int32) < kept, order, -1
                    )
                    out, _ = _g_pack.pack_gather(
                        list(cols), idx, all_valid=True
                    )
                    return out, _scalar(kept)

            return kern

        with span("sort.topk", rows=self._rows_hint()):
            out, kept = get_kernel(self.ctx, key, build)(
                (flat, self.counts_dev),
                (jnp.zeros((cap_out,), jnp.int8), np.int32(n)),
            )
        return self._rebuild_cols(
            list(zip(all_names, self._columns.values())), out, kept, cap_out
        )

    # ------------------------------------------------------------------
    # shuffle (the distributed backbone)
    # ------------------------------------------------------------------
    def shuffle(
        self,
        hash_columns: Sequence[Union[str, int]],
        byte_budget: Optional[int] = None,
    ) -> "Table":
        """Reference Shuffle (table.cpp:910-921): hash-partition on the given
        columns to world_size partitions + the chunked all-to-all.
        ``byte_budget`` caps the per-round exchange buffer (default: the
        context's ``shuffle_byte_budget``); smaller budgets trade one big
        padded exchange for more bounded-size rounds."""
        names = self._resolve_cols(hash_columns)
        if self.world_size == 1:
            return self
        return self._shuffle_impl(
            kind="hash", key_names=names, byte_budget=byte_budget
        )

    def _key_hash_cols(self, key_names: Sequence[str]) -> List[KeyCol]:
        """Key columns for HASH partitioning, with dictionary columns replaced
        by their value-hash lane (ops/hash.py hash_dictionary_host): equal
        strings route identically no matter which table/chunk encoded them."""
        from .ops.hash import hash_dictionary_host

        out: List[KeyCol] = []
        for n in key_names:
            c = self._columns[n]
            if c.dtype.is_dictionary:
                hh = jnp.asarray(hash_dictionary_host(c.dictionary))
                lane = hh[jnp.clip(c.data, 0, len(c.dictionary) - 1)]
                out.append((lane, c.valid))
            else:
                out.append((c.data, c.valid))
        return out

    def _shuffle_impl(
        self,
        kind: str,
        key_names: Sequence[str],
        asc0: bool = True,
        num_bins: int = 0,
        task_map: Optional[np.ndarray] = None,
        byte_budget: Optional[int] = None,
    ) -> "Table":
        """hash/range partition -> chunked header-fused exchange -> compact
        (SURVEY.md §7 stage 5; reference shuffle_table_by_hashing
        table.cpp:135-157 / MapToSortPartitions partition.cpp:168-198).
        The round scheduler lives in :func:`_shuffle_many`; ``byte_budget``
        overrides the context's per-round exchange budget."""
        return _shuffle_many(
            [
                _ShuffleSpec(
                    self, kind, tuple(key_names), asc0, num_bins, task_map,
                    byte_budget,
                )
            ]
        )[0]

    def task_partition(
        self, hash_columns: Sequence[Union[str, int]], plan
    ) -> Dict[int, "Table"]:
        """Task-based all-to-all (reference ArrowTaskAllToAll /
        LogicalTaskPlan, arrow/arrow_task_all_to_all.h:23-40): hash rows into
        the plan's logical tasks and shuffle each task to its owning worker.
        Returns {task_id: Table}."""
        from .parallel.task import task_partition as _tp

        return _tp(self, hash_columns, plan)

    def hash_partition(self, hash_columns: Sequence[Union[str, int]], num_partitions: int) -> Dict[int, "Table"]:
        """Local hash partition into k tables (reference HashPartition,
        table.cpp:384-405). Not a hot path; built on filter()."""
        names = self._resolve_cols(hash_columns)
        flat = tuple(self._key_hash_cols(names))
        key = ("hash_partition", tuple(names), num_partitions)

        def build():
            def kern(dp, rep):
                (cols, counts) = dp
                n = counts[0]
                return _p.hash_partition_ids(cols, n, num_partitions)

            return kern

        pid = get_kernel(self.ctx, key, build)((flat, self.counts_dev), ())
        out = {}
        for p in range(num_partitions):
            out[p] = self.filter(pid == p)
        return out

    # ------------------------------------------------------------------
    # join
    # ------------------------------------------------------------------
    @_obstrace.op("join")
    def join(
        self,
        other: "Table",
        on: Optional[Union[str, Sequence[str]]] = None,
        how: str = "inner",
        left_on: Optional[Sequence[str]] = None,
        right_on: Optional[Sequence[str]] = None,
        suffixes: Tuple[str, str] = ("_x", "_y"),
        algorithm: str = "sort",
        config: Optional["object"] = None,
        emit_order: str = "left",
        _left_mask: Optional[jax.Array] = None,
        _right_mask: Optional[jax.Array] = None,
        _totals: Optional[np.ndarray] = None,
    ) -> "Table":
        """Per-shard (local) equi-join — the reference's 4 types (Join,
        table.cpp:428-480; join/hash_join.cpp + sort_join.cpp) and two it
        lacks, **semi** and **anti**.

        ``how``: 'inner', 'left', 'right', 'outer' ('fullouter',
        'full_outer') emit both sides' columns, suffixed on a name clash.
        'semi' / 'anti' ('left_semi' / 'left_anti') are EXISTS / NOT
        EXISTS: this table's rows that have (have no) partner in
        ``other`` on the keys. Their output keeps this table's columns
        under their own names (no suffix: nothing of ``other`` comes out),
        each row at most once however many partners it has, in this
        table's row order, with its ordering descriptor and column stats
        as a filter keeps them. A null key has no partner on either side:
        semi drops the row, anti keeps it (NOT EXISTS, not NOT IN). They
        read ``other``'s key columns alone and move no payload before the
        rows are known: one keys-only program, one fetch (the kept
        count), one compaction at ``round_cap`` of it
        (:meth:`_semi_join`); ``emit_order='key'`` and
        ``algorithm='pallas_pk'`` do not apply to them.

        **Capacity of the result.** A join emits into a capacity it must
        choose before it knows its row count. By default it speculates:
        ``round_cap(max(cap_l, cap_r))`` slots a shard (the sum for a full
        outer join), which holds every join of about one match a key, and
        the count it fetches afterwards slices the result down where it
        overshot four times or more (``_maybe_compact``). That is right
        when most rows find a partner and wasteful when few do: the sorts
        and the emit's gathers then run over the padded inputs for rows
        that die. So an INNER join that a filter rides as a row mask
        (``_left_mask`` / ``_right_mask``, the planner's ``join_mask``
        rewrite: the one sign of selectivity the join is given) reduces
        first (:meth:`_semi_reduced`): one keys-only program finds the
        rows of either side that have a live partner and the exact output
        count, the host fetches the three counts, each side is cut to
        ``round_cap`` of its rows with a partner, and the join proper
        runs on those at ``round_cap`` of the exact count. Every capacity
        after the keys-only program is then under twice the rows that live
        in it. A join without a mask never reduces, whatever an earlier
        join of its signature counted. Where the two sides' positions do
        not fit the keys-only program's word (``ops.join.semi_capable``)
        the masks are applied as filters first (``Table.filter``'s
        capacity) and the join speculates as any other.

        ``algorithm``: 'sort' and 'hash' both execute the sort/searchsorted
        join (SURVEY.md §7: argsort is native, hash multimaps are not —
        accepted for reference JoinConfig parity); 'pallas_pk' selects the
        bucketed Pallas PK-FK probe (single null-free <=32-bit integer key,
        inner only; speculative — duplicate right keys or bucket overflow
        silently rerun the exact sort join). ``config`` takes a JoinConfig
        object (reference join_config.hpp:33-189) and must then be the ONLY
        join argument.

        ``emit_order``: 'left' (default) emits output rows in left-row
        order (pandas merge order); 'key' (INNER/LEFT only) emits them
        GROUPED BY the join key straight out of the probe's kv-sort — same
        kernel cost — and stamps the output's ordering descriptor so a
        downstream groupby/sort on the key skips its own lexsort (the
        planner's ``order_reuse`` rewrite lowers to this). Best-effort: a
        speculative-capacity overflow falls back to left order with no
        descriptor, never a wrong answer.

        Order-property reuse on inputs: a right table whose ordering
        descriptor proves it canonically sorted by the join key skips the
        probe's right-side ride sort entirely."""
        if config is not None:
            if (
                on is not None or left_on is not None or right_on is not None
                or how != "inner" or suffixes != ("_x", "_y")
                or algorithm != "sort" or emit_order != "left"
            ):
                raise ValueError(
                    "pass either config= or explicit join arguments, not both"
                )
            return self.join(other, **config.kwargs())
        if emit_order not in ("left", "key"):
            raise ValueError(f"unknown emit_order {emit_order!r}")
        l_names, r_names = self._resolve_join_keys(other, on, left_on, right_on)
        if emit_order == "key" and how not in ("inner", "left"):
            raise ValueError(
                "emit_order='key' needs how='inner'/'left' (the unmatched-"
                "right append of right/outer joins has no key-ordered emit)"
            )
        if algorithm == "pallas_pk":
            if emit_order == "key":
                raise ValueError(
                    "emit_order='key' is not supported by algorithm='pallas_pk'"
                )
            return self._pallas_pk_join(other, l_names, r_names, how, suffixes)
        howi = _j.join_type_id(how)
        if howi in _j.SEMI_TYPES:
            return self._semi_join(
                other, l_names, r_names, howi, _left_mask, _right_mask
            )
        # sorted-run reuse gate, read BEFORE dictionary unification/promotion
        # (both preserve value order, so the descriptor's claim survives
        # them; the _replace they perform drops the attribute itself)
        r_presorted = _ord.covers_prefix(
            other._ordering, r_names, need_canonical=not all(
                other._columns[n].valid is None for n in r_names
            ),
        )
        emit_key = emit_order == "key"
        left, right = _unify_dict_pair(self, other, l_names, r_names)
        # factorize-lane fusion (ops/stats.py): the multi-key / masked
        # probe's joint factorize bit-packs both sides' canonical key
        # lanes into fewer merged-sort passes, driven by the pair's MERGED
        # range stats (the single-uint32-key fast path is already one lane
        # and skips the stats kernel entirely)
        join_fuse = _plan_join_fusion(left, l_names, right, r_names)
        if join_fuse is not None:
            bump("lane_pack.join_fused",
                 rows=join_fuse.n_plain - join_fuse.n_words)
        lflat_k = left._flat_cols(l_names)
        rflat_k = right._flat_cols(r_names)
        lflat = left._flat_cols()
        rflat = right._flat_cols()
        lk_idx = tuple(left.column_names.index(n) for n in l_names)
        rk_idx = tuple(right.column_names.index(n) for n in r_names)
        key = (
            "join", howi, lk_idx, rk_idx, len(lflat), len(rflat),
            r_presorted, emit_key, join_fuse,
        ) + _j.impl_tag()
        masked = _left_mask is not None or _right_mask is not None
        if masked and howi != _j.INNER:
            raise ValueError(
                "a row mask rides an inner join only (or a semi or anti join)"
            )
        if masked:
            if not _j.semi_capable(left.shard_cap, right.shard_cap):
                # a mask never falls through unread: filter, then join
                if _left_mask is not None:
                    left = left.filter(_left_mask)
                if _right_mask is not None:
                    right = right.filter(_right_mask)
                return left.join(
                    right, left_on=l_names, right_on=r_names, how=how,
                    suffixes=suffixes, emit_order=emit_order,
                )
            left, right, totals = left._semi_reduced(
                right, l_names, r_names, _left_mask, _right_mask, join_fuse
            )
            return left.join(
                right, left_on=l_names, right_on=r_names, how=how,
                suffixes=suffixes, emit_order=emit_order, _totals=totals,
            )

        # Speculative single-dispatch path: fuse probe+count+emit into ONE
        # program with a capacity-factor output (cap_l+cap_r covers every
        # outer-join minimum and ~1-match-per-key workloads). One dispatch +
        # one host sync instead of two of each — on a remote-attached TPU the
        # per-dispatch latency dominates small joins. Overflow (exact count >
        # speculative cap) falls back to the exact two-phase path below.
        out_names = _suffix_names(left.column_names, right.column_names, suffixes)
        src_cols = list(left._columns.values()) + list(right._columns.values())
        cap_l = left.shard_cap
        cap_r = right.shard_cap
        # output order properties: the key-order emit ESTABLISHES canonical
        # key order; the default left-order emit of INNER/LEFT preserves the
        # left input's existing descriptor (rows repeat in left order)
        l_rename = dict(
            zip(left.column_names, out_names[: len(left.column_names)])
        )
        if howi in (_j.INNER, _j.LEFT):
            carry_ordering = _ord.rename(self._ordering, l_rename)
        else:
            carry_ordering = None
        key_ordering = None
        if emit_key:
            key_ordering = Ordering(
                keys=tuple(l_rename[n] for n in l_names),
                ascending=(True,) * len(l_names),
                nulls_last=True,
                scope="shard",
                canonical=True,
                lexsort_exact=all(
                    left._columns[n].valid is None for n in l_names
                ),
            )
        if r_presorted:
            bump("ordering.join_presorted_probe")
        if _speculative_join():
            if howi in (_j.INNER, _j.LEFT) and not r_presorted:
                _bump_ride(rflat)  # spec_join's right sort
            # INNER/LEFT/RIGHT: max(cap_l, cap_r) covers every <=1-match-per-
            # key workload at HALF the emit/gather width of cap_l + cap_r;
            # overflow falls back to the exact two-phase path below AND
            # records the observed output size, so workloads with fanout > 1
            # (e.g. fact-to-2-row-dim joins) pay the wasted speculative
            # dispatch only once per join signature. FULL_OUTER's zero-match
            # minimum is nl + nr, so it always keeps the sum.
            hints = self.ctx.__dict__.setdefault("_spec_cap_hints", {})
            if _totals is not None:
                # the semi-reduction counted the rows: no speculation
                spec_cap = round_cap(int(_totals.max()))
            elif howi == _j.FULL_OUTER:
                spec_cap = round_cap(cap_l + cap_r)
            else:
                spec_cap = max(
                    round_cap(max(cap_l, cap_r)), hints.get(key, 0)
                )

            emit_impl, emit_kw = _j.emit_impl_kwargs(self.ctx)
            # the join of semi-reduced sides: every row has its partner
            counted = _totals is not None

            def build_spec():
                def kern(dp, rep):
                    (lk, rk, lcols, rcols, nl, nr) = dp
                    (dummy,) = rep
                    co = dummy.shape[0]
                    out, total, shadow, handed = _j.spec_join(
                        lk, rk, lcols, rcols, nl[0], nr[0], howi, co,
                        emit_impl, r_presorted=r_presorted,
                        emit_key_order=emit_key, key_fuse=join_fuse,
                        mask_free=counted,
                    )
                    # pack count + f32 overflow shadow + the emit's form
                    # into one [3] i32 lane so the host needs a single fetch
                    stats = jnp.stack([
                        total,
                        jax.lax.bitcast_convert_type(shadow, jnp.int32),
                        handed,
                    ])
                    return out, stats

                return kern

            with span("join.speculative", rows=self._rows_hint()):
                out, stats = get_kernel(
                    self.ctx,
                    key + (("spec", "counted") if counted else ("spec",)),
                    build_spec, name="join_spec", **emit_kw,
                )(
                    (lflat_k, rflat_k, lflat, rflat, left.counts_dev, right.counts_dev),
                    (jnp.zeros((spec_cap,), jnp.int8),),
                )
                handed = None  # a counted join fetches nothing
                if _totals is None:
                    stats = _fetch(stats, "join.speculative").reshape(-1, 3)
                    totals = stats[:, 0].astype(np.int64)
                    _check_join_count(
                        totals, stats[:, 1].copy().view(np.float32)
                    )
                    handed = stats[:, 2] != 0
                else:
                    totals = _totals
            fits = totals.max() <= spec_cap
            bump("join.emit_slots", rows=spec_cap * len(totals))
            bump("join.emit_rows", rows=int(totals.sum()) if fits else 0)
            if fits and handed is not None:
                # the emit's form, a shard (ops.join._emit_inner_left)
                bump("join.emit.handthrough", rows=int(totals[handed].sum()))
                bump("join.emit.gathered", rows=int(totals[~handed].sum()))
            if fits:
                res = self._rebuild_cols(
                    list(zip(out_names, src_cols)), out, totals, spec_cap
                )
                if emit_key:
                    bump("ordering.join_key_order_emit")
                # compact when the speculative cap overshot so downstream
                # ops don't pay for dead padding
                return res._maybe_compact(totals)._attach_ordering(
                    key_ordering if emit_key else carry_ordering
                )
            # speculation overflowed: remember the observed size so the next
            # join with this signature speculates wide enough immediately
            # (guarded: the hints map is ctx-shared across concurrent
            # queries; reads stay lock-free — a lost read only re-pays the
            # one-time wasted speculative dispatch)
            with _engine.cache_lock(self.ctx):
                hints[key] = round_cap(int(totals.max()))

        # phase 1: probe (the sorts) — returns reusable probe state + count.
        # Count + overflow shadow ride ONE packed [2] i32 lane (the spec
        # path's single-fetch discipline), so the exact path syncs once.
        def build_probe():
            def kern(dp, rep):
                (lk, rk, nl, nr) = dp
                cap_l = lk[0][0].shape[0]
                cap_r = rk[0][0].shape[0]
                lo, cnt, r_order, r_cnt = _j.probe_arrays(
                    lk, rk, nl[0], nr[0], cap_l, cap_r, howi,
                    r_presorted=r_presorted, key_fuse=join_fuse,
                )
                total = _j.count_from_probe(cnt, r_cnt, nl[0], nr[0], howi)
                shadow = _j.count_overflow_check(cnt, r_cnt)
                stats = jnp.stack(
                    [
                        total.astype(jnp.int32),
                        jax.lax.bitcast_convert_type(
                            shadow.astype(jnp.float32), jnp.int32
                        ),
                    ]
                )
                return lo, cnt, r_order, r_cnt, stats

            return kern

        lo, cnt, r_order, r_cnt, pstats = get_kernel(
            self.ctx, key + ("probe",), build_probe, name="join_probe"
        )((lflat_k, rflat_k, left.counts_dev, right.counts_dev), ())
        pstats = _fetch(pstats, "join.exact_counts").reshape(-1, 2)
        cnts = pstats[:, 0].astype(np.int64)
        _check_join_count(cnts, pstats[:, 1].copy().view(np.float32))
        cap_out = round_cap(int(cnts.max()))
        bump("join.emit_slots", rows=cap_out * len(cnts))
        bump("join.emit_rows", rows=int(cnts.sum()))

        # phase 2: emit + gather, reusing the probe state (no re-sort)
        emit_impl, emit_kw = _j.emit_impl_kwargs(self.ctx)

        def build_emit():
            def kern(dp, rep):
                (lo, cnt, r_order, r_cnt, lcols, rcols, nl, nr) = dp
                (dummy,) = rep
                co = dummy.shape[0]
                out, n_out = _j.emit_gather(
                    lo, cnt, r_order, r_cnt, lcols, rcols,
                    nl[0], nr[0], howi, co, emit_impl,
                )
                return out, _scalar(n_out)

            return kern

        out, _nout = get_kernel(
            self.ctx, key + ("emit",), build_emit, name="join_emit",
            **emit_kw,
        )(
            (lo, cnt, r_order, r_cnt, lflat, rflat, left.counts_dev, right.counts_dev),
            (jnp.zeros((cap_out,), jnp.int8),),
        )
        # output schema: left columns then right columns, suffix on collision
        # (reference join_utils.cpp:28-160 suffix renaming). This exact
        # two-phase path always emits LEFT order (a key-order request that
        # overflowed speculation degrades to no descriptor, never an
        # unsound claim). The emit's count lane equals the probe's already-
        # fetched counts — reuse them, no second sync.
        return self._rebuild_cols(
            list(zip(out_names, src_cols)), out, cnts, cap_out
        )._attach_ordering(carry_ordering)

    def _semi_reduced(
        self, other: "Table", l_names, r_names, l_mask, r_mask, join_fuse
    ) -> Tuple["Table", "Table", np.ndarray]:
        """Both sides of an INNER join cut to the rows that have a live
        partner on the other side, in row order, and the join's exact row
        count a shard: the semi-reduction in front of a selective join
        (``Table.join``'s docstring has the rule). ``l_mask`` / ``r_mask``
        (bool, the padded layout; None for every live row) are the filters
        that ride the join: a row they drop is as dead as a padding slot.

        Two programs and one fetch. ``join_semi`` reads the key columns
        and the masks alone (``ops.join.semi_hits``: one kv-sort of the
        merged key ids, two blocked run scans, one single-operand sort); the host
        fetches rows-with-a-partner of either side and the total;
        ``join_reduce`` gathers each side's kept rows at ``round_cap`` of
        its count. No payload column is read before its rows are known."""
        l_on, r_on = bool(l_mask is not None), bool(r_mask is not None)
        lflat_k, rflat_k = self._flat_cols(l_names), other._flat_cols(r_names)
        sig = (
            tuple(self.column_names.index(n) for n in l_names),
            tuple(other.column_names.index(n) for n in r_names),
        )

        def build_semi():
            def kern(dp, rep):
                l_ids, r_ids, l_live, r_live, _rk = _key_ids_and_live(
                    dp, l_on, r_on, join_fuse
                )
                hits, stats = _j.semi_hits(l_ids, r_ids, l_live, r_live)
                return hits, stats

            return kern

        with span("join.semi", rows=self._rows_hint()):
            hits, stats = get_kernel(
                self.ctx, ("join_semi", sig, l_on, r_on, join_fuse),
                build_semi,
            )(
                (
                    lflat_k, rflat_k, self.counts_dev, other.counts_dev,
                    [m for m in (l_mask, r_mask) if m is not None],
                ),
                (),
            )
            got = _fetch(stats, "join.semi").reshape(-1, 4)
        n_r, n_l = got[:, 0].astype(np.int64), got[:, 1].astype(np.int64)
        totals = got[:, 2].astype(np.int64)
        _check_join_count(totals, got[:, 3].copy().view(np.float32))
        cap_lo, cap_ro = round_cap(int(n_l.max())), round_cap(int(n_r.max()))
        lflat, rflat = self._flat_cols(), other._flat_cols()

        def build_reduce():
            def kern(dp, rep):
                (hits, stats, lcols, rcols) = dp
                (dl, dr) = rep
                return _j.reduce_by_hits(
                    hits, stats, lcols, rcols, dl.shape[0], dr.shape[0]
                )

            return kern

        out_l, out_r = get_kernel(
            self.ctx, ("join_reduce", len(lflat), len(rflat)), build_reduce
        )(
            (hits, stats, lflat, rflat),
            (jnp.zeros((cap_lo,), jnp.int8), jnp.zeros((cap_ro,), jnp.int8)),
        )
        # row subsets in row order: ordering and stats survive, as a
        # filter's do
        left = self._rebuild_cols(
            list(zip(self.column_names, self._columns.values())),
            out_l, n_l, cap_lo,
        )._attach_ordering(self._ordering)._attach_stats(self._stats)
        right = other._rebuild_cols(
            list(zip(other.column_names, other._columns.values())),
            out_r, n_r, cap_ro,
        )._attach_ordering(other._ordering)._attach_stats(other._stats)
        return left, right, totals

    def _semi_join(
        self, other: "Table", l_names, r_names, howi: int, l_mask, r_mask,
        as_mask: bool = False,
    ):
        """The semi join (``howi`` ANTI: the anti join) of :meth:`join`:
        this table's rows that have (have no) partner in ``other``, each at
        most once, in row order. ``l_mask`` / ``r_mask`` ride it as they
        ride an INNER join: a row they drop is as dead as a padding slot (a
        dropped left row is not kept, a dropped right row is no partner).
        A null key has no partner on either side.

        One keys-only program (``join_semi_rows``: ``ops.join.semi_rows``
        over the key columns and the masks), which reads no payload column
        and nothing of ``other`` but its keys. Then one of two:

        - the rows are compacted: the kept count is fetched (the one
          sync) and ``join_semi_take`` gathers this table's columns at
          ``round_cap`` of it, by positions the first program already
          sorted. The result is a row subset in row order, so names,
          ordering and stats are kept as a filter keeps them;
        - ``as_mask`` (the planner's ``semi_as_mask``): nothing is fetched
          or gathered; ``(self, mask)`` comes back, the verdicts as a row
          mask over this table's padded layout, for the aggregate above.

        Where both sides' positions do not fit the keys-only program's
        word (``ops.join.semi_capable``) the same program carries the
        dead flag through its sort as an operand of its own (``wide``)."""
        anti = howi == _j.ANTI
        left, right = _unify_dict_pair(self, other, l_names, r_names)
        join_fuse = _plan_join_fusion(left, l_names, right, r_names)
        l_on, r_on = l_mask is not None, r_mask is not None
        wide = not _j.semi_capable(left.shard_cap, right.shard_cap)
        sig = (
            tuple(left.column_names.index(n) for n in l_names),
            tuple(right.column_names.index(n) for n in r_names),
        )

        def build_rows():
            def kern(dp, rep):
                l_ids, r_ids, l_live, r_live, rk = _key_ids_and_live(
                    dp, l_on, r_on, join_fuse
                )
                # EXISTS: a null key equals nothing, a null of the other
                # side included (the INNER join pairs them, as pandas
                # does). A right row with one is no partner; a left row
                # with one stays live and finds none (anti keeps it)
                for _d, v in rk:
                    r_live = r_live if v is None else r_live & v
                out, kept = _j.semi_rows(
                    l_ids, r_ids, l_live, r_live, anti, as_mask, wide
                )
                return out, _scalar(kept)

            return kern

        l_rows, r_rows = self._rows_hint(), other._rows_hint()
        bump("join.semi.left_rows", rows=l_rows or 0)
        with span("join.semi_join", rows=l_rows, right_rows=r_rows):
            rows, kept = get_kernel(
                self.ctx,
                ("join_semi_rows", sig, l_on, r_on, join_fuse, anti,
                 as_mask, wide),
                build_rows,
            )(
                (
                    left._flat_cols(l_names), right._flat_cols(r_names),
                    left.counts_dev, right.counts_dev,
                    [m for m in (l_mask, r_mask) if m is not None],
                ),
                (),
            )
            if as_mask:
                bump("join.semi.payload_rows", rows=0)
                return self, rows
            counts = _fetch(kept, "join.semi_join").reshape(-1).astype(
                np.int64
            )
        kept_rows = int(counts.sum())
        bump("join.semi.kept_rows", rows=kept_rows)
        bump("join.semi.payload_rows", rows=kept_rows)
        cap_out = round_cap(int(counts.max()))
        flat = self._flat_cols()

        def build_take():
            def kern(dp, rep):
                (rows, kept, cols) = dp
                (dummy,) = rep
                co, cap = dummy.shape[0], rows.shape[0]
                if co <= cap:
                    idx = rows[:co]
                else:
                    idx = jnp.concatenate(
                        [rows, jnp.full((co - cap,), -1, jnp.int32)]
                    )
                idx = jnp.where(
                    jnp.arange(co, dtype=jnp.int32) < kept[0], idx, -1
                )
                # every -1 lands past the kept rows: a column without a
                # validity lane keeps none
                out, _ = _g_pack.pack_gather(list(cols), idx, all_valid=True)
                return out

            return kern

        out = get_kernel(
            self.ctx, ("join_semi_take", len(flat)), build_take
        )((rows, kept, flat), (jnp.zeros((cap_out,), jnp.int8),))
        return self._rebuild_cols(
            list(zip(self.column_names, self._columns.values())),
            out, counts, cap_out,
        )._attach_ordering(self._ordering)._attach_stats(self._stats)

    def _pallas_pk_join(
        self,
        other: "Table",
        l_names,
        r_names,
        how: str,
        suffixes: Tuple[str, str],
    ) -> "Table":
        """``algorithm='pallas_pk'``: the bucketed Pallas PK-FK probe
        (ops/pallas_join.py — VMEM broadcast-compare, no probe sort) as a
        selectable join algorithm, the way the reference's JoinConfig picks
        SORT vs HASH (join_config.hpp:26-189).

        Single integer (or dictionary-code) key, inner join, no nulls on
        the key. Right-key uniqueness and bucket overflow are SPECULATED:
        the kernel reports a ``bad`` flag and the join silently reruns on
        the exact sort-based path — same single-sync philosophy as
        spec_join, never a wrong answer."""
        if how != "inner":
            raise ValueError("algorithm='pallas_pk' supports how='inner' only")
        left, right = _unify_dict_pair(self, other, l_names, r_names)
        left, right = _promote_key_pair(left, right, l_names, r_names)
        lk = left._flat_cols(l_names)
        rk = right._flat_cols(r_names)
        if len(lk) != 1 or lk[0][1] is not None or rk[0][1] is not None:
            raise ValueError(
                "algorithm='pallas_pk' needs a single null-free key column"
            )
        kd = lk[0][0].dtype
        if not (jnp.issubdtype(kd, jnp.integer) and np.dtype(kd).itemsize <= 4):
            raise ValueError(
                "algorithm='pallas_pk' needs an integer (or dictionary-"
                f"encoded) key <= 32 bits, got {np.dtype(kd)}"
            )
        from .ops import pallas_join as _pk

        lflat = left._flat_cols()
        rflat = right._flat_cols()
        # inner PK-FK output has <= 1 match per left row: cap_out = cap_l is
        # a static exact bound -> single dispatch, ONE host sync
        cap_out = left.shard_cap
        B = 256
        interp = self.ctx.platform == "cpu"
        key = (
            "pallas_pk_join", len(lflat), len(rflat), cap_out, B, interp,
        )

        def build():
            def kern(dp, rep):
                (lkc, rkc, lcols, rcols, nl, nr) = dp
                l_idx, r_idx, total, bad = _pk.pk_inner_join(
                    lkc[0][0], rkc[0][0], nl[0], nr[0], B=B, interpret=interp,
                )
                out_l, _ = _g_pack.pack_gather(list(lcols), l_idx)
                out_r, _ = _g_pack.pack_gather(list(rcols), r_idx)
                return list(out_l) + list(out_r), jnp.stack([total, bad])

            return kern

        with span("join.pallas_pk", rows=self._rows_hint()):
            args = (lk, rk, lflat, rflat, left.counts_dev, right.counts_dev)
            # world==1: shard_map is a no-op, so the kernel is jitted
            # directly. Multi-device meshes run the pallas_call per shard
            # under shard_map, compiled on an accelerator mesh and
            # interpreted on a CPU one (a kernel the chip's compiler
            # refuses raises here; it is never swapped for another
            # algorithm); check_vma=False because pallas_call output vma
            # interplay with unvarying iotas trips shard_map's checker
            out, stats = get_kernel(
                self.ctx, key, build, check_vma=False,
                use_shard_map=self.ctx.world_size > 1,
            )(args, ())
            # the ONE host sync
            stats = _fetch(stats, "join.pallas_pk").reshape(-1, 2)
        if int(stats[:, 1].sum()) != 0:
            # speculation miss (duplicate right keys / bucket overflow):
            # exact sort-based join, correctness never depends on the hint
            return self.join(
                other,
                left_on=l_names if l_names != r_names else None,
                right_on=r_names if l_names != r_names else None,
                on=l_names if l_names == r_names else None,
                how=how,
                suffixes=suffixes,
            )
        out_names = _suffix_names(left.column_names, right.column_names, suffixes)
        src_cols = list(left._columns.values()) + list(right._columns.values())
        res = self._rebuild_cols(
            list(zip(out_names, src_cols)), out, stats[:, 0].astype(np.int64),
            cap_out,
        )
        return res._maybe_compact(res._row_counts)

    @_obstrace.op("distributed_join")
    def distributed_join(
        self,
        other: "Table",
        on: Optional[Union[str, Sequence[str]]] = None,
        how: str = "inner",
        *,
        mode: str = "eager",
        **kwargs,
    ) -> "Table":
        """The flagship op (reference DistributedJoin, table.cpp:482-502):
        both tables' rows that share a key are brought to one chip, then
        every shard runs the local :meth:`join`. world_size==1
        short-circuits to the local join (reference :487-489). On a mesh
        an eager join takes one of two routes, and the rule that picks is
        ``ops.join.replicate_side`` (no keyword, variable or tuned
        decision moves it):

        - **shuffle**: both sides are hash-shuffled on the join keys over
          the mesh (one engine call, the two tables' rounds interleaved,
          the semi-join sketch filter where the type allows it), then the
          local join of what each shard received. The result lies where
          the hash put its keys.
        - **replicate**: the small side's live rows are gathered to every
          chip by one program (``join_replicate``: an ``all_gather`` of
          its columns and counts, front-packed to ``round_cap`` of the
          total; nothing is fetched, the counts are the host's already),
          the big side stays where it lies, and every chip joins its own
          shard against the whole small table. The result is sharded
          exactly as the big side was: each chip's rows in the big side's
          row order, its ordering descriptor carried as the local join
          carries the left side's, ONE copy of the result over the mesh.
          The gathered columns never leave this call: no table whose
          shards each hold every row reaches an operation that would
          count it once a chip.

        Which side MAY be replicated follows from the join type alone
        (``ops.join.REPLICABLE_SIDES``): the right side of ``inner``,
        ``left``, ``semi``, ``anti``; the left side of ``inner``,
        ``right`` (the join is then run from the big side, ``right`` as a
        left join, and the columns put back in this order); neither side
        of ``outer``, since an unmatched row of a replicated side would
        come out once a chip. Whether it IS follows from what the host
        already holds, each side's row count and the bytes a row takes on
        the device: a side is replicated where the whole of it is at most
        one part in ``config.REPLICATE_JOIN_MIN_RATIO`` of one chip's
        share of the other (64:1 between the tables on four chips). A
        side whose count is still on the device takes the shuffle route
        and nothing is fetched to decide. ``join.route.replicate`` /
        ``join.route.shuffle`` count the routes (``rows=`` both sides'
        rows), ``join.replicate.rows`` the rows the gather brought to
        chips that did not hold them.

        ``mode='fused'`` runs the whole shuffle->join chain as ONE compiled
        XLA program with static capacities and a single host sync (the
        product surface of parallel/pipeline.py — the analog of the
        reference's streaming DisJoinOP graph, ops/dis_join_op.cpp:26-71).
        In EAGER mode extra kwargs (``suffixes``, ``algorithm`` — incl.
        'pallas_pk', which the shuffle co-partitions for) pass through to
        the per-shard join; fused mode rejects a non-default ``algorithm``
        (its join is baked into the fused program).
        Undersized capacities are detected via the overflow flag and retried
        with doubled capacities (no wrong answers, just a recompile).

        Capacity under skew: one program runs on every shard (SPMD), so
        every shard of a shuffled side has the slots of the FULLEST one,
        ``round_cap`` (the next power of two) of its received rows, and the
        result's shards have ``round_cap(max(cap_l, cap_r))`` slots, the
        speculative capacity that covers any join of at most one match a
        key. A foreign key that sends half the rows to one of four shards
        therefore gives all four the slots of that half, about half of
        them empty (``join.emit_rows`` over ``join.emit_slots``), and every
        sort and gather of the local join runs at that width on every
        chip."""
        if on is not None:
            kwargs["on"] = on
        kwargs.setdefault("how", how)
        if mode == "fused":
            if kwargs.get("algorithm", "sort") not in ("sort", "hash"):
                raise ValueError(
                    "mode='fused' bakes the sort join into the fused "
                    f"program; algorithm={kwargs['algorithm']!r} needs "
                    "mode='eager'"
                )
            if kwargs.get("emit_order", "left") != "left":
                raise ValueError(
                    "mode='fused' bakes the left-order emit into the fused "
                    "program; emit_order='key' needs mode='eager'"
                )
            return self._fused_join(other, **kwargs)
        if mode != "eager":
            raise ValueError(f"unknown join mode {mode!r}")
        if self.world_size == 1:
            return self.join(other, **kwargs)
        l_names, r_names = self._resolve_join_keys(
            other, kwargs.get("on"), kwargs.get("left_on"), kwargs.get("right_on")
        )
        if _j.join_type_id(kwargs["how"]) in _j.SEMI_TYPES:
            # a semi or anti join reads the right side's keys alone: no
            # other column of it is packed, exchanged or compacted
            other = other.project(r_names)
        side = _j.replicate_side(
            kwargs["how"], self._host_size(), other._host_size(),
            self.world_size,
        )
        if side is not None:
            for k in ("on", "left_on", "right_on"):
                kwargs.pop(k, None)
            return self._join_replicated(
                other, side, l_names, r_names, **kwargs
            )
        _bump_join_route("shuffle", self, other)
        left, right = _unify_dict_pair(self, other, l_names, r_names)
        # promote key dtype pairs BEFORE hashing: the shuffle hashes each side
        # independently, and murmur words depend on the physical dtype — an
        # int32 5 and int64 5 would otherwise land on different shards
        left, right = _promote_key_pair(left, right, l_names, r_names)
        # one engine call for both sides: the two shuffles' rounds interleave
        # in the dispatch queue (pack of one hides behind the collective of
        # the other) instead of serializing table-by-table. The semi-join
        # sketch filter prunes provably partnerless rows before the payload
        # exchange, gated by join type (inner: both sides; left/right: the
        # other side only; semi: the left side; outer and anti: off —
        # ops/sketch.join_filter_sides)
        ls, rs = _shuffle_pair(
            left, l_names, right, r_names,
            semi=_sketch.join_filter_sides(kwargs.get("how", "inner")),
        )
        return ls.join(rs, **kwargs)

    def _host_size(self) -> Optional[Tuple[int, int]]:
        """``(rows, bytes a row takes on the device)`` as the host holds
        them, ``None`` while the row count is still on the device: what
        ``ops.join.replicate_side`` weighs. The validity lanes are left
        out, as the planner's schema has none (``plan.nodes``)."""
        if self._counts_host is None:
            return None
        return (
            int(self._counts_host.sum()),
            sum(c.data.dtype.itemsize for c in self._columns.values()),
        )

    def _replicated(self) -> "Table":
        """This table's live rows on EVERY shard, front-packed at
        ``round_cap`` of the total, each shard's count the total: the
        small side of a distributed join's replicate route. One program
        (``join_replicate``, ``parallel/shuffle.replicate_cols``) and no
        fetch. PRIVATE to that route: every other operation would read
        such a table as ``world`` copies of its rows."""
        total = int(self._row_counts.sum())
        cap = round_cap(total)
        flat = self._flat_cols()
        axis = self.ctx.axis_name

        def build():
            def kern(dp, rep):
                (cols, n) = dp
                (dummy,) = rep
                return _sh.replicate_cols(cols, n, axis, dummy.shape[0])

            return kern

        world = self.world_size
        bump("join.replicate.rows", rows=total * (world - 1))
        with span("join.replicate", rows=total):
            out = get_kernel(self.ctx, ("join_replicate", len(flat)), build)(
                (flat, self.counts_dev), (jnp.zeros((cap,), jnp.int8),)
            )
        # a row's values are what they were: the range stats hold; the
        # shards' runs end to end are in no order
        return self._rebuild_cols(
            list(zip(self.column_names, self._columns.values())),
            out, np.full(world, total, np.int64), cap,
        )._attach_stats(self._stats)

    def _join_replicated(
        self, other: "Table", side: str, l_names, r_names, how: str,
        suffixes: Tuple[str, str] = ("_x", "_y"), as_mask: bool = False,
        **kwargs,
    ) -> "Table":
        """The replicate route of :meth:`distributed_join`: ``side``
        (``"right"``: ``other``; ``"left"``: this table) is gathered whole
        to every chip and every chip joins its own shard of the big side
        against it. The local join always runs FROM the big side, so the
        result keeps the big side's shards, row order and ordering
        descriptor: with the left side replicated the sides are swapped
        (``right`` becomes ``left``, ``inner`` stays; the types that could
        not be swapped may not replicate their left side) and the columns
        are put back left first under the names the unswapped join gives
        them.

        A semi or anti join ships the right side's keys alone. ``as_mask``
        (the planner's ``semi_as_mask`` over such a join): nothing is
        compacted, ``(self, mask)`` comes back as from :meth:`_semi_join`."""
        howi = _j.join_type_id(how)
        if howi in _j.SEMI_TYPES:
            other = other.project(r_names)
        _bump_join_route("replicate", self, other)
        if as_mask:
            return self._semi_join(
                other._replicated(), l_names, r_names, howi, None, None,
                as_mask=True,
            )
        if side == "right":
            return self.join(
                other._replicated(), left_on=l_names, right_on=r_names,
                how=how, suffixes=suffixes, **kwargs,
            )
        if howi == _j.RIGHT and kwargs.get("emit_order", "left") == "key":
            raise ValueError(
                "emit_order='key' needs how='inner'/'left' (the unmatched-"
                "right append of right/outer joins has no key-ordered emit)"
            )
        res = other.join(
            self._replicated(), left_on=r_names, right_on=l_names,
            how="left" if howi == _j.RIGHT else how,
            suffixes=(suffixes[1], suffixes[0]), **kwargs,
        )
        names = _suffix_names(self.column_names, other.column_names, suffixes)
        return res.project(names)

    def _fused_join(
        self,
        other: "Table",
        on=None,
        how: str = "inner",
        left_on=None,
        right_on=None,
        suffixes: Tuple[str, str] = ("_x", "_y"),
        capacity_factor: float = 2.0,
        max_retries: int = 3,
        respill: int = 1,
        num_slices: int = 1,
        **_ignored,
    ) -> "Table":
        """shuffle->join as one XLA program (see distributed_join). One host
        sync per attempt: the fetch of (out_counts, overflow).

        ``respill`` = extra in-program exchange rounds per shuffle: a bucket
        hotter than bucket_cap drains over (1+respill) rounds with no host
        sync; only a bucket past (1+respill)*bucket_cap triggers the
        host-level doubled-capacity retry. Raise it for known-skewed keys to
        trade collective rounds for recompiles.

        ``num_slices`` = K > 1 runs K hash-slice rounds so each probe sort
        sees ~n/K rows (log^2(n/K) passes — PARITY.md north-star lever 1).
        Worth it when per-shard rows are large enough that sort depth
        dominates; ignored on 1-device meshes (no shuffle to ride)."""
        from .parallel.pipeline import make_distributed_join_step

        ctx = self.ctx
        world = ctx.world_size
        l_names, r_names = self._resolve_join_keys(other, on, left_on, right_on)
        howi = _j.join_type_id(how)
        if howi in _j.SEMI_TYPES:
            raise ValueError(
                "mode='fused' emits both sides' columns; a semi or anti "
                "join needs mode='eager'"
            )
        left, right = _unify_dict_pair(self, other, l_names, r_names)
        left, right = _promote_key_pair(left, right, l_names, r_names)
        lk_idx = tuple(left.column_names.index(n) for n in l_names)
        rk_idx = tuple(right.column_names.index(n) for n in r_names)
        lflat = left._flat_cols()
        rflat = right._flat_cols()
        cap_l, cap_r = left.shard_cap, right.shard_cap
        respill = int(respill)
        if respill < 0:
            raise ValueError("respill must be >= 0")
        num_slices = int(num_slices)
        if num_slices < 1:
            raise ValueError("num_slices must be >= 1")
        if world <= 1:
            num_slices = 1  # no shuffle for the slice filter to ride
        bucket_cap = round_cap(
            int(
                capacity_factor * max(cap_l, cap_r)
                / max(world * num_slices, 1)
            )
        )
        if world > 1:
            # thread the chunked engine's byte budget through the fused
            # path: cap the per-round exchange buffer the same way the
            # eager engine does (an undersized first attempt is recovered
            # by the overflow retry loop below, which may exceed the
            # budget — correctness over memory)
            row_bytes = max(
                _sh.exchange_row_bytes(lflat), _sh.exchange_row_bytes(rflat)
            )
            bucket_cap = min(
                bucket_cap,
                _sh.budget_bucket_cap(
                    row_bytes, world,
                    # the feedback re-coster's per-shape budget (threaded
                    # into the plan fingerprint) overrides the static
                    # default here exactly as in _shuffle_many
                    _feedback.tuned_shuffle_budget()
                    or ctx.shuffle_byte_budget,
                    bucket_cap,
                ),
            )
            join_cap = round_cap(2 * (1 + respill) * world * bucket_cap)
        else:
            join_cap = round_cap(cap_l + cap_r)
        # the effective 2-D topology routes every fused shuffle as the
        # structured two-hop (parallel/topo.py); a static build parameter
        # exactly like the quant specs — it joins the step cache key below
        topo_cfg = _topo.effective(ctx) if world > 1 else None
        for attempt in range(max_retries):
            if world > 1:
                # fused-path exchange accounting: same counter family the
                # eager planner feeds, so fused and eager regimes compare
                # like-for-like in BENCH / EXPLAIN (pipeline.py helper)
                from .parallel.pipeline import (
                    fused_axis_bytes,
                    fused_exchange_bytes,
                )

                bump(
                    "shuffle.exchanged_bytes",
                    rows=fused_exchange_bytes(
                        world, bucket_cap, respill,
                        _sh.exchange_row_bytes(lflat),
                        _sh.exchange_row_bytes(rflat),
                        num_slices,
                    ),
                )
                for rb_side in (
                    _sh.exchange_row_bytes(lflat),
                    _sh.exchange_row_bytes(rflat),
                ):
                    fi, fo = fused_axis_bytes(
                        world, bucket_cap, respill, rb_side, topo_cfg,
                        num_slices,
                    )
                    if fi:
                        bump("shuffle.coll_bytes.intra", rows=fi)
                    bump("shuffle.coll_bytes.inter", rows=fo)
            # the quantized wire tier rides the fused shuffles too: per-
            # side codec specs (key columns excluded) are static build
            # parameters, so they join the step cache key — a tolerance
            # flip builds a fresh program, never aliases
            quant_l = _quant.quant_spec(
                [d.dtype for d, _v in lflat], lk_idx, ctx.quant_tol
            )
            quant_r = _quant.quant_spec(
                [d.dtype for d, _v in rflat], rk_idx, ctx.quant_tol
            )
            key = (
                "fused_join", howi, lk_idx, rk_idx, len(lflat), len(rflat),
                bucket_cap, join_cap, respill, num_slices,
                _st.enabled(), quant_l, quant_r,
                ("topo", tuple(topo_cfg) if topo_cfg else None),
            ) + _j.impl_tag()
            cache = ctx.__dict__.setdefault("_jit_cache", {})
            step = cache.get(key)
            if step is None:
                # cached outside get_kernel, timed like its programs
                step = _engine.TimedProgram(
                    make_distributed_join_step(
                        ctx.mesh, ctx.axis_name, lk_idx, rk_idx, howi,
                        bucket_cap, join_cap, respill, num_slices,
                        quant_l=quant_l, quant_r=quant_r, topo=topo_cfg,
                    ),
                    "join_fused",
                )
                cache[key] = step
            t0_prof = _time.perf_counter()
            with span("join.fused", rows=self._rows_hint()):
                from .engine import record_dispatch

                record_dispatch(
                    step, (lflat, left.counts_dev, rflat, right.counts_dev), ()
                )
                out, nout, overflow = step(
                    (lflat, left.counts_dev, rflat, right.counts_dev), ()
                )
                # ONE host transfer for counts + overflow: concatenate the
                # tiny stat arrays on device, fetch once
                stats = jnp.concatenate(
                    [nout.astype(jnp.int32), overflow.astype(jnp.int32)]
                )
                stats = _fetch(stats, "join.fused")  # THE host sync
                # fused-pipeline stage clocks (obs/prof.py): the stats
                # fetch above IS this attempt's device-resolved end, and
                # every work unit is shape-derived — host math only
                _prof.record_stages(
                    "fused",
                    _prof.fused_units(
                        world, bucket_cap, num_slices * (1 + respill),
                        self._rows_hint() or cap_l * world,
                        other._rows_hint() or cap_r * world,
                        join_cap,
                    ),
                    world, t0_prof, _obstrace.last_fetch_return_s(),
                )
            P = world
            nout_h = stats[:P].astype(np.int64)
            ov = stats[P:].reshape(-1, 2)
            ov_shuffle = int(ov[:, 0].sum())
            ov_join = int(ov[:, 1].max())
            if ov_shuffle == 0 and ov_join == 0:
                out_names = _suffix_names(
                    left.column_names, right.column_names, suffixes
                )
                src_cols = list(left._columns.values()) + list(
                    right._columns.values()
                )
                res = self._rebuild_cols(
                    list(zip(out_names, src_cols)), out, nout_h,
                    num_slices * join_cap,
                )
                # sliced runs allocate K*join_cap but fill ~the same rows a
                # 1-slice run would: drop dead padding before returning
                return res._maybe_compact(nout_h) if num_slices > 1 else res
            if ov_join >= 2**31 - 1:
                # the pipeline's saturated wrap sentinel (the int32-wrap
                # guard in pipeline.join_shard): a shard's join count
                # overflowed int32. Resizing to
                # join_cap + 2^31 would overflow the int32 iotas/allocation
                # downstream, so diagnose cleanly instead of recompiling.
                raise RuntimeError(
                    "fused join per-shard output count exceeds int32 "
                    "(extreme skew); use mode='eager'"
                )
            if ov_shuffle > 0:
                bucket_cap *= 2
                join_cap = max(
                    join_cap, round_cap(2 * (1 + respill) * world * bucket_cap)
                )
            if ov_join > 0:
                # the join lane reports the EXACT shortfall: converge at once
                join_cap = round_cap(join_cap + ov_join)
        raise RuntimeError(
            f"fused join overflowed after {max_retries} capacity retries "
            f"(extreme skew); use mode='eager'"
        )

    def _join_sum_pushdown(
        self,
        other: "Table",
        left_on: Sequence[str],
        right_on: Sequence[str],
        val_col: str,
        out_key_names: Sequence[str],
        out_val: str,
    ) -> "Table":
        """INNER join + groupby-SUM(``val_col``, a LEFT column) BY the join
        key as ONE per-shard kernel (ops.join.join_sum_by_key_pushdown with
        key-value emission) — the lowering target of the planner's
        ``fused_join_groupby`` rewrite. The caller (plan/lower.py) must have
        already co-partitioned, dictionary-unified and dtype-promoted the
        pair, exactly as it would before a local join.

        Output: the left key columns named ``out_key_names`` (join-pair
        order) then ``out_val`` = per-group sum over the join result.
        ``group_cap = min(cap_l, cap_r)`` is a static EXACT bound (a group
        needs a live row on both sides), so like groupby there is no count
        phase and NO host sync: the count fetch is deferred to result
        materialization (the q3 ``dispatch()`` single-sync pin)."""
        left, right = self, other
        lk_idx = tuple(left.column_names.index(n) for n in left_on)
        rk_idx = tuple(right.column_names.index(n) for n in right_on)
        val_idx = left.column_names.index(val_col)
        lflat = left._flat_cols()
        rflat = right._flat_cols()
        group_cap = min(left.shard_cap, right.shard_cap)
        # impl_tag: the kernel reads CYLON_TPU_SEGSUM_IMPL at trace time
        # (join_sum_by_key_pushdown's scatter discipline) — graft-lint's
        # first live catch: without the tag a mid-process flip kept the
        # stale program
        key = (
            "join_sum_pushdown", lk_idx, rk_idx, val_idx, len(lflat),
            len(rflat), group_cap,
        ) + _j.impl_tag()

        def build():
            def kern(dp, rep):
                (lcols, lcounts, rcols, rcounts) = dp
                nl, nr = lcounts[0], rcounts[0]
                lk = [lcols[i] for i in lk_idx]
                rk = [rcols[i] for i in rk_idx]
                s, ng, _nj, _og, reps, vcnt = _j.join_sum_by_key_pushdown(
                    lk, rk, lcols[val_idx], nl, nr, group_cap,
                    return_reps=True,
                )
                gmask = jnp.arange(group_cap, dtype=jnp.int32) < ng
                rep_idx = jnp.where(gmask, reps, -1)
                out = [_j.gather_column(d, v, rep_idx) for d, v in lk]
                # mirror groupby_aggregate's SUM validity: a group whose
                # left values are ALL null sums to null, not 0
                sum_valid = (
                    None if lcols[val_idx][1] is None
                    else gmask & (vcnt > 0)
                )
                out.append((s, sum_valid))
                return out, _scalar(ng)

            return kern

        t0_prof = _time.perf_counter()
        with span("join.sum_pushdown", rows=self._rows_hint()):
            out, nout = get_kernel(self.ctx, key, build)(
                (lflat, left.counts_dev, rflat, right.counts_dev), ()
            )
        # stage clocks for the sync-free fused q3 kernel: dispatch-time
        # work units attach PENDING to the active query trace; the window
        # resolves when the deferred count fetch stamps the query's
        # device-resolved end (obs.prof.finalize) — no sync added, the
        # q3 dispatch census stays at exactly one fetch
        _prof.record_fused(
            _prof.fused_units(
                self.ctx.world_size, 0, 1,
                self._rows_hint() or left.shard_cap,
                other._rows_hint() or right.shard_cap,
                group_cap,
            ),
            self.ctx.world_size, t0_prof,
        )
        cols_od: "OrderedDict[str, Column]" = OrderedDict()
        for name, srcn, (d, v) in zip(
            out_key_names, left_on, out[: len(left_on)]
        ):
            src = left._columns[srcn]
            cols_od[name] = Column(d, src.dtype, v, src.dictionary)
        d, v = out[-1]
        cols_od[out_val] = Column(d, DataType.from_numpy_dtype(d.dtype), v, None)
        # deferred counts: the fetch (and the overshoot compaction) happen
        # at result materialization — a dispatched q3 chain stays sync-free
        res = Table(self.ctx, cols_od, nout, group_cap)
        # groups emit in canonical key order (join_sum_by_key_pushdown
        # numbers them over the merged kv-sort)
        return res._attach_ordering(Ordering(
            keys=tuple(out_key_names),
            ascending=(True,) * len(out_key_names),
            nulls_last=True, scope="shard", canonical=True,
            lexsort_exact=all(
                left._columns[n].valid is None for n in left_on
            ),
        ))

    def lazy(self) -> "object":
        """Start a lazy query plan over this table: build with
        ``.filter/.select/.join/.groupby/.sort``, inspect with
        ``.explain()``, run with ``.collect()`` (plan/lazy.py)."""
        from .plan.lazy import LazyFrame

        return LazyFrame.from_table(self)

    # ------------------------------------------------------------------
    # set operations
    # ------------------------------------------------------------------
    def _setop_pair(self, other: "Table"):
        if self.column_names != other.column_names:
            raise ValueError("set operations require identical schemas")
        return _unify_dict_pair(self, other, self.column_names, other.column_names)

    def union(self, other: "Table") -> "Table":
        """Distinct union (reference Union, table.cpp:531-603).

        One program (setops.union_emit): the concat never materializes —
        both tables' rows go through a single shared sort and the keepers
        are gathered straight out of a lane-packed [left ++ right] matrix.
        Same sorted-space design (and code path) as subtract/intersect,
        but the output can draw from BOTH tables so cap_out = cap_l +
        cap_r and the program is its own cache entry."""
        return self._two_table_setop(other, "union")

    def subtract(self, other: "Table") -> "Table":
        """Distinct rows of self not in other (reference Subtract,
        table.cpp:605-663)."""
        return self._two_table_setop(other, "subtract")

    def intersect(self, other: "Table") -> "Table":
        """Distinct rows present in both (reference Intersect,
        table.cpp:665-721)."""
        return self._two_table_setop(other, "intersect")

    def _two_table_setop(self, other: "Table", op: str) -> "Table":
        """Shared single-dispatch emit for union/subtract/intersect.

        Single-dispatch: the output is a subset of the input rows, so
        cap_out is a static exact upper bound (left cap for subtract/
        intersect, cap_l + cap_r for union) — no count phase, no overflow
        possible, no dispatch-time host sync (the count fetch defers to
        result materialization). A selective result is compacted there
        like the join's. Subtract and intersect share ONE
        program: the op rides in as a replicated traced scalar
        (setops.setop_emit), not a cache key; union's differing cap_out
        and two-source gather make it its own program."""
        # sorted-input fast path gate, read BEFORE _setop_pair (whose dict
        # unification _replace drops the attribute; the remap preserves code
        # order, so the claim itself survives it). Single mask-free non-f64
        # column with BOTH inputs sorted ascending: run detection + a sorted
        # membership probe replace the combined canonical sort entirely
        # (ops.setops.{setop,union}_emit_sorted).
        def _sortable(t: "Table") -> bool:
            if t.column_count != 1:
                return False
            c = next(iter(t._columns.values()))
            if c.valid is not None or c.data.dtype == jnp.float64:
                return False
            return _ord.covers_prefix(
                t._ordering, t.column_names, need_canonical=False
            )

        sorted_fast = _sortable(self) and _sortable(other)
        a, b = self._setop_pair(other)
        is_union = op == "union"
        if is_union and any(
            ca.dtype != cb.dtype
            for ca, cb in zip(a._columns.values(), b._columns.values())
        ):
            # mixed-dtype schemas need the concat's per-column promotion of
            # the RESULT dtype; keep the concat+unique path for that edge
            return _concat_tables([a, b]).unique()
        lflat = a._flat_cols()
        rflat = b._flat_cols()
        nc = len(lflat)

        cap_out = a.shard_cap + b.shard_cap if is_union else a.shard_cap
        key = ("setop_union" if is_union else "setop2", nc, cap_out,
               sorted_fast)
        if sorted_fast:
            bump("ordering.setop_sorted_probe")

        def build_emit():
            def kern(dp, rep):
                (lk, rk, nl, nr) = dp
                cap_l = lk[0][0].shape[0]
                cap_r = rk[0][0].shape[0]
                if is_union:
                    emit = _s.union_emit_sorted if sorted_fast else _s.union_emit
                    idx, total, src = emit(
                        lk, rk, nl[0], nr[0], cap_l, cap_r, cap_out
                    )
                else:
                    (want_in_r,) = rep
                    emit = _s.setop_emit_sorted if sorted_fast else _s.setop_emit
                    idx, total = emit(
                        lk, rk, nl[0], nr[0], cap_l, cap_r, cap_out,
                        want_in_r,
                    )
                    src = list(lk)
                out, _ = _g_pack.pack_gather(src, idx)
                return out, _scalar(total)

            return kern

        rep = () if is_union else (jnp.asarray(op == "intersect"),)
        with span(f"setop.{op}", rows=self._rows_hint()):
            out, nout = get_kernel(self.ctx, key + ("emit",), build_emit)(
                (lflat, rflat, a.counts_dev, b.counts_dev), rep
            )
        # deferred counts: fetch + overshoot compaction happen at result
        # materialization (L3 sync budget: set ops = 0 at dispatch time)
        res = a._rebuild_cols(
            list(zip(a.column_names, a._columns.values())), out, nout, cap_out
        )
        if not is_union:
            # subtract/intersect keep a subset of LEFT rows in left order
            res = res._attach_ordering(self._ordering)._attach_stats(
                a._stats
            )
        return res

    def distributed_union(self, other: "Table") -> "Table":
        return self._dist_setop(other, "union")

    def distributed_subtract(self, other: "Table") -> "Table":
        return self._dist_setop(other, "subtract")

    def distributed_intersect(self, other: "Table") -> "Table":
        return self._dist_setop(other, "intersect")

    def _dist_setop(self, other: "Table", op: str) -> "Table":
        """Reference DoDistributedSetOperation (table.cpp:727-785): shuffle
        both tables on ALL columns — through ONE chunked-engine call, so the
        pair's exchange rounds overlap — then run the local op per shard."""
        if self.world_size == 1:
            return getattr(self, op)(other)
        a, b = self._setop_pair(other)
        # intersect/subtract are natural semi-join consumers: rows provably
        # absent from the side that decides their fate never ship (set-op
        # equality treats null == null — the sketches' null-as-value mode
        # matches, ops/sketch.py module doc)
        asf, bsf = _shuffle_pair(
            a, a.column_names, b, b.column_names,
            semi=_sketch.setop_filter_sides(op),
        )
        return getattr(asf, op)(bsf)

    # ------------------------------------------------------------------
    # unique
    # ------------------------------------------------------------------
    def unique(
        self,
        columns: Optional[Sequence[Union[str, int]]] = None,
        keep: str = "first",
        _order_col: Optional[str] = None,
    ) -> "Table":
        """Per-shard dedup (reference Unique, table.cpp:923-982).

        ``_order_col``: internal — name of a column whose VALUES define the
        first/last ordering among duplicates (instead of row position); the
        column is consumed (absent from the output). Used by
        :meth:`distributed_unique` to carry global row order across the
        shuffle."""
        names = self.column_names if columns is None else self._resolve_cols(columns)
        all_names = self.column_names
        if _order_col is not None:
            names = [n for n in names if n != _order_col]
        key_idx = tuple(all_names.index(n) for n in names)
        order_idx = all_names.index(_order_col) if _order_col is not None else -1
        out_pairs = [
            (n, c) for n, c in self._columns.items() if n != _order_col
        ]
        # lint: keyed=out_idx -- fully determined by (len(flat), order_idx),
        # both key components: out_idx is every column index except order_idx
        out_idx = tuple(all_names.index(n) for n, _ in out_pairs)
        flat = self._flat_cols()
        # Single-dispatch: dedup output is a subset of the input rows, so
        # cap_out = shard_cap is a static exact upper bound — no count
        # phase, no dispatch-time host sync (deferred count fetch);
        # selective results compact at materialization.
        cap_out = self.shard_cap
        # order-property reuse: input canonically ordered by the dedup keys
        # -> run-detect + mask compaction instead of the two canonical sorts
        # (identical output: on sorted input, run starts/ends ARE the
        # first/last occurrences, emitted in the same ascending row order)
        sorted_fast = (
            order_idx < 0
            and keep in ("first", "last")
            and _ord.covers_prefix(self._ordering, names)
        )
        if sorted_fast:
            bump("ordering.unique_run_detect")
        key = ("unique", key_idx, keep, len(flat), cap_out, order_idx,
               sorted_fast)

        def build_emit():
            def kern(dp, rep):
                (cols, counts) = dp
                n = counts[0]
                cap = cols[0][0].shape[0]
                keys = [cols[i] for i in key_idx]
                if sorted_fast:
                    idx, total = _s.unique_emit_sorted(
                        keys, n, cap, cap_out, keep
                    )
                else:
                    order_lane = None
                    if order_idx >= 0:
                        from .ops.sort import orderable_key

                        order_lane = orderable_key(cols[order_idx][0])
                    idx, total = _s.unique_emit(
                        keys, n, cap, cap_out, keep, order_lane=order_lane
                    )
                out, _ = _g_pack.pack_gather([cols[i] for i in out_idx], idx)
                return out, _scalar(total)

            return kern

        with span("unique", rows=self._rows_hint()):
            out, nout = get_kernel(self.ctx, key + ("emit",), build_emit)(
                (flat, self.counts_dev), ()
            )
        # deferred counts: fetch + overshoot compaction at materialization
        res = self._rebuild_cols(out_pairs, out, nout, cap_out)
        # dedup keeps a subset of rows in input order: descriptor survives
        # (range bounds likewise)
        return res._attach_ordering(
            self._ordering
        )._attach_stats(self._stats)

    def distributed_unique(
        self, columns: Optional[Sequence[Union[str, int]]] = None, keep: str = "first"
    ) -> "Table":
        """Reference DistributedUnique (table.cpp:984-999): shuffle on the
        key columns then local unique. A global row-id column rides the
        shuffle so keep='first'/'last' selects by ORIGINAL table order —
        multi-round exchanges do not preserve within-key arrival order (the
        reference's MPI arrival order is likewise nondeterministic; pandas
        order semantics are kept here)."""
        if self.world_size == 1:
            return self.unique(columns, keep)
        names = self.column_names if columns is None else self._resolve_cols(columns)
        rid = "__rowid__"
        while rid in self.column_names:  # never collide with user columns
            rid += "_"
        t = self.add_column(rid, self._global_rowid_column())
        shuffled = t._shuffle_impl(kind="hash", key_names=names)
        return shuffled.unique(names, keep, _order_col=rid)

    # ------------------------------------------------------------------
    # groupby
    # ------------------------------------------------------------------
    @_obstrace.op("groupby")
    def groupby(
        self,
        by: Union[str, int, Sequence[Union[str, int]]],
        agg: Dict[str, Union[str, int, Sequence[Union[str, int]]]],
        ddof: int = 1,
        quantile: float = 0.5,
        _sorted: bool = False,
        _mask=None,
        _dense: bool = True,
    ) -> "Table":
        """Per-shard groupby-aggregate (reference HashGroupBy,
        groupby/hash_groupby.cpp). ``agg`` maps value column -> op(s) from
        {sum,count,min,max,mean,var,std,nunique,quantile,median}. Output has
        the key columns (sorted key order) then one column per (col, op)
        named ``col_op`` (pycylon naming, data/table.pyx:587-648).

        Order-property reuse: when the table's ordering descriptor proves
        the rows canonically ordered by the group keys (a prior sort on
        mask-free keys, a key-order join emit, a groupby output...), the
        factorize lexsort is replaced by the run-detect pass automatically
        — the ``PipelineGroupBy`` fast path without the caller contract.

        Dense path: when every key column has a measured range (or is
        dictionary-coded) and the product of the spans is small
        (``ops.groupby.DENSE_MAX_SLOTS``), group ids are arithmetic on the
        rebased keys and every aggregate is a masked reduction a slot: no
        sort, no scatter, no gather, same output. The input decides.

        No key at all (``by=[]``, the planner's ``LazyFrame.agg``): the
        dense path with one slot, for sum, count, min, max and mean. The
        result is exactly one row a shard, also where no row passes:
        ``count`` 0 and every other aggregate null, as SQL has it.

        ``_mask`` (the planner's ``GroupBy(Filter)`` rewrite): a row mask
        as :meth:`filter` takes it; rows whose mask is false or null
        count in no aggregate and found no group, as if filtered first.
        The mask rides the dense path's reductions, and the other path's
        factorize sort as a padding class; only rows already in key order
        (no sort to ride) are filtered first. ``_dense=False`` (tests and
        measurements) keeps a call off the dense path."""
        key_names = self._resolve_cols(by)
        specs = _agg_specs(agg)
        provably_sorted = _ord.covers_prefix(self._ordering, key_names)
        dense = None
        if _dense and not (_sorted or provably_sorted):
            dense = self._dense_groupby_plan(
                key_names, [oid for _c, oid, _n in specs]
            )
        if dense is not None:
            return self._groupby_dense(key_names, specs, dense, _mask)
        if not key_names:
            raise ValueError(_KEYLESS_OPS)
        return self._groupby_segment(
            key_names, specs, ddof, quantile, _sorted, mask=_mask
        )

    def _groupby_segment(
        self, key_names, specs, ddof: int = 1, quantile: float = 0.5,
        _sorted: bool = False, stage: Optional[str] = None,
        widen: Sequence[bool] = (), merge: Optional["_PartialAgg"] = None,
        mask=None,
    ) -> "Table":
        """The sort-and-segment group-by of every shard's own rows: one
        program, ``jit_groupby``; ``specs`` are ``(column, op id, name)``.

        The two halves of :meth:`distributed_groupby`'s exchange of
        partials are this program under a ``stage`` name of their own:
        the pre-combine (``specs`` the partial states, ``widen[i]`` a
        mean's sum that adds its column in ``wide_float``) and the
        combine (``merge``: ``specs`` reduce the received state columns,
        and the caller's aggregates are finished from them in the same
        program and name the output).

        ``mask``: a row mask as :meth:`filter` takes it; a row it drops
        sorts with the padding and founds no group. Rows already in key
        order run no sort for it to ride and are filtered first."""
        provably_sorted = _ord.covers_prefix(self._ordering, key_names)
        if mask is not None and (_sorted or provably_sorted):
            return self.filter(mask)._groupby_segment(
                key_names, specs, ddof, quantile, _sorted, stage, widen, merge
            )
        m = None if mask is None else self._as_mask(mask)
        bump("groupby.factorize_path")
        # the run heads reach their slots by log-step moves, counted from
        # the rule the kernel itself follows (ops.sort.step_passes)
        bump("groupby.compact.steps", rows=self.shard_cap)
        bump("groupby.compact.passes",
             rows=len(_sort_mod.step_passes(self.shard_cap)))
        if not _sorted and provably_sorted:
            # canonical prefix order: run adjacency AND emitted group order
            # match the factorize path exactly (factorize_runs(presorted=True))
            _sorted = True
            bump("ordering.groupby_run_detect")
        # the factorize path emits groups in canonical key order by
        # construction; the run-detect path does too only when the input
        # order is provable (a caller-contracted pipeline_groupby is not)
        out_canonical = (not _sorted) or provably_sorted
        # canonical-lane fusion (ops/stats.py): the factorize lexsort's
        # [live, (null, value)*] lane stack bit-packs into fewer chained
        # passes when the key ranges are measured — identical group ids
        # (ops/sort.canonical_row_lanes). Quantized plan in the cache key.
        gb_fuse = None
        if not _sorted and _st.enabled():
            gspecs = self._fusion_specs(key_names)
            if gspecs:
                gb_fuse = _sort_mod.plan_lane_fusion(
                    gspecs, pad_bits=1, prefix_bits=0,
                    allow64=bool(jax.config.jax_enable_x64),
                )
        if gb_fuse is not None:
            bump("lane_pack.groupby_fused",
                 rows=gb_fuse.n_plain - gb_fuse.n_words)
        all_names = self.column_names
        key_idx = tuple(all_names.index(n) for n in key_names)
        val_idx = tuple(all_names.index(c) for c, _, _ in specs)
        ops_t = tuple(oid for _, oid, _ in specs)
        flat = self._flat_cols()
        # Single-dispatch: num_groups <= live rows, so cap_out = shard_cap is
        # a static exact upper bound — no count phase, NO dispatch-time host
        # sync (the count fetch defers to result materialization); selective
        # results compact there.
        cap_out = self.shard_cap
        # a value column rides the factorize sort once, whatever ops read
        # it (and once more widened, for a partial mean's sum)
        # lint: keyed=vals -- val_idx paired with ``widen``: val_idx is a
        # key component, and widen is all False unless a stage is given,
        # whose key carries vals whole
        vals = tuple(zip(val_idx, tuple(widen) or (False,) * len(val_idx)))
        ride = tuple(dict.fromkeys(vals))
        finish = None if merge is None else (merge.ops, merge.reads)
        key = (
            "groupby", key_idx, val_idx, ops_t, ddof, quantile, len(flat),
            _sorted, cap_out, gb_fuse,
        ) + ((stage, vals, finish) if stage else ()) + (
            ("mask",) if m is not None else ()
        )
        scope = (
            (lambda: jax.named_scope(stage)) if stage
            else contextlib.nullcontext
        )

        def build_emit():
            def kern(dp, rep):
                (m, cols, counts) = dp
                with scope():
                    keys, aggs, ng = _g.groupby_aggregate(
                        [cols[i] for i in key_idx],
                        [_g.widened(cols[i]) if w else cols[i]
                         for i, w in ride],
                        [(oid, ride.index(v)) for v, oid in zip(vals, ops_t)],
                        counts[0], cap_out, fuse=gb_fuse, presorted=_sorted,
                        ddof=ddof, quantile=quantile, mask=m,
                    )
                    if finish is not None:
                        aggs = _g.finish_states(*finish, aggs)
                return keys + aggs, _scalar(ng)

            return kern

        with span("groupby.emit", rows=self._rows_hint()):
            out, nout = get_kernel(self.ctx, key + ("emit",), build_emit)(
                (m, flat, self.counts_dev), ()
            )
        return self._groupby_result(
            key_names, specs if merge is None else merge.specs, out, nout,
            cap_out, out_canonical,
        )

    def _groupby_result(
        self, key_names, specs, out, nout, cap_out: int, canonical: bool,
        scope: str = "shard",
    ) -> "Table":
        """The group-by's output table from a kernel's (key columns,
        aggregate columns, group count): names, dtypes, dictionaries,
        stats and ordering descriptor, the same for either path
        (``scope`` "global" where the groups of the whole mesh lie on the
        first shard; no descriptor without a key to order by)."""
        names_src: List[Tuple[str, Column]] = [
            (n, self._columns[n]) for n in key_names
        ]
        agg_cols = []
        for (coln, oid, oname), (a, av) in zip(specs, out[len(key_names):]):
            agg_cols.append((f"{coln}_{oname}", a, av))
        cols_od: "OrderedDict[str, Column]" = OrderedDict()
        for (n, src), (d, v) in zip(names_src, out[: len(key_names)]):
            cols_od[n] = Column(d, src.dtype, v, src.dictionary)
        for cname, d, v in agg_cols:
            cols_od[cname] = Column(d, DataType.from_numpy_dtype(d.dtype), v, None)
        # deferred counts (L3 sync budget: groupby = 0 at dispatch time);
        # the group-count fetch + overshoot compaction happen at result
        # materialization
        res = Table(self.ctx, cols_od, nout, cap_out)
        res = res._attach_stats(
            {n: self._stats.get(n) for n in key_names}
        )
        if canonical and key_names:
            res._attach_ordering(Ordering(
                keys=tuple(key_names),
                ascending=(True,) * len(key_names),
                nulls_last=True, scope=scope, canonical=True,
                lexsort_exact=all(
                    self._columns[n].valid is None for n in key_names
                ),
            ))
        return res

    def _dense_groupby_plan(self, key_names, op_ids):
        """``(los, spans, key_meta)`` where the dense path applies, else
        None: every op dense, every key an integer, bool or dictionary
        column with a known range, and the slots within
        ``ops.groupby.DENSE_MAX_SLOTS``.
        A dictionary column's codes are dense by construction; any other
        key's range is measured (:meth:`ensure_stats`, cached on the
        table) and its span rounded up to a power of two, so that a
        drifting range compiles nothing."""
        if not all(o in _g.DENSE_OPS for o in op_ids):
            return None
        cols = [self._columns[n] for n in key_names]
        plain = [
            n for n, c in zip(key_names, cols) if not c.dtype.is_dictionary
        ]
        stats = self.ensure_stats(plain) if plain else {}
        los, spans, meta = [], [], []
        for n, c in zip(key_names, cols):
            if c.dtype.is_dictionary:
                # code 0 in the orderable encoding of an int32; the range
                # is known without a measurement, and kept like one
                stat = _st.dictionary_stat(len(c.dictionary))
                width = stat.hi - stat.lo + 1
                if _st.enabled() and n not in self._stats:
                    self._stats[n] = stat
            else:
                stat = stats.get(n)
                width = _g.dense_span(stat)
                if width is None:
                    return None
            cls, lo = stat.cls, stat.lo
            los.append((np.uint64 if _st.is64(cls) else np.uint32)(lo))
            spans.append(width)
            meta.append((cls, str(c.data.dtype)))
        slots = _g.dense_slots(spans, [c.valid is not None for c in cols])
        if slots > _g.DENSE_MAX_SLOTS:
            return None
        return tuple(los), tuple(spans), tuple(meta)

    def _groupby_dense(
        self, key_names, specs, plan, mask, combine: bool = False
    ) -> "Table":
        """The dense group-by: one program, ``jit_groupby_dense``.

        ``combine`` (a mesh; :meth:`distributed_groupby`): every shard
        reduces its own rows to the partial slot table, the tables are
        combined over the mesh axis inside the same program
        (``ops.groupby.dense_combine``, stage ``groupby.combine``) and the
        groups are emitted once, on the first shard, in key order; no row
        leaves its shard. Without it each shard emits the groups of its
        own rows (what follows a shuffle, or one shard)."""
        combine = combine and self.world_size > 1
        bump("groupby.dense_path")
        los, spans, meta = plan
        all_names = self.column_names
        key_idx = tuple(all_names.index(n) for n in key_names)
        val_idx = tuple(all_names.index(c) for c, _, _ in specs)
        ops_t = tuple(oid for _, oid, _ in specs)
        flat = self._flat_cols()
        nullable = tuple(self._columns[n].valid is not None for n in key_names)
        slots = _g.dense_slots(spans, nullable)
        cap_out = round_cap(slots)
        m = None if mask is None else self._as_mask(mask)
        key = (
            "groupby_dense", key_idx, val_idx, ops_t, len(flat), spans, meta,
            nullable, m is not None,
        ) + (("combine",) if combine else ())
        axis = self.ctx.axis_name
        if combine:
            bump("groupby.partial_path")
            bump("groupby.partial.rows", rows=self._rows_hint() or 0)
            bump("groupby.combine.slots", rows=slots)

        def build_emit():
            def kern(dp, rep):
                (m, cols, counts) = dp
                keys = [cols[i] for i in key_idx]
                lo = list(rep)
                gid = _g.dense_group_ids(
                    keys, lo, spans, counts[0], m, cap=cols[0][0].shape[0]
                )
                rows = _g.dense_rows(gid, slots)
                # without a key the one slot is emitted whatever it holds
                empty = [
                    not keys or cols[vi][1] is not None for vi in val_idx
                ]
                if combine:
                    rows, parts = _g.dense_combine(rows, [
                        (oid, *_g.dense_partial(oid, *cols[vi], gid, slots))
                        for vi, oid in zip(val_idx, ops_t)
                    ], axis)
                    aggs = [
                        _g.dense_finalize(*part, e)
                        for part, e in zip(parts, empty)
                    ]
                else:
                    # one shard holds every row of its groups: partial
                    # and finalize back to back, an aggregate at a time
                    aggs = [
                        _g.dense_aggregate(oid, *cols[vi], gid, slots, e)
                        for vi, oid, e in zip(val_idx, ops_t, empty)
                    ]
                out, ng = _g.dense_emit(
                    rows, aggs, meta, lo, spans, nullable, cap_out
                )
                if combine:
                    # every shard holds the result; the first one emits it
                    ng = jnp.where(jax.lax.axis_index(axis) == 0, ng, 0)
                return out, _scalar(ng)

            return kern

        with span("groupby.emit", rows=self._rows_hint()):
            out, nout = get_kernel(self.ctx, key, build_emit)(
                (m, flat, self.counts_dev), los
            )
        return self._groupby_result(
            key_names, specs, out, nout, cap_out, True,
            scope="global" if combine else "shard",
        )

    @_obstrace.op("distributed_groupby")
    def distributed_groupby(
        self,
        by: Union[str, int, Sequence[Union[str, int]]],
        agg: Dict[str, Union[str, Sequence[str]]],
        _mask=None,
        **kw,
    ) -> "Table":
        """Group-by over the whole mesh (reference DistributedHashGroupBy,
        groupby/groupby.cpp:33-91), one of three ways; the input decides.

        **Combined in place.** Where the dense plan applies
        (:meth:`_dense_groupby_plan`: every op one of sum, count, min,
        max, mean, whatever the ops a column; every key dictionary-coded
        or an integer or bool with a measured range; at most
        ``ops.groupby.DENSE_MAX_SLOTS`` slots; also no key at all), every
        shard reduces its own rows to a partial slot table (a row count
        and a sum, minimum or maximum a slot; a mean as its sum and
        count), the tables are combined across the mesh inside the same
        program (counts and integer sums by ``psum``; float sums, minima
        and maxima gathered and folded in shard order, so the result is
        the same bits run to run) and finalized once. No row crosses the
        mesh: what moves is slots x aggregates numbers. The groups lie on
        the FIRST shard in key order and the other shards hold no row, so
        ``row_count``, ``to_pydict`` and a following sort see one copy
        (the ordering descriptor's scope is "global"). The reference's
        scalar aggregates (compute/aggregates.cpp:26-137) are this: local
        reduce, AllReduce.

        **Partial, shuffle, combine.** The same ops (sum, count, min, max,
        mean; any number of ops a column) over keys the dense plan
        declines (many values, no known range, a float key)
        (:meth:`_groupby_exchange`): every shard reduces its own rows to
        one partial row a group by the sort-and-segment group-by (stage
        ``groupby.partial``; a sum as its sum, a count as its count, a
        minimum and a maximum as themselves, a mean as its sum and its
        count, each state computed once however many ops read it); the
        partial rows' count is fetched and they are cut to ``round_cap``
        of the fullest shard's (they lie first in their shard, in key
        order: a slice, no compaction sort); a hash shuffle on the keys
        carries them at that capacity; each shard combines the partial
        rows it received (sums of sums and of counts, minima of minima,
        maxima of maxima) and finishes the aggregates (mean = sum over
        count) in one program (stage ``groupby.merge``). Every shard emits
        the groups whose keys hash to it, each group once over the mesh,
        named and typed as :meth:`groupby` names and types them. A group's
        partial rows are added in source-shard order (the pack's sort by
        destination and the factorize sort are both stable), so a query
        gives the same bits run to run. Where every shard holds rows of
        every group, world x groups rows cross the mesh and not the input.
        The reference pre-combines for {SUM, MIN, MAX} alone, one op a
        column (:24-31, 57-67).

        **Raw rows.** var / std / nunique / quantile / median have no
        partial state here: the input rows are hash-shuffled on the keys
        and grouped where they land.

        ``_mask`` (the planner): a row mask as :meth:`groupby` takes it;
        it rides the combined reductions and the pre-combine's sort, and
        filters the rows in front of a shuffle of raw rows. Counters:
        ``groupby.partial_path`` (the first two routes) /
        ``groupby.raw_shuffle_path``, ``groupby.precombine.*`` (the
        second)."""
        if self.world_size == 1:
            return self.groupby(by, agg, _mask=_mask, **kw)
        key_names = self._resolve_cols(by)
        specs = _agg_specs(agg)
        if kw.get("_dense", True) and not kw.get("_sorted", False):
            dense = self._dense_groupby_plan(
                key_names, [oid for _c, oid, _n in specs]
            )
            if dense is not None:
                return self._groupby_dense(
                    key_names, specs, dense, _mask, combine=True
                )
        if not key_names:
            raise ValueError(_KEYLESS_OPS)
        return self._groupby_exchange(key_names, agg, _mask, **kw)

    def _groupby_exchange(self, key_names, agg, _mask=None, **kw) -> "Table":
        """The group-by of a mesh whose rows of a group must meet: every
        shard ends up owning the groups whose keys hash to it (what a hash
        shuffle on the keys and a local group-by give; the planner's
        lowering calls this for exactly that pair of nodes).

        Ops of ``ops.groupby.PARTIAL_OPS`` alone: pre-combine (stage
        ``groupby.partial``), count, exchange of the partial rows at
        ``round_cap`` of the fullest shard's count, combine and finish
        (stage ``groupby.merge``). Any other op: the rows themselves are
        shuffled and grouped where they land."""
        specs = _agg_specs(agg)
        if not all(oid in _g.PARTIAL_OPS for _c, oid, _n in specs):
            bump("groupby.raw_shuffle_path")
            t = self if _mask is None else self.filter(_mask)
            shuffled = t._shuffle_impl(kind="hash", key_names=key_names)
            return shuffled.groupby(key_names, agg, **kw)
        bump("groupby.partial_path")
        plan = _PartialAgg.of(self, specs)
        pre = plan.pre_combine(
            self, key_names, _mask, kw.get("_sorted", False)
        )
        # the partial rows lie first in their shards, in key order: the
        # count (the exchange plans on the host and would fetch it anyway)
        # and a slice are all it takes to ship them at their own capacity
        # and not at the input's
        counts = pre.row_counts
        fullest = int(counts.max())
        pre = pre._compact(round_cap(fullest))
        bump("groupby.precombine.rows_in", rows=self._rows_hint() or 0)
        bump("groupby.precombine.rows_out", rows=int(counts.sum()))
        bump("groupby.precombine.fullest", rows=fullest)
        bump("groupby.precombine.slots", rows=pre.shard_cap)
        shuffled = pre._shuffle_impl(kind="hash", key_names=key_names)
        return plan.combine(shuffled, key_names)

    def pipeline_groupby(
        self,
        by: Union[str, int, Sequence[Union[str, int]]],
        agg: Dict[str, Union[str, int, Sequence[Union[str, int]]]],
        **kw,
    ) -> "Table":
        """Groupby over input ALREADY sorted by the key columns (reference
        PipelineGroupBy, groupby/pipeline_groupby.cpp:30-90): a single
        run-detection pass replaces the factorize lexsort. The caller is
        responsible for sortedness, as in the reference."""
        return self.groupby(by, agg, _sorted=True, **kw)

    def distributed_pipeline_groupby(
        self,
        by: Union[str, int, Sequence[Union[str, int]]],
        agg: Dict[str, Union[str, int, Sequence[Union[str, int]]]],
        **kw,
    ) -> "Table":
        """Reference DistributedPipelineGroupBy (groupby/groupby.cpp:93-137):
        range-partition shuffle on the keys (global key order across shards),
        local sort, then the sorted-run pipeline groupby."""
        key_names = self._resolve_cols(by)
        if self.world_size == 1:
            return self.sort(key_names).pipeline_groupby(by, agg, **kw)
        shuffled = self._shuffle_impl(kind="range", key_names=key_names)
        return shuffled.sort(key_names).pipeline_groupby(by, agg, **kw)

    # ------------------------------------------------------------------
    # scalar aggregates (reference compute::Sum/Count/Min/Max,
    # compute/aggregates.cpp:26-137 — local arrow::compute + AllReduce; here
    # a global masked reduction over the sharded array: XLA inserts the
    # cross-shard collective automatically)
    # ------------------------------------------------------------------
    def _masked_col(self, column: Union[str, int]):
        name = self._resolve_cols(column)[0]
        col = self._columns[name]
        live = self._live_mask()
        ok = live if col.valid is None else (live & col.valid)
        return col, ok

    def sum(self, column: Union[str, int]):
        col, ok = self._masked_col(column)
        d = col.data
        if jnp.issubdtype(d.dtype, jnp.integer):
            d = d.astype(jnp.int64)
        return jnp.sum(jnp.where(ok, d, jnp.zeros_like(d))).item()

    def count(self, column: Union[str, int]) -> int:
        _, ok = self._masked_col(column)
        return int(jnp.sum(ok).item())

    def min(self, column: Union[str, int]):
        col, ok = self._masked_col(column)
        d = col.data
        if jnp.issubdtype(d.dtype, jnp.floating):
            big = jnp.asarray(jnp.inf, d.dtype)
        else:
            big = jnp.asarray(jnp.iinfo(d.dtype).max, d.dtype)
        out = jnp.min(jnp.where(ok, d, big)).item()
        return self._decode_scalar(col, out)

    def max(self, column: Union[str, int]):
        col, ok = self._masked_col(column)
        d = col.data
        if jnp.issubdtype(d.dtype, jnp.floating):
            small = jnp.asarray(-jnp.inf, d.dtype)
        else:
            small = jnp.asarray(jnp.iinfo(d.dtype).min, d.dtype)
        out = jnp.max(jnp.where(ok, d, small)).item()
        return self._decode_scalar(col, out)

    def mean(self, column: Union[str, int]):
        col, ok = self._masked_col(column)
        d = col.data.astype(jnp.float64)
        s = jnp.sum(jnp.where(ok, d, 0.0))
        c = jnp.sum(ok)
        return (s / jnp.maximum(c, 1)).item()

    def minmax(self, column: Union[str, int]):
        """Fused MinMax (reference compute/aggregates.cpp:82-121: one pass +
        one AllReduce for both bounds). Both reductions live in ONE jitted
        program — XLA fuses them into a single pass over the column and a
        single collective pair — and both scalars come back in ONE host
        fetch, vs two programs + two fetches for separate min()/max()."""
        col, ok = self._masked_col(column)
        d = col.data
        if jnp.issubdtype(d.dtype, jnp.floating):
            big = jnp.asarray(jnp.inf, d.dtype)
            small = jnp.asarray(-jnp.inf, d.dtype)
        else:
            info = jnp.iinfo(d.dtype)
            big = jnp.asarray(info.max, d.dtype)
            small = jnp.asarray(info.min, d.dtype)
        # lint: sync=device -- the np.asarray fetches the fused kernel's
        # [2] result pair: the ONE deliberate host sync of this reducer
        both = np.asarray(_minmax_kernel(d, ok, big, small))
        return (
            self._decode_scalar(col, both[0]),
            self._decode_scalar(col, both[1]),
        )

    @staticmethod
    def _decode_scalar(col: Column, value):
        if col.dtype.is_dictionary:
            return col.dictionary[int(value)]
        return value

    # ------------------------------------------------------------------
    # elementwise / pandas-flavored utilities (pycylon table.pyx surface)
    # ------------------------------------------------------------------
    def applymap(self, fn) -> "Table":
        """Per-element Python UDF over every column (reference pycylon
        ``Table.applymap``, python/pycylon/data/table.pyx:2222-2240).
        Arbitrary host callables can't be traced, so each shard round-trips
        through the host and is re-encoded in place — sharding is preserved
        and string-valued UDFs work (results re-infer their encoding).
        Device-traceable fns belong on :func:`compute.map_columns`."""
        shards: List[Dict[str, Any]] = []
        for s in range(self.world_size):
            data: Dict[str, Any] = {}
            for name in self.column_names:
                d, v = self._host_physical_shard(name, s)
                vals = self._columns[name].decode_host(d, v)
                data[name] = np.asarray([fn(x) for x in vals], dtype=object)
            shards.append(data)
        if self.world_size == 1:
            out = Table.from_pydict(self.ctx, shards[0])
        else:
            out = Table.from_shards(self.ctx, shards)
        out.index_name = self.index_name  # row-preserving op: index survives
        return out

    def isnull(self) -> "Table":
        cols = OrderedDict()
        for n, c in self._columns.items():
            nulls = (~c.valid) if c.valid is not None else jnp.zeros(c.data.shape, bool)
            cols[n] = Column(nulls, DataType(Type.BOOL), None, None)
        return self._replace(columns=cols)

    def notnull(self) -> "Table":
        cols = OrderedDict()
        for n, c in self._columns.items():
            ok = c.valid if c.valid is not None else jnp.ones(c.data.shape, bool)
            cols[n] = Column(ok, DataType(Type.BOOL), None, None)
        return self._replace(columns=cols)

    def fillna(self, value) -> "Table":
        cols = OrderedDict()
        for n, c in self._columns.items():
            if c.valid is None:
                cols[n] = c
                continue
            if c.dtype.is_dictionary:
                # add fill value to dictionary if missing (width-promoting)
                dic, pos, inserted = _dict_insert(c.dictionary, value)
                if inserted:
                    remap = jnp.asarray(
                        np.searchsorted(dic, c.dictionary).astype(np.int32)
                    )
                    data = remap[jnp.clip(c.data, 0, len(c.dictionary) - 1)]
                else:
                    data = c.data
                filled = jnp.where(c.valid, data, jnp.int32(pos))
                cols[n] = Column(filled, c.dtype, None, dic)
            else:
                filled = jnp.where(c.valid, c.data, jnp.asarray(value, c.data.dtype))
                cols[n] = Column(filled, c.dtype, None, None)
        return self._replace(columns=cols)

    def astype(self, dtype_map: Union[Any, Dict[str, Any]]) -> "Table":
        """Column dtype conversion incl. strings both ways (pycylon astype,
        data/table.pyx:2411): string->numeric parses the DICTIONARY on the
        host and keeps the device codes; numeric->string builds a dictionary
        from the column's distinct values."""
        if not isinstance(dtype_map, dict):
            dtype_map = {n: dtype_map for n in self.column_names}
        cols = OrderedDict(self._columns)
        for n, dt in dtype_map.items():
            c = self._columns[n]
            want_str = dt in (str, "str", "string", "object") or (
                isinstance(dt, np.dtype) and dt.kind in ("U", "S", "O")
            )
            if c.dtype.is_dictionary:
                if want_str:
                    cols[n] = c
                    continue
                # string -> numeric: parse dictionary values (host, O(|dict|))
                # and remap the device codes through the parsed lookup
                nd = np.dtype(dt)
                parsed = c.dictionary.astype(nd)
                lookup = jnp.asarray(parsed)
                data = lookup[jnp.clip(c.data, 0, len(parsed) - 1)]
                cols[n] = Column(data, DataType.from_numpy_dtype(nd), c.valid, None)
            elif want_str:
                # numeric -> string: distinct values become the dictionary
                data_np, valid_np = self._host_physical(n)
                strs = np.array([str(v) for v in data_np], object)
                enc, valid2, dtype2, dic = Column.encode_host(strs)
                if valid_np is not None:
                    valid2 = valid_np if valid2 is None else (valid2 & valid_np)
                cols[n] = _host_col_like(self, enc, valid2, dtype2, dic)
            else:
                nd = np.dtype(dt)
                cols[n] = Column(
                    c.data.astype(nd), DataType.from_numpy_dtype(nd), c.valid, None
                )
        return self._replace(columns=cols)

    def where(self, cond, other=None) -> "Table":
        """pandas-style where (pycylon table.pyx:1683-1999 surface): keep
        each value where ``cond`` is True, else replace with ``other``
        (null when ``other`` is None). Shape is preserved."""
        m = self._as_mask(cond)
        live = self._live_mask()
        keep = m & live
        cols = OrderedDict()
        for n, c in self._columns.items():
            if other is None:
                v = keep if c.valid is None else (keep & c.valid)
                cols[n] = Column(c.data, c.dtype, v, c.dictionary)
            elif c.dtype.is_dictionary:
                dic, pos, inserted = _dict_insert(c.dictionary, other)
                if inserted:
                    remap = jnp.asarray(
                        np.searchsorted(dic, c.dictionary).astype(np.int32)
                    )
                    data = remap[jnp.clip(c.data, 0, len(c.dictionary) - 1)]
                else:
                    data = c.data
                filled = jnp.where(keep, data, jnp.int32(pos))
                v = None if c.valid is None else jnp.where(keep, c.valid, True)
                cols[n] = Column(filled, c.dtype, v, dic)
            else:
                filled = jnp.where(keep, c.data, jnp.asarray(other, c.data.dtype))
                v = None if c.valid is None else jnp.where(keep, c.valid, True)
                cols[n] = Column(filled, c.dtype, v, None)
        return self._replace(columns=cols)

    def mask(self, cond, other=None) -> "Table":
        """pandas-style mask: replace where cond is True (inverse of where)."""
        m = self._as_mask(cond)
        return self.where(~m, other)

    def __getitem__(self, key):
        """pycylon Table __getitem__ (data/table.pyx:1066-1223): column name /
        list -> projection; boolean mask -> filter; slice -> row range."""
        if isinstance(key, str):
            return self.project([key])
        if isinstance(key, (list, tuple)) and all(isinstance(k, str) for k in key):
            return self.project(list(key))
        if isinstance(key, slice):
            start, stop, step = key.indices(self.row_count)
            return self.take(np.arange(start, stop, step))
        return self.filter(key)

    def __setitem__(self, key, value) -> None:
        """pycylon Table __setitem__: ``t['c'] = array/scalar`` adds or
        replaces a column; ``t[bool_mask] = scalar`` sets every (numeric)
        cell of the masked rows (data/table.pyx mask-__setitem__)."""
        self._built_index = None  # in-place mutation invalidates loc cache
        self._ordering = None  # ...and any sortedness claim
        self._stats = {}  # ...and any range-stats claim (lane packing)
        if isinstance(key, str):
            if np.isscalar(value):
                value = np.full(self.row_count, value)
            if isinstance(value, Column):
                col = value
            else:
                enc, valid, dtype, dic = Column.encode_host(np.asarray(value))
                col = _host_col_like(self, enc, valid, dtype, dic)
            new = self.add_column(key, col)
            self._columns = new._columns
            return
        masked = self.mask(key, value)
        self._columns = masked._columns

    def __bool__(self) -> bool:
        # __eq__ returns an elementwise Table (pandas semantics); plain
        # truthiness would then silently misanswer `t == u` / `t in list` —
        # raise like pandas does
        raise ValueError(
            "The truth value of a Table is ambiguous; use Table.equals() or "
            "row_count"
        )

    # comparison / arithmetic operators (pycylon table.pyx:1224-1656); the
    # heavy lifting (dictionary-aware compare, masks) lives in compute.py
    def _cmp(self, other, op):
        from . import compute as _cc

        return _cc.table_compare_op(self, other, op)

    def __eq__(self, other):  # noqa: A003 — pycylon Table semantics
        return self._cmp(other, _op.eq)

    def __ne__(self, other):
        return self._cmp(other, _op.ne)

    def __lt__(self, other):
        return self._cmp(other, _op.lt)

    def __le__(self, other):
        return self._cmp(other, _op.le)

    def __gt__(self, other):
        return self._cmp(other, _op.gt)

    def __ge__(self, other):
        return self._cmp(other, _op.ge)

    def __hash__(self):  # __eq__ returns a Table; keep identity hashing
        return id(self)

    def _math(self, op, other):
        from . import compute as _cc

        return _cc.math_op(self, op, other)

    def __add__(self, other):
        return self._math("add", other)

    def __radd__(self, other):
        return self._math("add", other)

    def __sub__(self, other):
        return self._math("sub", other)

    def __mul__(self, other):
        return self._math("mul", other)

    def __rmul__(self, other):
        return self._math("mul", other)

    def __truediv__(self, other):
        from . import compute as _cc

        return _cc.division_op(self, "truediv", other)

    def __floordiv__(self, other):
        from . import compute as _cc

        return _cc.division_op(self, "floordiv", other)

    def __neg__(self):
        from . import compute as _cc

        return _cc.neg(self)

    def __invert__(self):
        from . import compute as _cc

        return _cc.invert(self)

    def __and__(self, other):
        return self._math(_op.and_, other)

    def __or__(self, other):
        return self._math(_op.or_, other)

    def iterrows(self):
        """Yield (index_value, row OrderedDict) per live row — host-side
        generator (pycylon iterrows, data/table.pyx:2402)."""
        host = self.to_pydict()
        names = self.column_names
        idx_vals = (
            host[self.index_name]
            if self.index_name is not None
            else np.arange(self.row_count)
        )
        for i in range(self.row_count):
            yield idx_vals[i], OrderedDict((n, host[n][i]) for n in names)

    def equals(self, other: "Table", ordered: bool = True) -> bool:
        """Content equality WITHOUT gathering the global table.

        ordered=True: device-side row-for-row compare (falls back to a host
        compare only when the two tables' physical layouts differ).
        ordered=False: exact multiset compare — each table is reduced to
        (distinct row, multiplicity) via groupby-count, and the counted
        tables are set-compared by two-way subtract. Stronger than the
        reference's Subtract-emptiness check (test_utils.hpp:37-59), which
        ignores duplicate multiplicities.
        """
        if self.column_names != other.column_names or self.row_count != other.row_count:
            return False
        if ordered:
            if (
                (self._row_counts == other._row_counts).all()
                and self._shard_cap == other._shard_cap
            ):
                return self._device_equal(other)
            a = self.to_pandas()
            b = other.to_pandas()
            try:
                import pandas.testing as pdt

                pdt.assert_frame_equal(a, b, check_dtype=False)
                return True
            except AssertionError:
                return False
        a = self._row_multiset()
        b = other._row_multiset()
        if a.row_count != b.row_count:
            return False
        return (
            a.distributed_subtract(b).row_count == 0
            and b.distributed_subtract(a).row_count == 0
        )

    def _device_equal(self, other: "Table") -> bool:
        """Row-for-row device compare of identically laid out tables: null
        rows compare equal regardless of payload; float NaN == NaN."""
        a, b = _unify_dict_pair(self, other, self.column_names, other.column_names)
        live = a._live_mask()
        ok = True
        for n in a.column_names:
            ca, cb = a._columns[n], b._columns[n]
            if ca.dtype.is_dictionary != cb.dtype.is_dictionary:
                return False
            va, vb = ca.valid_mask(), cb.valid_mask()
            same_valid = (va == vb) | ~live
            same = (ca.data == cb.data)
            if jnp.issubdtype(ca.data.dtype, jnp.floating):
                same = same | (jnp.isnan(ca.data) & jnp.isnan(cb.data))
            same = same | ~live | ~va
            ok = ok and bool(jnp.all(same_valid & same))
        return ok

    def _row_multiset(self) -> "Table":
        """(distinct row, multiplicity) table: groupby-count over ALL
        columns (a never-null ones column carries the count)."""
        w = "__row_weight__"
        ones = Column(
            jnp.ones(self._shard_cap * self.world_size, jnp.int32),
            DataType(Type.INT32),
            None,
            None,
        )
        t = self.add_column(w, ones)
        return t.distributed_groupby(self.column_names, {w: "count"})

    # ------------------------------------------------------------------
    # indexing (reference indexing/ subsystem; pycylon set_index/loc/iloc
    # surface, data/table.pyx:2057-2333)
    # ------------------------------------------------------------------
    def set_index(self, column: Union[str, int], drop: bool = False) -> "Table":
        """Designate a column as the index (reference Set_Index,
        table.hpp; HashIndex build indexing/index_utils.cpp). ``drop`` is
        rejected: the index IS a column here."""
        if drop:
            raise ValueError("drop=True unsupported: the index is a live column")
        name = self._resolve_cols(column)[0]
        t = self._replace()
        t.index_name = name
        return t._attach_ordering(self._ordering)

    def reset_index(self) -> "Table":
        t = self._replace()
        t.index_name = None
        return t._attach_ordering(self._ordering)

    @staticmethod
    def concat(
        tables: Sequence["Table"],
        axis: int = 0,
        join: str = "inner",
        algorithm: str = "sort",
        distributed: bool = False,
    ) -> "Table":
        """Reference Table.concat (table.pyx:2334-2400): axis=0 row-stacks
        same-schema tables (the reference routes to Merge); axis=1 joins
        successive tables on their index column. Functional — inputs are
        never mutated (the reference mutates its inputs' indexes in place).

        Tables with a RangeIndex (no index column) join on global row
        number, matching pandas' align-on-index semantics for the default
        index."""
        tables = list(tables)
        if not tables:
            raise ValueError("need at least one table")
        if any(not isinstance(t, Table) for t in tables):
            raise ValueError("concat expects Tables")
        if axis == 0:
            return tables[0] if len(tables) == 1 else _concat_tables(tables)
        if axis != 1:
            raise ValueError(f"invalid axis {axis}, must be 0 or 1")

        tmp_key = "__concat_index__"
        tmp_rkey = "__concat_rkey__"
        for t in tables:
            if tmp_key in t.column_names or tmp_rkey in t.column_names:
                raise ValueError(
                    f"column names {tmp_key}/{tmp_rkey} are reserved by concat"
                )

        def keyed(t: "Table") -> Tuple["Table", str, bool]:
            if t.index_name is not None:
                return t, t.index_name, False
            return t.add_column(tmp_key, t._global_rowid_column()), tmp_key, True

        res, res_key, res_tmp = keyed(tables[0])
        for i, other in enumerate(tables[1:], start=1):
            o, o_key, _ = keyed(other)
            # the right key rides under a RESERVED name so the drop below can
            # never hit a user column that happens to collide with it
            o = o.rename({o_key: tmp_rkey})
            use_dist = distributed and res.world_size > 1
            join_fn = res.distributed_join if use_dist else res.join
            # per-iteration suffix: with 3+ tables sharing a column name, a
            # fixed "_y" would collide on the second join and silently
            # overwrite the middle table's column in the OrderedDict
            res = join_fn(
                o,
                how=join,
                left_on=[res_key],
                right_on=[tmp_rkey],
                suffixes=("", "_y" if i == 1 else f"_y{i}"),
                algorithm="sort" if algorithm not in ("sort", "hash") else algorithm,
            )
            if join in ("right", "outer", "fullouter", "full_outer"):
                # coalesce the index: right-only rows carry their values in
                # the right key column (the join never merges key columns)
                lcol = res._columns[res_key]
                rcol = res._columns[tmp_rkey]
                prefer_r = join == "right"
                a, b = (rcol, lcol) if prefer_r else (lcol, rcol)
                a_ok = a.valid if a.valid is not None else jnp.ones(
                    a.data.shape, bool
                )
                data = jnp.where(a_ok, a.data, b.data)
                valid = (
                    None
                    if a.valid is None or b.valid is None
                    else (a.valid | b.valid)
                )
                cols = OrderedDict(res._columns)
                # jnp.where may promote (int32 left index vs int64 right):
                # derive the declared dtype from the promoted buffer, keeping
                # the Column data-matches-physical-dtype invariant
                out_dt = (
                    lcol.dtype
                    if lcol.dtype.is_dictionary
                    else DataType.from_numpy_dtype(np.dtype(data.dtype))
                )
                cols[res_key] = Column(data, out_dt, valid, lcol.dictionary)
                res = res._replace(columns=cols)
            res = res.drop([tmp_rkey])
        if res_tmp:
            res = res.drop([res_key]) if res_key in res.column_names else res
        elif res_key in res.column_names:
            res = res.set_index(res_key)
        return res

    @property
    def index(self):
        from .indexing import ColumnIndex, RangeIndex

        if self.index_name is None:
            return RangeIndex(self.row_count)
        return ColumnIndex(self.index_name)

    def get_index(self):
        """Alias of :attr:`index` (reference table.pyx:2252 GetIndex)."""
        return self.index

    @property
    def context(self) -> CylonContext:
        """The mesh context (reference table.pyx ``context`` property)."""
        return self.ctx

    def isna(self) -> "Table":
        """Alias of :meth:`isnull` (reference table.pyx isna)."""
        return self.isnull()

    def notna(self) -> "Table":
        """Alias of :meth:`notnull` (reference table.pyx notna)."""
        return self.notnull()

    @staticmethod
    def merge(tables: Sequence["Table"]) -> "Table":
        """Row-stack same-schema tables (reference Table.merge,
        table.pyx:2300-2330 / C++ Merge, table.cpp:267-289). Alias of
        :meth:`Table.concat` axis=0 — one source of truth for the
        single-table/validation handling."""
        return Table.concat(tables, axis=0)

    def to_csv(self, path, csv_write_options=None) -> None:
        """Write CSV (reference table.pyx to_csv; per-rank when given a
        list of world_size paths)."""
        from .io.csv import write_csv

        write_csv(self, path, csv_write_options)

    def clear(self) -> None:
        """Drop this table's column references (reference Table.Clear,
        table.pyx:2290). Device buffers free once no other table shares
        them — XLA buffers are refcounted, so there is no manual
        retain/release cycle to manage (the reference's
        retain_memory/is_retain have no analog: memory ownership is
        always the runtime's)."""
        self._columns = OrderedDict()
        self._row_counts = np.zeros_like(self._row_counts)
        self._counts_dev = None
        self.index_name = None
        self._ordering = None
        self._stats = {}
        self._built_index = None  # the loc cache pins host copies otherwise

    def build_index(self, kind: str = "hash"):
        """Build (once) and cache a value->positions lookup over the index
        column; subsequent ``loc`` calls reuse it (reference IndexUtil::Build
        + HashIndex, indexing/index_utils.cpp / index.hpp:82). ``kind`` is
        'hash' (sorted probe, O(log n) lookups) or 'linear' (scan)."""
        from .indexing import HashIndex, LinearIndex

        cached = getattr(self, "_built_index", None)
        if cached is not None and cached[0] == (kind, self.index_name):
            return cached[1]
        if kind == "hash":
            idx = HashIndex(self)
        elif kind == "linear":
            idx = LinearIndex(self)
        else:
            raise ValueError(f"unknown index kind {kind!r}")
        self._built_index = ((kind, self.index_name), idx)
        return idx

    @property
    def loc(self):
        from .indexing import LocIndexer

        return LocIndexer(self)

    @property
    def iloc(self):
        from .indexing import ILocIndexer

        return ILocIndexer(self)

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------
    def _resolve_cols(self, spec) -> List[str]:
        if isinstance(spec, (str, int)):
            spec = [spec]
        names = []
        for s in spec:
            names.append(self.column_names[s] if isinstance(s, int) else s)
        missing = [n for n in names if n not in self._columns]
        if missing:
            raise KeyError(f"unknown columns {missing}")
        return names

    @staticmethod
    def _resolve_asc(ascending, k) -> Tuple[bool, ...]:
        if isinstance(ascending, bool):
            return tuple([ascending] * k)
        return tuple(ascending)

    def _resolve_join_keys(self, other, on, left_on, right_on):
        if on is not None:
            names = self._resolve_cols(on)
            return names, names
        if left_on is None or right_on is None:
            raise ValueError("join requires `on` or both `left_on`/`right_on`")
        return self._resolve_cols(left_on), other._resolve_cols(right_on)


# ----------------------------------------------------------------------
# the chunked, compute-overlapped shuffle engine
# ----------------------------------------------------------------------

class _ShuffleSpec(NamedTuple):
    """One table's shuffle request for :func:`_shuffle_many`.

    The ``sketch`` fields carry the semi-join filter (ops/sketch.py): when
    ``sketch`` is a combined global sketch array (built by
    :func:`_pair_sketches`), the count and pack kernels probe the shuffle
    key columns against row ``probe_row`` of its per-shard [S, L] view and
    rows that provably have no partner on the other side are never packed
    — the payload collective ships only the survivors."""

    table: "Table"
    kind: str
    key_names: Tuple[str, ...]
    asc0: bool = True
    num_bins: int = 0
    task_map: Optional[np.ndarray] = None
    byte_budget: Optional[int] = None
    sketch: Optional[jax.Array] = None
    probe_row: int = 0
    use_range: bool = False
    # spill tiering (parallel/spill.py): ``sink`` streams the received
    # rows into a caller-owned host sink (``accept(table, shard_cols,
    # counts)``) instead of materializing a device result — the unified
    # out-of-core ingestion path; ``spill_tier`` forces the tier for this
    # shuffle (None = choose_tier's measured decision)
    sink: Optional[object] = None
    spill_tier: Optional[int] = None


def _shuffle_state(spec: "_ShuffleSpec") -> dict:
    """Static per-table state: partition-id closure, cache keys, the lane
    plan, and the three phase-kernel builders."""
    t = spec.table
    ctx = t.ctx
    world = ctx.world_size
    all_names = t.column_names
    key_idx = tuple(all_names.index(n) for n in spec.key_names)
    flat = t._flat_cols()
    khash = tuple(t._key_hash_cols(spec.key_names))
    ax = ctx.axis_name
    nb = spec.num_bins if spec.num_bins else 16 * world
    kind, asc0, task_map = spec.kind, spec.asc0, spec.task_map
    task_map_dev = (
        jnp.asarray(np.asarray(task_map, np.int32))
        if task_map is not None
        else None
    )

    def compute_pid(cols, kcols, n):
        if kind == "hash":
            return _p.hash_partition_ids(kcols, n, world)
        if kind == "task":
            # rows already carry logical task ids in the key column; route
            # task t to worker task_map[t] (reference LogicalTaskPlan
            # task->worker mapping, arrow_task_all_to_all.h:23-40)
            tasks, _ = cols[key_idx[0]]
            cap = tasks.shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < n
            wid = task_map_dev[jnp.clip(tasks, 0, len(task_map) - 1)]
            return jnp.where(live, wid, world).astype(jnp.int32)
        keys = [cols[i] for i in key_idx]
        return _p.range_partition_ids(
            keys[0], n, world, num_bins=nb, axis_name=ax, ascending=asc0
        )

    tm_key = (
        tuple(np.asarray(task_map).tolist()) if task_map is not None else None
    )
    plan_sig = tuple(_g_pack.lane_plan(flat))
    semi = spec.sketch is not None
    # range-stats measurement rides the count pass (ops/stats.py): the
    # count kernel touches every row anyway, so every statable column's
    # orderable min/max comes back in the ONE existing count fetch — the
    # wire-narrowing plan and downstream consumers (sort/groupby/join
    # fusion on the shuffle output) get global bounds for free
    stats_on = _st.enabled()
    stat_cols = tuple(
        ci for ci, (d, _v) in enumerate(flat)
        if stats_on and _st.enc_class(d.dtype) is not None
    )
    # quantized float wire tier (ops/quant.py): payload float columns may
    # ride lossy block-scaled codecs under the per-context tolerance —
    # join/groupby KEY columns are never quantized (exact identity is the
    # contract), and the decided per-column codec joins the kernel cache
    # key below AND the WirePlan the pack/compact keys already carry. The
    # relay and spill host crossings engage only the byte-staged 'q8'
    # tier of the signature. The lossy tier rides the wire codec, so the
    # CYLON_TPU_NO_LANE_PACK oracle disables it too (``stats_on`` — same
    # behavior as the fused path's gated static_wire_plan).
    quant_sig = _quant.quant_spec(
        [d.dtype for d, _v in flat], key_idx,
        ctx.quant_tol if stats_on else 0.0,
    )
    relay_qsig = tuple(c if c == "q8" else None for c in quant_sig)
    if not any(c is not None for c in relay_qsig):
        relay_qsig = None
    relay_qplan, relay_qcols = (
        _g_pack.quant_lane_parts(plan_sig, relay_qsig)
        if relay_qsig is not None
        else (plan_sig, ())
    )

    def probe_ok(cols, sk_view):
        """Per-row semi-filter survival against the OTHER side's combined
        sketch (row ``probe_row`` of the per-shard [S, L] view)."""
        keys = [cols[i] for i in key_idx]
        return _sketch.probe(keys, sk_view[spec.probe_row], spec.use_range)

    # the lane plan is part of the kernel identity: the pack/compact
    # builders bake the passthrough layout in, so same-arity tables with
    # different dtypes must not alias to one cache entry; the semi-filter
    # probe changes both kernels' bodies, so its statics join the key,
    # and so do the stats columns the count pass measures and the
    # quantized-tier codec signature (tolerance flips recompile, never
    # alias). The effective 2-D topology (parallel/topo.py; None = flat /
    # CYLON_TPU_NO_TOPO) joins too: the relay builder reads it and the
    # coll/compact dispatch keys below carry the full two-hop plan, so a
    # mesh-shape or kill-switch flip recompiles, never aliases.
    topo_cfg = _topo.effective(ctx)
    key = (
        "shuffle", kind, key_idx, asc0, nb, plan_sig, tm_key, stat_cols,
        quant_sig, ("topo", tuple(topo_cfg) if topo_cfg else None),
    ) + (
        ("semi", spec.probe_row, spec.use_range) if semi else ()
    )
    has_lanes = any(
        tag is not None or has_valid for tag, _nl, has_valid in plan_sig
    )
    pt_order = tuple(ci for ci, (tag, _nl, _hv) in enumerate(plan_sig) if tag is None)

    def build_count():
        def kern(dp, rep):
            with jax.named_scope(_stages.SHUFFLE_COUNT):
                if semi:
                    # flat [2P + 4S]: unfiltered counts ++ filtered counts ++
                    # per-statable-column range words — the host reads counts,
                    # exact selectivity AND global column bounds in its ONE
                    # existing count fetch
                    (cols, kcols, counts, sk) = dp
                    n = counts[0]
                    pid = compute_pid(cols, kcols, n)
                    pid_f = jnp.where(probe_ok(cols, sk), pid, world)
                    parts = [
                        _sh.bucket_counts(pid, world),
                        _sh.bucket_counts(pid_f, world),
                    ]
                else:
                    (cols, kcols, counts) = dp
                    n = counts[0]
                    pid = compute_pid(cols, kcols, n)
                    parts = [_sh.bucket_counts(pid, world)]
                parts += [_st.stat_words(cols[ci], n) for ci in stat_cols]
                return jnp.concatenate(parts)

        return kern

    def build_pack():
        # late-bound wire state: the stats-driven wire plan is decided on
        # the host AFTER the count fetch (st["wire"]/st["bases"]); the
        # dispatch key appends st["wire"], so each decision compiles its
        # own program and the builders read the decided state at build time
        def kern(dp, rep):
            with jax.named_scope(_stages.SHUFFLE_PACK):
                wire = st["wire"]
                if semi:
                    (cols, kcols, counts, sk) = dp
                    if wire is not None:
                        (dummy, rnd, usef, bases) = rep
                    else:
                        (dummy, rnd, usef) = rep
                        bases = None
                    n = counts[0]
                    pid = compute_pid(cols, kcols, n)
                    # the adaptive gate's decision rides in as a traced scalar
                    # so ONE compiled pack program serves both outcomes
                    pid = jnp.where(
                        (usef != 0) & ~probe_ok(cols, sk), world, pid
                    )
                else:
                    (cols, kcols, counts) = dp
                    if wire is not None:
                        (dummy, rnd, bases) = rep
                    else:
                        (dummy, rnd) = rep
                        bases = None
                    n = counts[0]
                    pid = compute_pid(cols, kcols, n)
                bc = dummy.shape[0]
                n_header = (
                    _sh.wire_header_rows(wire) if wire is not None
                    else _sh.HEADER_ROWS
                )
                cnt = _sh.bucket_counts(pid, world)
                hx = None
                if wire is not None:
                    # bit-width-adaptive wire narrowing: lanes are the packed
                    # words of the stats-driven wire plan (validity at 1
                    # bit/row, values at measured width, global rebase words
                    # riding in as the tiny replicated `bases` operand).
                    # Quantized 'q8' fields additionally compute one block
                    # scale per destination chunk here and ship it in the
                    # (widened) header rows beside the counts (n_header above).
                    qrows = None
                    if _g_pack.wire_q8_cols(wire):
                        # a row's chunk is its destination
                        scales = _sh.quant_chunk_scales_sorted(
                            cols, wire, pid, cnt, world, bc, rnd
                        )
                        qrows = _sh.send_row_scales(scales, pid, 1)
                        hx = jax.lax.bitcast_convert_type(scales, jnp.int32)
                    lanes, passthrough = _g_pack.wire_pack_cols(
                        list(cols), wire, bases, qscales=qrows
                    )
                    pt_eff = _g_pack.wire_pt_order(wire, pt_order)
                else:
                    _plan, lanes, passthrough = _g_pack.pack_cols(list(cols))
                    pt_eff = pt_order
                # rows reach the send buffer by a sort keyed by destination
                # that every lane rides, each destination's chunk a window
                # of the sorted rows (parallel/shuffle.pack_by_sort). The
                # fused count/payload exchange: this round's per-destination
                # send counts ride the lane buffer's header row (a pure-f64
                # table has none: `head` is the counts)
                return _sh.pack_by_sort(
                    lanes, [passthrough[ci] for ci in pt_eff], pid, cnt,
                    world, bc, rnd, header_extra=hx, n_header=n_header,
                )

        return kern

    def build_coll():
        # late-bound like st["wire"]: the two-hop plan (st["topo_plan"],
        # a topo.TwoHopPlan or None) is decided on the host after the
        # count fetch; the dispatch key carries its full tuple, so each
        # decision compiles its own program
        def kern(dp, rep):
            with jax.named_scope(_stages.SHUFFLE_ALL_TO_ALL):
                (head, pts) = dp
                tp = st["topo_plan"]
                if tp is not None:
                    # two-hop exchange: inner grouped all_to_all, dense
                    # count-informed cross-outer repack, outer grouped
                    # all_to_all — the pack output rides in UNCHANGED
                    bc = head.shape[0] // world - tp.n_header
                    return _topo.two_hop_exchange(
                        head, pts, _topo.Topology(tp.outer, tp.inner),
                        bc, tp.cap_o, tp.n_header, ax,
                    )
                # a decided wire plan guarantees word lanes even when the
                # plain codec had none (pure-f64 quantized tables)
                if has_lanes or st["wire"] is not None:
                    out_head = _sh.exchange_buffer(head, world, ax)
                else:
                    out_head = _sh.exchange_counts(head, ax)
                out_pts = tuple(_sh.exchange_buffer(p, world, ax) for p in pts)
                return out_head, out_pts

        return kern

    def build_relay():
        # skew-split tail extraction (parallel/spill.plan_schedule): rows
        # past the collective quota of the adaptive schedule leave through
        # the host relay — packed once into PLAIN int32 lanes (the host
        # codec ops/gather.host_unpack_cols decodes them; wire narrowing
        # never applies, the rows do not ride a collective), destination-
        # major so the host splits per-source buffers with the planner's
        # own relay counts. Under the quantized tier, eligible float
        # payload columns leave the lane matrix as uint8 q8 codes (one
        # block scale per source shard) so the double host crossing ships
        # 1 byte/row instead of 4-8. Dispatched under the separately-
        # keyed ("relay",) suffix only when the schedule is adaptive.
        def kern(dp, rep):
            if semi:
                (cols, kcols, counts, sk) = dp
                (dummy, quota, usef) = rep
            else:
                (cols, kcols, counts) = dp
                (dummy, quota) = rep
            with jax.named_scope(_stages.SHUFFLE_PACK):
                n = counts[0]
                pid = compute_pid(cols, kcols, n)
                if semi:
                    pid = jnp.where(
                        (usef != 0) & ~probe_ok(cols, sk), world, pid
                    )
                rc = dummy.shape[0]
                cnt = _sh.bucket_counts(pid, world)
                sel = None
                if st["relay_mode"] == "inter":
                    # two-hop relay split: same-outer-group tails left this
                    # kernel for the device ppermute ring (build_ring); only
                    # cross-outer tails still cross the host
                    inner = st["topo_plan"].inner
                    o_self = jax.lax.axis_index(ax) // inner
                    sel = (jnp.arange(world, dtype=jnp.int32) // inner) != o_self
                dest = _sh.relay_send_slots(pid, cnt, world, quota, rc, sel=sel)
                if relay_qcols:
                    lanes, passthrough, qcodes, qscales = (
                        _g_pack.pack_cols_quant(
                            list(cols), relay_qplan, relay_qcols,
                            live=dest < rc,
                        )
                    )
                else:
                    _plan2, lanes, passthrough = _g_pack.pack_cols(list(cols))
                if lanes:
                    mat = _sh.scatter_send(
                        jnp.stack(lanes, axis=1), dest, 1, rc
                    )
                else:
                    mat = jnp.zeros((rc, 0), jnp.int32)
                pts = tuple(
                    _sh.scatter_send(passthrough[ci], dest, 1, rc)
                    for ci in pt_order
                    if not relay_qcols or relay_qsig[ci] != "q8"
                )
                if relay_qcols:
                    pts = pts + (
                        _sh.scatter_send(qcodes, dest, 1, rc), qscales
                    )
                return mat, pts

        return kern

    def build_ring():
        # device-direct intra-group skew relay (parallel/topo.ring_relay):
        # the same tail extraction as build_relay, restricted to SAME-
        # outer-group destinations, packed as plain int32 lanes plus a
        # destination-pid lane, then rotated around the inner-axis
        # ppermute neighbor ring with every device absorbing its own rows
        # — the tail never crosses a host. Compacted in-kernel; the host
        # rebuilds from the planner's own intra relay counts (no extra
        # fetch beyond the one deferred count stack).
        def kern(dp, rep):
            if semi:
                (cols, kcols, counts, sk) = dp
                (dummy, quota, usef) = rep
            else:
                (cols, kcols, counts) = dp
                (dummy, quota) = rep
            with jax.named_scope(_stages.SHUFFLE_PACK):
                n = counts[0]
                pid = compute_pid(cols, kcols, n)
                if semi:
                    pid = jnp.where(
                        (usef != 0) & ~probe_ok(cols, sk), world, pid
                    )
                rc = dummy.shape[0]
                cnt = _sh.bucket_counts(pid, world)
                tp = st["topo_plan"]
                o_self = jax.lax.axis_index(ax) // tp.inner
                sel = (
                    jnp.arange(world, dtype=jnp.int32) // tp.inner
                ) == o_self
                dest = _sh.relay_send_slots(
                    pid, cnt, world, quota, rc, sel=sel
                )
                _plan2, lanes, passthrough = _g_pack.pack_cols(list(cols))
                if lanes:
                    mat = _sh.scatter_send(
                        jnp.stack(lanes, axis=1), dest, 1, rc
                    )
                else:
                    mat = jnp.zeros((rc, 0), jnp.int32)
                pidl = jnp.full((rc,), -1, jnp.int32).at[dest].set(
                    pid, mode="drop"
                )
                pts = tuple(
                    _sh.scatter_send(passthrough[ci], dest, 1, rc)
                    for ci in pt_order
                )
            with jax.named_scope(_stages.SHUFFLE_ALL_TO_ALL):
                lanes_all, mask_all, pts_all = _topo.ring_relay(
                    mat, pidl, pts,
                    _topo.Topology(tp.outer, tp.inner), ax,
                )
            out = _sh.compact_received_lanes(
                list(plan_sig),
                lanes_all if has_lanes else None,
                dict(zip(pt_order, pts_all)),
                _sh.order_front(mask_all),
            )
            return out, _scalar(mask_all.sum().astype(jnp.int32))

        return kern

    def build_compact():
        def kern(dp, rep):
            with jax.named_scope(_stages.SHUFFLE_COMPACT):
                wire = st["wire"]
                tp = st["topo_plan"]
                if tp is not None:
                    # two-hop receive: same-group rows (final after hop 1)
                    # fuse with the combined cross-outer chunks into ONE
                    # front-pack — the self chunk of the outer hop arrived
                    # empty by construction, so its mask is all dead
                    (got2, self_rows, self_cnt, pts2, ptsS) = dp
                    bc = self_rows.shape[0] // tp.inner
                    lane_rows, mask, total = _topo.two_hop_received(
                        got2, self_rows, self_cnt,
                        _topo.Topology(tp.outer, tp.inner),
                        bc, tp.cap_o, tp.n_header,
                    )
                    pt_eff = (
                        _g_pack.wire_pt_order(wire, pt_order)
                        if wire is not None
                        else pt_order
                    )
                    pt_cols = {
                        ci: jnp.concatenate([ps, p2], axis=0)
                        for ci, ps, p2 in zip(pt_eff, ptsS, pts2)
                    }
                    # unequal chunks: the liveness sort and gathers stay
                    front = _sh.order_front(mask)
                    if wire is not None:
                        (bases,) = rep
                        out = _sh.compact_received_wire(
                            wire, bases, lane_rows, pt_cols, front
                        )
                    else:
                        out = _sh.compact_received_lanes(
                            list(plan_sig), lane_rows, pt_cols, front
                        )
                    return out, _scalar(total)
                (head, pts) = dp
                qsc_rows = None
                if wire is not None:
                    n_header = _sh.wire_header_rows(wire)
                    lane_rows, recv_counts = _sh.split_header(
                        head, world, n_header
                    )
                    nq8 = len(_g_pack.wire_q8_cols(wire))
                    if nq8:
                        # each received row dequantizes with its SOURCE
                        # chunk's block scale, broadcast from the header rows
                        # before the compaction moves anything
                        qsc_rows = _sh.recv_row_scales(
                            _sh.split_header_scales(
                                head, world, n_header, nq8
                            ),
                            world, lane_rows.shape[0] // world,
                        )
                    pt_cols = dict(
                        zip(_g_pack.wire_pt_order(wire, pt_order), pts)
                    )
                elif has_lanes:
                    lane_rows, recv_counts = _sh.split_header(head, world)
                    pt_cols = dict(zip(pt_order, pts))
                else:
                    lane_rows, recv_counts = None, head
                    pt_cols = dict(zip(pt_order, pts))
                # one hop leaves `world` equal chunks, each a live prefix:
                # every lane reaches the front by a block write a chunk at
                # the running offsets of `recv_counts`, no liveness mask,
                # no sort and no gather (parallel/shuffle.front_pack_chunks)
                front = _sh.chunk_front(recv_counts)
                if wire is not None:
                    (bases,) = rep
                    out = _sh.compact_received_wire(
                        wire, bases, lane_rows, pt_cols, front,
                        qscale_rows=qsc_rows,
                    )
                else:
                    out = _sh.compact_received_lanes(
                        list(plan_sig), lane_rows, pt_cols, front
                    )
                return out, _scalar(jnp.sum(recv_counts).astype(jnp.int32))

        return kern

    st = dict(
        spec=spec, t=t, ctx=ctx, world=world, flat=flat, khash=khash,
        key=key, plan_sig=plan_sig, has_lanes=has_lanes, n_pt=len(pt_order),
        pt_order=pt_order, stat_cols=stat_cols, wire=None, bases=None,
        quant_sig=quant_sig, relay_qsig=relay_qsig,
        topo_cfg=topo_cfg, topo_plan=None, relay_mode="all", ring=None,
        build_count=build_count, build_pack=build_pack,
        build_coll=build_coll, build_compact=build_compact,
        build_relay=build_relay, build_ring=build_ring,
        pending_spill=None,
    )
    return st


def _shuffle_many(specs: Sequence["_ShuffleSpec"]) -> List["Table"]:
    """The chunked, compute-overlapped shuffle engine (the distributed
    backbone — every Distributed* op funnels through here).

    One shuffle = a COUNT kernel (a host sync, but NOT a collective) + K
    chunked exchange rounds with ``K = ceil(hottest bucket / bucket_cap)``,
    where bucket_cap is derived from the per-round byte budget
    (config.py DEFAULT_SHUFFLE_BYTE_BUDGET; shuffle.plan_rounds) — peak
    exchange memory is O(budget), not O(max-shard padding), so a table K
    times the budget streams through in K bounded rounds without the full
    padded buffer ever materializing.

    Each round is three ASYNC dispatches — PACK (partition ids + send
    slots + header-fused scatter), COLLECTIVE (the one all_to_all; the
    round's send counts ride the lane buffer's header rows instead of a
    separate count collective, so a distributed join issues 2 collectives,
    down from 4), COMPACT (header split + lane-level front-pack: on a flat
    mesh a block write a source chunk at the running offsets of the
    received counts, no sort and no gather) — with no
    host sync anywhere in the loop: while round r's collective is in
    flight the host has already queued round r+1's pack and round r-1's
    compact, and every round's received count comes back in ONE deferred
    fetch at the end. Shuffling several tables through one call (the
    join / set-op pair path) interleaves their rounds in the dispatch
    queue, so table B's pack hides behind table A's collective even at
    K = 1. ``tracing.report()`` shows the per-phase spans
    (``shuffle.round.{pack,collective,compact}``) and the
    ``shuffle.overlap_efficiency`` gauge = fraction of the measured
    device window (dispatch-open to the deferred round-count fetch
    return) spent issuing overlapped work rather than blocked. Under
    ``CYLON_TPU_PROF`` the profiler (obs/prof.py) additionally derives
    per-stage per-shard stage clocks and the straggler ledger from the
    same already-fetched counts — zero added host syncs.
    """
    # a deferred-count input materializes UP FRONT: the shuffle is host-
    # planned regardless (the count fetch below), and materialization
    # applies the pending overshoot compaction — without it an uncompacted
    # intermediate (e.g. a partial-aggregate feeding distributed_groupby's
    # exchange) would pad every pack/sort pass to its stale capacity
    for s in specs:
        s.table._materialize()
    states = [_shuffle_state(s) for s in specs]
    rows_total = sum(st["t"]._rows_hint() or 0 for st in states)

    # phase 0: counts — dispatch every table's count kernel before fetching
    # any, so a pair's two count programs overlap on the device. Semi-
    # filtered tables' count kernels consume the (already dispatched)
    # sketch collective and return both the unfiltered and the filtered
    # counts, so the adaptive gate rides the one existing fetch.
    for st in states:
        spec = st["spec"]
        dp = (st["flat"], st["khash"], st["t"].counts_dev)
        if spec.sketch is not None:
            dp = dp + (spec.sketch,)
        with span("shuffle.count", rows=st["t"]._rows_hint()):
            st["counts_fut"] = get_kernel(
                st["ctx"], st["key"] + ("count",), st["build_count"],
                name="shuffle_count",
            )(dp, ())
    for st in states:
        spec = st["spec"]
        w = st["world"]
        S = len(st["stat_cols"])
        per = (2 * w if spec.sketch is not None else w) + 4 * S
        got = _fetch(st["counts_fut"], "shuffle.counts").reshape(w, per)
        if spec.sketch is not None:
            st["counts_pair"] = (got[:, :w], got[:, w : 2 * w])
            st["send_counts"] = got[:, :w]  # provisional; gated below
            base = 2 * w
        else:
            st["use_filter"] = False
            st["send_counts"] = got[:, :w]  # [src, dst]
            base = w
        # global column range stats measured by the count pass: fold the
        # per-shard words, cache on the INPUT table (later local ops on it
        # skip the stats kernel) and remember them for the wire plan and
        # the output table (the shuffle permutes rows, bounds survive)
        st["col_stats"] = {}
        if S:
            names = st["t"].column_names
            sw = got[:, base:].reshape(w, S, 4)
            for i, ci in enumerate(st["stat_cols"]):
                cls = _st.enc_class(st["flat"][ci][0].dtype)
                st["col_stats"][ci] = _st.fold_stat_words(sw[:, i, :], cls)
            st["t"]._attach_stats(
                {names[ci]: v for ci, v in st["col_stats"].items()}
            )

    # phase 1: round plan from the byte budget. The semi-filter APPLY
    # decision is plan-aware: shipped bytes are rounds x P x bucket_cap x
    # row_bytes regardless of how full the buffers are (capacities round
    # to powers of two), so the filter is used only when the filtered
    # counts yield a strictly cheaper round plan — a prune that does not
    # cross a capacity boundary would cost probe work for zero byte win.
    for st in states:
        # explicit per-call budget wins; then the feedback re-coster's
        # per-shape tuned budget (present only inside a plan execution
        # whose fingerprint carries it); then the static default
        budget = int(
            st["spec"].byte_budget
            or _feedback.tuned_shuffle_budget()
            or st["ctx"].shuffle_byte_budget
        )
        row_bytes = _sh.exchange_row_bytes(st["flat"])
        if st["spec"].sketch is not None:
            unfiltered, filtered = st["counts_pair"]
            tot_u, tot_f = int(unfiltered.sum()), int(filtered.sum())
            gauge(
                "shuffle.semi_filter.selectivity", tot_f / max(tot_u, 1)
            )
            # measured selectivity feeds the persistent per-fingerprint
            # profile: the feedback re-coster's semi decision substrate
            _obsstore.note_semi(sel=tot_f / max(tot_u, 1), built=True)
            cap_u, k_u = _sh.plan_rounds(
                unfiltered, row_bytes, st["world"], budget
            )
            cap_f, k_f = _sh.plan_rounds(
                filtered, row_bytes, st["world"], budget
            )
            st["use_filter"] = cap_f * k_f < cap_u * k_u
            if st["use_filter"]:
                bump("shuffle.semi_filter.applied")
                bump("shuffle.semi_filter.pruned_rows", rows=tot_u - tot_f)
                st["send_counts"] = filtered
                st["bucket_cap"], st["n_rounds"] = cap_f, k_f
            else:
                bump("shuffle.semi_filter.gate_skipped")
                st["send_counts"] = unfiltered
                st["bucket_cap"], st["n_rounds"] = cap_u, k_u
        else:
            st["bucket_cap"], st["n_rounds"] = _sh.plan_rounds(
                st["send_counts"], row_bytes, st["world"], budget
            )
        # skew-adaptive schedule (parallel/spill.py): re-plan the chosen
        # counts — non-skewed histograms return plan_rounds' own (cap, K)
        # with no relay, keeping those plans byte-identical; heavy buckets
        # shrink the collective rounds to the cold histogram and ship
        # their over-quota tails through the host relay instead. The
        # engagement ratio is the feedback re-coster's tuned trigger when
        # the straggler ledger earned one (rides the plan fingerprint via
        # the Decisions component), else the static 4x-mean
        w = st["world"]
        skew_trigger = _feedback.tuned_skew_trigger()
        sched = _spill.plan_schedule(
            st["send_counts"], row_bytes, w, budget, trigger=skew_trigger
        )
        st["bucket_cap"], st["n_rounds"] = sched.bucket_cap, sched.n_rounds
        st["sched"] = sched
        # bit-width-adaptive wire narrowing, gated plan-aware like the
        # semi filter and now schedule-aware: decision cost = global
        # collective row slots x row bytes + the relay tail's double host
        # crossing (relay rows never touch a collective; under the
        # quantized tier they stage as q8 bytes, else plain lanes — so
        # only the collective part narrows here). The lossy quant fields
        # (ops/quant.py) ride the same plan: float payload columns whose
        # codec the tolerance picked ship 8/16/32-bit fields with block
        # scales in the headers.
        if st["col_stats"] or any(c is not None for c in st["quant_sig"]):
            stats_list = [None] * len(st["plan_sig"])
            for ci, stat in st["col_stats"].items():
                stats_list[ci] = (stat.cls, _st.field_bits(stat))
            wplan = _g_pack.wire_plan(
                list(st["plan_sig"]), stats_list, quant=st["quant_sig"]
            )
            if wplan is not None:
                rb_w = _g_pack.wire_row_bytes(wplan)
                sched_w = _spill.plan_schedule(
                    st["send_counts"], rb_w, w, budget,
                    trigger=skew_trigger,
                )
                relay_rb = _spill.RELAY_COST_FACTOR * row_bytes
                total_wire = (
                    sched_w.coll_row_slots(w) * rb_w
                    + sched_w.relay_rows() * relay_rb
                )
                total_plain = (
                    sched.coll_row_slots(w) * row_bytes
                    + sched.relay_rows() * relay_rb
                )
                if total_wire < total_plain:
                    st["wire"] = wplan
                    st["bases"] = jnp.asarray(
                        _g_pack.wire_bases(wplan, st["col_stats"])
                    )
                    sched = sched_w
                    st["sched"] = sched
                    st["bucket_cap"], st["n_rounds"] = (
                        sched.bucket_cap, sched.n_rounds,
                    )
                    bump("lane_pack.wire.applied")
                    bump(
                        "lane_pack.wire.bytes_saved",
                        rows=int(total_plain - total_wire),
                    )
                    gauge(
                        "lane_pack.wire.row_bytes_ratio",
                        rb_w / max(row_bytes, 1),
                    )
                    if _g_pack.wire_has_quant(wplan):
                        nq = sum(
                            1 for f in wplan.fields if f.kind == "q"
                        )
                        bump("shuffle.quant.applied")
                        bump("shuffle.quant.cols", rows=nq)
                        bump(
                            "shuffle.quant.bytes_saved",
                            rows=int(total_plain - total_wire),
                        )
                        gauge(
                            "shuffle.quant.row_bytes_ratio",
                            rb_w / max(row_bytes, 1),
                        )
                else:
                    bump("lane_pack.wire.gate_skipped")
                    if _g_pack.wire_has_quant(wplan):
                        bump("shuffle.quant.gate_skipped")
        # per-exchange wire accounting for the active query trace: total
        # shipped bytes = K rounds x world^2 bucket blocks x effective
        # (possibly wire-narrowed) row bytes, plus the plain-codec relay
        # tail under a skew-split schedule. Attaches to the innermost
        # open span — the owning plan.node.* during lowered execution —
        # so explain(analyze=True) prints per-node coll MB. Host
        # arithmetic only; adds no sync and no dispatch.
        # effective lane/passthrough layout under the decided wire plan:
        # quantized f64 columns leave the passthrough set, and a wire
        # plan guarantees word lanes exist even for tables whose plain
        # codec had none (pure-f64 quantized)
        st["pt_eff"] = (
            _g_pack.wire_pt_order(st["wire"], st["pt_order"])
            if st["wire"] is not None
            else st["pt_order"]
        )
        st["has_lanes_eff"] = st["has_lanes"] or st["wire"] is not None
        rb_eff = (
            row_bytes if st["wire"] is None
            else _g_pack.wire_row_bytes(st["wire"])
        )
        # two-hop decision (parallel/topo.py): a configured 2-D topology
        # routes this exchange as inner-hop + dense cross-outer hop.
        # Requirements: word lanes for the headers to ride, and exactly
        # one header row (q8-widened wire headers keep the flat path —
        # their per-chunk scale blocks don't survive the hop-2 repack).
        # The autopilot's tuned hop_mode (plan/feedback.py) can force
        # "1hop" per shape; None defaults to two-hop when configured.
        n_hdr = (
            _sh.wire_header_rows(st["wire"])
            if st["wire"] is not None
            else _sh.HEADER_ROWS
        )
        two_hop_ok = (
            st["topo_cfg"] is not None
            and st["has_lanes_eff"]
            and n_hdr == 1
        )
        if two_hop_ok and _feedback.tuned_hop_mode() != "1hop":
            tcfg = st["topo_cfg"]
            ob = _topo.outer_budget()
            while True:
                tp = _topo.plan_two_hop(
                    st["send_counts"], tcfg, st["bucket_cap"],
                    st["n_rounds"], n_hdr,
                )
                # per-axis budgeting: with the default (shared) budget
                # the outer hop always fits (cap_o <= inner * cap, so
                # outer * cap_o <= P * cap); a tighter CYLON_TPU_OUTER
                # _BUDGET shrinks the global cap — more, smaller rounds
                # — until the combined-chunk buffer fits
                if (
                    not ob
                    or st["bucket_cap"] <= 8
                    or tcfg.outer * (tp.cap_o + n_hdr) * int(rb_eff) <= ob
                ):
                    break
                budget //= 2
                sched = _spill.plan_schedule(
                    st["send_counts"], int(rb_eff), w, budget,
                    trigger=skew_trigger,
                )
                st["sched"] = sched
                st["bucket_cap"], st["n_rounds"] = (
                    sched.bucket_cap, sched.n_rounds,
                )
            st["topo_plan"] = tp
        tp = st["topo_plan"]
        # received-buffer capacity of one round's compact output: flat
        # receives world cap-chunks; two-hop receives inner hop-1 self
        # chunks + outer combined chunks
        st["recv_cap"] = (
            tp.inner * st["bucket_cap"] + tp.outer * tp.cap_o
            if tp is not None
            else w * st["bucket_cap"]
        )
        # per-axis byte ledger (traced counters + the hop_mode autopilot's
        # observation substrate): intra = inner-axis/ICI bytes, inter =
        # cross-outer bytes; inter_alt = the OTHER hop mode's inter bytes
        # computed exactly from the same count matrix, so the feedback
        # proposer compares modes without reconstructing anything
        intra_b = inter_b = 0
        st["inter_alt"] = None
        if st["topo_cfg"] is not None:
            intra_b, inter_b = _topo.axis_coll_bytes(
                st["topo_cfg"], w, st["bucket_cap"], st["n_rounds"],
                int(rb_eff), n_hdr,
                cap_o=tp.cap_o if tp is not None else None,
            )
            bump("shuffle.coll_bytes.intra", rows=intra_b)
            bump("shuffle.coll_bytes.inter", rows=inter_b)
            annotate_add(
                coll_bytes_intra=intra_b, coll_bytes_inter=inter_b
            )
        if two_hop_ok:
            alt_cap_o = (
                None if tp is not None
                else _topo.plan_two_hop(
                    st["send_counts"], st["topo_cfg"], st["bucket_cap"],
                    st["n_rounds"], n_hdr,
                ).cap_o
            )
            st["inter_alt"] = _topo.axis_coll_bytes(
                st["topo_cfg"], w, st["bucket_cap"], st["n_rounds"],
                int(rb_eff), n_hdr, cap_o=alt_cap_o,
            )[1]
            # traced beside intra/inter so one run carries BOTH modes'
            # cross-outer bytes (tools/topo_smoke.py reads the pair for
            # its reduction gate without a second oracle execution)
            bump("shuffle.coll_bytes.inter_alt", rows=st["inter_alt"])
        coll_bytes = (
            intra_b + inter_b
            if tp is not None
            else sched.coll_row_slots(w) * int(rb_eff)
        )
        annotate_add(
            coll_bytes=coll_bytes,
            shuffle_rounds=int(st["n_rounds"]),
        )
        bump("shuffle.exchanged_bytes", rows=coll_bytes)
        if sched.adaptive:
            relay_bytes = sched.relay_rows() * int(row_bytes)
            bump("shuffle.spill.relay_bytes", rows=relay_bytes)
            annotate_add(relay_bytes=relay_bytes)
        st["new_counts"] = st["send_counts"].sum(axis=0).astype(np.int64)
        bump("shuffle.rounds", rows=st["n_rounds"])
        # how full the rounds' buffers are: the slots the collective rounds
        # ship (K x world^2 x cap, however empty a cold bucket is) and the
        # rows they carry (a skew-split schedule's relay tail rides none)
        bump("shuffle.coll_slots", rows=sched.coll_row_slots(w))
        bump(
            "shuffle.coll_rows",
            rows=int(st["new_counts"].sum()) - sched.relay_rows(),
        )
        kind = st["spec"].kind
        if kind in ("hash", "range"):
            # how unevenly the rows came out (a hash's skew after the semi
            # filter's decision, the sampled splitters' cut): the fullest
            # shard and the mean, from the counts the host fetched anyway
            bump(
                f"shuffle.{kind}.shard_rows_max",
                rows=int(st["new_counts"].max()),
            )
            bump(
                f"shuffle.{kind}.shard_rows_mean",
                rows=int(round(float(st["new_counts"].mean()))),
            )
        st["rounds_out"] = []
        # spill-tier decision from the same measured counts: per-shard
        # staged-output bytes vs the device spill budget (the forced knob
        # wins; a caller-owned sink implies at least tier 1 — the rows'
        # destination IS the host)
        tier = st["spec"].spill_tier
        staged = int(st["send_counts"].sum(axis=0).max()) * row_bytes
        if tier is None:
            # the feedback re-coster can PROMOTE the tier before the
            # budget line from historically observed staged bytes (it
            # never demotes below the measured decision)
            tier = _spill.choose_tier(
                staged, tuned=_feedback.tuned_spill_tier()
            )
        if st["spec"].sink is not None and tier == _spill.TIER_HBM:
            tier = _spill.TIER_HOST
        st["tier"] = tier
        # relay ladder under a two-hop plan: same-outer-group skew tails
        # upgrade from the host relay to the device-direct inner-axis
        # ppermute ring (build_ring) — only cross-outer tails keep the
        # host crossing. In-HBM plain-lane relays only: q8-staged tails
        # and spilled shuffles keep the full host relay (their rows are
        # host-bound anyway), and a caller-owned sink expects every row
        # through the arena path.
        if sched.adaptive and tp is not None:
            intra_m, inter_m = _topo.split_relay(
                sched.relay, st["topo_cfg"]
            )
            if (
                intra_m is not None
                and tier == _spill.TIER_HBM
                and st["relay_qsig"] is None
                and st["spec"].sink is None
            ):
                cap_ri = _topo.ring_cap(intra_m)
                st["ring"] = (intra_m, cap_ri)
                st["relay_inter"] = inter_m
                st["relay_mode"] = "inter"
                ring_b = _topo.ring_bytes(
                    st["topo_cfg"], cap_ri, int(row_bytes)
                )
                bump("shuffle.relay.ring_rows", rows=int(intra_m.sum()))
                bump("shuffle.coll_bytes.intra", rows=ring_b)
                annotate_add(coll_bytes_intra=ring_b)
        st["src_pairs"] = list(
            zip(st["t"].column_names, st["t"]._columns.values())
        )
        if tier != _spill.TIER_HBM:
            bump("shuffle.spill.shuffles")
            gauge("shuffle.spill.tier", tier)
            if st["spec"].sink is not None:
                # caller-owned sinks (the out-of-core ingestion path) keep
                # the original 3-arg accept contract and receive decoded
                # physical columns — the quantized staging tier applies
                # only to the engine's own arenas
                st["sink_obj"] = st["spec"].sink
                st["spill_qsig"] = None
            else:
                names = st["t"].column_names
                # quantized spill arenas: q8-tier columns stage and LIVE
                # in the arenas as uint8 codes (+ per-batch scales), so
                # tier-1/2 host/disk budgets stretch ~4x on float-heavy
                # tables; arena_result dequantizes at rebuild
                qsig = st["relay_qsig"]
                quant_map = {}
                schema = []
                for ci in range(len(names)):
                    dt = np.dtype(st["flat"][ci][0].dtype)
                    if qsig is not None and qsig[ci] == "q8":
                        quant_map[ci] = dt
                        dt = np.dtype(np.uint8)
                    schema.append(
                        (names[ci], dt, bool(st["plan_sig"][ci][2]))
                    )
                st["sink_obj"] = _spill.ShardArenaSink(
                    w, schema,
                    _spill.TIER_DISK
                    if tier == _spill.TIER_DISK
                    else _spill.TIER_HOST,
                    quant=quant_map or None,
                )
                st["spill_qsig"] = st["relay_qsig"]
        # analytic peak-device accounting (per shard, bytes): input +
        # double-buffered round exchange buffers + staged round outputs
        # (every round device-resident at tier 0; at most the two-deep
        # staging window when spilled) + the relay buffer — the number
        # the spill-smoke CI gate pins against the budget
        bc = st["bucket_cap"]
        staged_rounds = (
            st["n_rounds"]
            if tier == _spill.TIER_HBM
            else min(st["n_rounds"], 2)
        )
        hdr_rows = (
            _sh.wire_header_rows(st["wire"])
            if st["wire"] is not None
            else _sh.HEADER_ROWS
        )
        peak_rows = (
            st["t"].shard_cap
            + 2 * w * (bc + hdr_rows)
            + staged_rounds * st["recv_cap"]
            + sched.relay_cap()
            + (
                st["topo_cfg"].inner * st["ring"][1]
                if st["ring"] is not None
                else 0
            )
        )
        st["dev_peak_bytes"] = peak_rows * row_bytes
        if tier != _spill.TIER_HBM:
            st["sink_obj"].device_rows_peak = max(
                getattr(st["sink_obj"], "device_rows_peak", 0), peak_rows
            )
        # persist this shuffle's measured planning inputs + decisions for
        # the feedback re-coster (host dict work; no-op without an active
        # exec-observation context / store)
        m = np.asarray(st["send_counts"], np.int64)
        _obsstore.note_shuffle(
            world=w,
            row_bytes=int(row_bytes),
            hot=int(m.max()) if m.size else 0,
            mean_bucket=-(-int(m.sum()) // max(m.size, 1)),
            staged=staged,
            tier=int(tier),
            rounds=int(st["n_rounds"]),
            coll=int(coll_bytes),
            budget=budget,
            static_budget=int(st["ctx"].shuffle_byte_budget),
            wire=st["wire"] is not None,
            relay=sched.adaptive,
            topo=tuple(st["topo_cfg"]) if st["topo_cfg"] else None,
            hop2=tp is not None,
            intra=int(intra_b),
            inter=int(inter_b),
            inter_alt=(
                int(st["inter_alt"])
                if st["inter_alt"] is not None
                else -1
            ),
        )
    gauge(
        "shuffle.spill.peak_device_bytes",
        sum(st["dev_peak_bytes"] for st in states),
    )

    # phase 2: the double-buffered round loop — all dispatches async, the
    # single blocking fetch deferred past the last round. Skew-split
    # relay extractions dispatch FIRST so the one-per-shuffle relay
    # program overlaps every collective round behind it.
    #
    # FAILURE DOMAIN (cylon_tpu/fault): any exception out of this phase
    # fails ONLY the owning query — the failure-model invariant demands
    # every engine-owned spill arena closed (host/disk ledger bytes back
    # to baseline) and the error typed: a raw spill-path OSError that
    # escaped the staging retry ladder (a caller-owned ooc sink, a
    # memmap flush) leaves as SpillIOError, scope="query".
    try:
        return _shuffle_many_rounds(states, rows_total)
    except BaseException as e:
        for st in states:
            so = st.get("sink_obj")
            if so is not None and st["spec"].sink is None:
                so.close()
        if isinstance(e, OSError) and not isinstance(e, _fault_errors.CylonError):
            raise _spill.SpillIOError("spilled shuffle failed", e) from e
        raise


def _shuffle_many_rounds(states, rows_total) -> List["Table"]:
    """Phase 2 of ``_shuffle_many`` (split out so the failure-domain
    wrapper above stays readable): the round loop, the one deferred
    fetch, and result assembly."""
    results: List["Table"] = []
    with span("shuffle.exchange", rows=rows_total):
        t0 = _time.perf_counter()
        for st in states:
            if not st["sched"].adaptive:
                continue
            dp = (st["flat"], st["khash"], st["t"].counts_dev)
            usef = ()
            if st["spec"].sketch is not None:
                dp = dp + (st["spec"].sketch,)
                usef = (
                    jnp.asarray(1 if st["use_filter"] else 0, jnp.int32),
                )
            quota = jnp.asarray(st["sched"].quota, jnp.int32)
            if st["relay_mode"] == "inter":
                # two-hop relay ladder: the intra-group tail rides the
                # device ppermute ring (never a host crossing); the
                # ring/inter/flat relay bodies differ, so each dispatches
                # under its own key suffix
                cap_ri = st["ring"][1]
                with span(
                    "shuffle.round.relay_ring",
                    rows=int(st["ring"][0].sum()),
                ):
                    st["ring_out"] = get_kernel(
                        st["ctx"], st["key"] + ("relay", "ring"),
                        st["build_ring"], name="shuffle_ring",
                    )(dp, (jnp.zeros((cap_ri,), jnp.int8), quota) + usef)
                # the ring compacts what it absorbed in-kernel, by order
                bump("shuffle.compact.by_order")
                if st["relay_inter"] is None:
                    continue
            rc = st["sched"].relay_cap()
            rep = (jnp.zeros((rc,), jnp.int8), quota) + usef
            rkey = st["key"] + (
                ("relay", "inter")
                if st["relay_mode"] == "inter"
                else ("relay",)
            )
            with span("shuffle.round.relay", rows=st["sched"].relay_rows()):
                st["relay_out"] = get_kernel(
                    st["ctx"], rkey, st["build_relay"], name="shuffle_relay"
                )(dp, rep)
        for r in range(max(st["n_rounds"] for st in states)):
            for st in states:
                if r >= st["n_rounds"]:
                    continue
                ctx = st["ctx"]
                rep = (
                    jnp.zeros((st["bucket_cap"],), jnp.int8),
                    jnp.asarray(r, jnp.int32),
                )
                dp = (st["flat"], st["khash"], st["t"].counts_dev)
                if st["spec"].sketch is not None:
                    dp = dp + (st["spec"].sketch,)
                    rep = rep + (
                        jnp.asarray(1 if st["use_filter"] else 0, jnp.int32),
                    )
                if st["wire"] is not None:
                    rep = rep + (st["bases"],)
                with span("shuffle.round.pack"):
                    head, pts = get_kernel(
                        ctx, st["key"] + ("pack", st["wire"]),
                        st["build_pack"], name="shuffle_pack",
                    )(dp, rep)
                _bump_pack_ride(
                    st["plan_sig"], st["wire"], len(st["pt_eff"])
                )
                # the two-hop plan joins both dispatch keys: its cap_o /
                # header statics are baked into the kernel bodies, so a
                # plan (or kill-switch) flip compiles its own program
                tp_key = (
                    tuple(st["topo_plan"])
                    if st["topo_plan"] is not None
                    else None
                )
                with span("shuffle.round.collective"):
                    coll_out = get_kernel(
                        ctx,
                        ("shuffle_coll", st["has_lanes_eff"],
                         len(st["pt_eff"]), tp_key),
                        st["build_coll"],
                    )((head, pts), ())
                with span("shuffle.round.compact"):
                    out, nout = get_kernel(
                        ctx,
                        ("shuffle_compact", st["plan_sig"],
                         st["has_lanes"], st["wire"], tp_key),
                        st["build_compact"],
                    )(
                        coll_out,
                        (st["bases"],) if st["wire"] is not None else (),
                    )
                # which front-pack the dispatch ran: the block writes of a
                # one-hop receive (rows= the chunks placed), or the liveness
                # sort and gathers the two-hop receive keeps
                if tp_key is None:
                    bump("shuffle.compact.blocks", rows=st["world"])
                else:
                    bump("shuffle.compact.by_order")
                if st["tier"] != _spill.TIER_HBM:
                    # tier 1/2: this round's compacted output streams into
                    # the host arena ONE ROUND DEEP — round r is fetched
                    # only after round r+1's kernels are queued (below,
                    # AFTER every state's round-r dispatches, so one
                    # table's staging fetch never stalls its pair
                    # sibling's dispatches), and at most two round
                    # outputs are ever resident. The received counts are
                    # host-known from the plan (same expectation the
                    # deferred validation uses): staging adds no count
                    # fetch.
                    bc = st["bucket_cap"]
                    expect_r = (
                        np.clip(st["send_counts"] - r * bc, 0, bc)
                        .sum(axis=0)
                        .astype(np.int64)
                    )
                    rt = st["t"]._rebuild_cols(
                        st["src_pairs"], out, expect_r, st["recv_cap"]
                    )
                    st["spill_fresh"] = (rt, expect_r)
                    st["rounds_out"].append((None, nout))
                else:
                    st["rounds_out"].append((out, nout))
            for st in states:
                fresh = st.pop("spill_fresh", None)
                if fresh is None:
                    continue
                prev = st["pending_spill"]
                st["pending_spill"] = fresh
                if prev is not None:
                    _spill.stage_table(
                        st["sink_obj"], *prev, qspec=st["spill_qsig"]
                    )
        # when the last program of the loop was enqueued (a staging fetch
        # of a spilled tier, which blocks, is no issuing)
        t_disp = _obstrace.last_dispatch_return_s()

        # the ONE deferred sync per table: every round's received counts
        # come back in a single stacked fetch (fetching per round made the
        # deferred-sync count scale with K — flagged by the graft-lint
        # host-sync pass, which pins host_syncs as K-independent), then
        # validate against the count-phase expectation and assemble tables
        for st in states:
            t = st["t"]
            src_pairs = st["src_pairs"]
            bc = st["bucket_cap"]
            spilled = st["tier"] != _spill.TIER_HBM
            nouts = [nout for _out, nout in st["rounds_out"]]
            ring_out = st.get("ring_out")
            if ring_out is not None:
                # the ring's absorbed-row count rides the SAME stacked
                # fetch as the round counts — the ring adds no host sync
                nouts.append(ring_out[1])
            got_all = _fetch(
                nouts[0] if len(nouts) == 1 else jnp.stack(nouts),
                "shuffle.round_counts",
            ).reshape(len(nouts), -1).astype(np.int64)
            # stage-clock stamp: this fetch's return IS the device-
            # resolved end of this table's exchange (all rounds complete)
            st["t_dev"] = _obstrace.last_fetch_return_s()
            round_tables: List["Table"] = []
            for r, (out, _nout) in enumerate(st["rounds_out"]):
                got = got_all[r]
                expect = (
                    np.clip(st["send_counts"] - r * bc, 0, bc)
                    .sum(axis=0)
                    .astype(np.int64)
                )
                if not (got == expect).all():
                    raise RuntimeError(
                        f"shuffle round {r}: received row counts {got} != "
                        f"expected {expect} — internal routing bug"
                    )
                if not spilled:
                    round_tables.append(
                        t._rebuild_cols(src_pairs, out, got, st["recv_cap"])
                    )
            if spilled and st["pending_spill"] is not None:
                # flush the one-deep staging window
                pend, st["pending_spill"] = st["pending_spill"], None
                _spill.stage_table(
                    st["sink_obj"], *pend, qspec=st["spill_qsig"]
                )
            # skew-split relay tails: fetched once, regrouped by owner
            # shard on the host. Spilled shuffles merge them straight into
            # the arenas; in-HBM shuffles restage them as one extra table
            # in the round concat.
            relay_tbl = None
            ring_tbl = None
            if ring_out is not None:
                # ring rows are device-resident and their per-destination
                # counts are host-known from the planner's intra matrix —
                # validate against the fetched absorb count, then restage
                # as one extra table in the round concat
                intra_m, cap_ri = st["ring"]
                expect_ring = intra_m.sum(axis=0).astype(np.int64)
                got_ring = got_all[len(st["rounds_out"])]
                if not (got_ring == expect_ring).all():
                    raise RuntimeError(
                        f"shuffle relay ring: absorbed row counts "
                        f"{got_ring} != expected {expect_ring} — "
                        "internal routing bug"
                    )
                ring_tbl = t._rebuild_cols(
                    src_pairs, ring_out[0], expect_ring,
                    st["topo_plan"].inner * cap_ri,
                )
            if st["sched"].adaptive and st.get("relay_out") is not None:
                relay_m = (
                    st["relay_inter"]
                    if st["relay_mode"] == "inter"
                    else st["sched"].relay
                )
                per_dst, rcounts = _spill.fetch_relay(
                    st["ctx"], list(st["plan_sig"]), st["pt_order"],
                    *st["relay_out"], relay_m,
                    qspec=st["relay_qsig"],
                )
                if spilled:
                    st["sink_obj"].accept(t, per_dst, rcounts)
                else:
                    relay_tbl = _spill.shards_to_table(t, per_dst, rcounts)
            if spilled:
                if st["spec"].sink is not None:
                    # the rows live in the caller's sink (the unified
                    # out-of-core ingestion path) — no device result
                    results.append(None)
                    continue
                res = _spill.arena_result(st["sink_obj"], t)
            else:
                parts = round_tables + (
                    [relay_tbl] if relay_tbl is not None else []
                ) + ([ring_tbl] if ring_tbl is not None else [])
                if len(parts) > 1:
                    # that the reassembly ran, and over what: the blocks
                    # one program wrote and the live rows they placed
                    bump("shuffle.reassemble.parts", rows=len(parts))
                    bump(
                        "shuffle.reassemble.rows",
                        rows=sum(int(p.row_counts.sum()) for p in parts),
                    )
                res = _concat_tables(parts)
                # compact when the uniform bucket sizing overshot; any
                # input sortedness is gone — rows arrive source-major per
                # round and K-round chunks interleave
                # (shuffle.ordering_after_shuffle)
                res = res._maybe_compact(st["new_counts"], factor=2)
            res._ordering = _sh.ordering_after_shuffle(st["spec"].kind)
            if st["col_stats"]:
                names = t.column_names
                res._attach_stats(
                    {names[ci]: v for ci, v in st["col_stats"].items()}
                )
            results.append(res)
        # the measured overlap ledger (ISSUE 15): the device window ends
        # when the ONE deferred round-count fetch returned — the
        # exchange's device-resolved end — NOT when the host finished
        # assembling results. The old host-wall denominator counted
        # relay fetches and table rebuilds as exchange time, so the
        # gauge under/over-reported on async chains; the stable name and
        # 0..1 range are unchanged (tests/test_obs.py
        # test_overlap_gauge_excludes_host_assembly pins that host-side
        # assembly work cannot move this gauge).
        t_dev = max(st.get("t_dev", t_disp) for st in states)
        window_s = max(t_dev - t0, 1e-9)
        gauge(
            "shuffle.overlap_efficiency",
            min(max(t_disp - t0, 0.0) / window_s, 1.0),
        )
        # per-stage per-shard stage clocks (obs/prof.py): pure host
        # arithmetic over the count matrices phase 0 already fetched and
        # the [t0, t_dev] window stamped above — zero added syncs
        _prof.record_shuffle(
            [
                (st["send_counts"], st["n_rounds"], st["bucket_cap"],
                 st["sched"].relay,
                 tuple(st["topo_plan"]) if st["topo_plan"] else None)
                for st in states
            ],
            states[0]["world"], t0, t_dev,
        )
    return results


def _pair_sketches(
    a: "Table",
    a_keys: Sequence[str],
    b: "Table",
    b_keys: Sequence[str],
    sides: str,
    size_gate: bool = True,
) -> Optional[dict]:
    """Build the combined semi-join key sketches for a shuffle pair
    (ops/sketch.py): each side named in ``sides`` ('both'/'a'/'b' = which
    tables get FILTERED) needs the OTHER side's sketch, so the build list
    is the probe targets. Every needed local sketch rides ONE collective
    (sketch.combine_pair's all_gather) and the dispatch happens here —
    before any count/pack kernel — so the exchange overlaps the pair's
    count programs and the first pack dispatch.

    Returns None when the filter is provably not worth it or not sound:
    (1) a paired key column's hashing family differs across the sides
    (the local op may equate values the sketches hash apart), or (2) the
    filtered payload is too small to repay the sketch collective's own
    bytes (config.SEMI_FILTER_MIN_PAYOFF). The min/max range words engage
    only when both first keys share an exact monotone-uint32 encoding
    class (dictionary CODES qualify — the post-unification codes, not the
    value hashes, are what gets probed)."""
    ctx = a.ctx
    world = ctx.world_size
    for an, bn in zip(a_keys, b_keys):
        ca, cb = a._columns[an], b._columns[bn]
        if ca.dtype.is_dictionary != cb.dtype.is_dictionary:
            return None
        ha = _sketch.hash_class(ca.data.dtype)
        hb = _sketch.hash_class(cb.data.dtype)
        if ha is None or ha != hb:
            return None
    ra = _sketch.range_class(a._columns[a_keys[0]].data.dtype)
    rb = _sketch.range_class(b._columns[b_keys[0]].data.dtype)
    use_range = ra is not None and ra == rb
    build = []
    if sides in ("both", "b"):
        build.append(("a", a, tuple(a_keys)))  # a's sketch: b probes it
    if sides in ("both", "a"):
        build.append(("b", b, tuple(b_keys)))  # b's sketch: a probes it
    if not build:
        return None
    bits = max(
        _sketch.sketch_bits_for(t.row_count, ctx.sketch_bits)
        for _, t, _k in build
    )
    wire = len(build) * _sketch.sketch_len(bits) * 4
    # per-shard basis on both sides of the inequality: each shard ships
    # rows/world of payload but injects the WHOLE local sketch
    prunable = 0
    if sides in ("both", "a"):
        prunable += a.row_count * _sh.exchange_row_bytes(a._flat_cols())
    if sides in ("both", "b"):
        prunable += b.row_count * _sh.exchange_row_bytes(b._flat_cols())
    prunable //= max(world, 1)
    from .config import SEMI_FILTER_MIN_PAYOFF

    # ``size_gate=False`` (the feedback re-coster's "on"/"explore" semi
    # modes) overrides ONLY this static payoff heuristic — the soundness
    # gates above (hash-class pairing, range-class match) always stand
    if size_gate and prunable < SEMI_FILTER_MIN_PAYOFF * wire:
        _obsstore.note_semi(payoff_skip=True)
        return None
    kflats = [tuple(t._flat_cols(list(keys))) for _, t, keys in build]
    sig = tuple(
        tuple((str(d.dtype), v is not None) for d, v in kf) for kf in kflats
    )
    key = ("semi_sketch", sig, bits, use_range)
    ax = ctx.axis_name

    def builder():
        def kern(dp, rep):
            locals_ = [
                _sketch.build_local(list(kc), counts[0], bits, use_range)
                for kc, counts in dp
            ]
            return _sketch.combine_pair(jnp.stack(locals_), ax, world)

        return kern

    dp = tuple(
        (kf, t.counts_dev) for (_n, t, _k), kf in zip(build, kflats)
    )
    with span("shuffle.semi_filter.sketch", rows=wire):
        gsk = get_kernel(ctx, key, builder)(dp, ())
    bump("semi_filter.sketch_bytes", rows=wire)
    annotate_add(coll_bytes=int(wire), sketch_bytes=int(wire))
    row_of = {name: i for i, (name, _t, _k) in enumerate(build)}
    probe = {}
    if sides in ("both", "a"):
        probe["a"] = row_of["b"]
    if sides in ("both", "b"):
        probe["b"] = row_of["a"]
    return dict(sketch=gsk, probe=probe, use_range=use_range)


def _bump_join_route(route: str, left: "Table", right: "Table") -> None:
    """One bump a distributed join on a mesh, by the route it took
    (``rows=`` both sides' rows as far as the host holds them: what
    ``join.replicate.rows`` and ``shuffle.coll_rows`` are a share of)."""
    bump(
        "join.route." + route,
        rows=(left._rows_hint() or 0) + (right._rows_hint() or 0),
    )


def _shuffle_pair(
    a: "Table",
    a_keys: Sequence[str],
    b: "Table",
    b_keys: Sequence[str],
    byte_budget: Optional[int] = None,
    semi: Optional[str] = None,
) -> Tuple["Table", "Table"]:
    """Hash-shuffle two tables with INTERLEAVED round dispatch (one engine
    call): the pair path of distributed joins and set ops, where table B's
    pack/compact hides behind table A's collective.

    ``semi`` ('both'/'a'/'b', see ops/sketch.join_filter_sides) engages the
    semi-join sketch filter: the named sides' rows are probed against the
    other side's broadcast key sketch inside the count/pack kernels and
    provably partnerless rows never enter the payload exchange. False
    positives only ship extra rows, so output equals the unfiltered
    shuffle's (CYLON_TPU_NO_SEMI_FILTER=1 disables for differentials)."""
    sa = _ShuffleSpec(a, "hash", tuple(a_keys), byte_budget=byte_budget)
    sb = _ShuffleSpec(b, "hash", tuple(b_keys), byte_budget=byte_budget)
    # the feedback re-coster's semi decision (threaded through the plan
    # fingerprint; None outside plan execution / with autotune off):
    # "off" skips even building the sketch — observed selectivity too
    # high to ever repay the sketch collective; "on"/"explore" build it
    # past the static size gate ("on": observed selectivity low;
    # "explore": measure-then-decide on a shape with no evidence yet)
    mode = _feedback.tuned_semi_mode()
    if semi is not None and a.world_size > 1 and mode == "off":
        bump("autotune.semi_skipped")
    if (
        semi is not None and a.world_size > 1 and _sketch.enabled()
        and mode != "off"
    ):
        if mode in ("on", "explore"):
            bump("autotune.semi_forced")
        got = _pair_sketches(
            a, a_keys, b, b_keys, semi,
            size_gate=mode not in ("on", "explore"),
        )
        if got is not None:
            if "a" in got["probe"]:
                sa = sa._replace(
                    sketch=got["sketch"], probe_row=got["probe"]["a"],
                    use_range=got["use_range"],
                )
            if "b" in got["probe"]:
                sb = sb._replace(
                    sketch=got["sketch"], probe_row=got["probe"]["b"],
                    use_range=got["use_range"],
                )
    out = _shuffle_many([sa, sb])
    return out[0], out[1]


# ----------------------------------------------------------------------
# module-level helpers
# ----------------------------------------------------------------------

def _encode_arrow_array(chunked):
    """pyarrow ChunkedArray/Array -> (physical, valid, DataType, dictionary),
    typed (reference arrow type bridge, arrow/arrow_types.cpp). Dictionary
    codes are remapped onto the sorted unique dictionary (the Column
    invariant: code order == value order)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    arr = chunked.combine_chunks() if hasattr(chunked, "combine_chunks") else chunked
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.chunk(0) if arr.num_chunks == 1 else pa.concat_arrays(arr.chunks)
    valid = None
    if arr.null_count:
        valid = ~np.asarray(arr.is_null())
    t = arr.type
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        arr = arr.dictionary_encode()
        t = arr.type
    if pa.types.is_dictionary(t):
        raw_dict = np.asarray(arr.dictionary.to_pylist(), dtype=str)
        codes = np.asarray(pc.fill_null(arr.indices, 0)).astype(np.int32)
        sorted_dict, remap = np.unique(raw_dict, return_inverse=True)
        codes = remap.astype(np.int32)[codes]
        return codes, valid, DataType(Type.STRING), sorted_dict
    if pa.types.is_timestamp(t) or pa.types.is_date(t):
        data = np.asarray(arr.cast(pa.timestamp("ns")).fill_null(0)).astype(np.int64)
        return data, valid, DataType(Type.TIMESTAMP), None
    if pa.types.is_duration(t):
        data = np.asarray(arr.cast(pa.duration("ns")).fill_null(0)).astype(np.int64)
        return data, valid, DataType(Type.DURATION), None
    if pa.types.is_boolean(t):
        data = np.asarray(arr.fill_null(False))
        return data, valid, DataType(Type.BOOL), None
    if pa.types.is_floating(t):
        data = np.asarray(arr.fill_null(0.0))
        return data, valid, DataType.from_numpy_dtype(data.dtype), None
    if pa.types.is_integer(t):
        data = np.asarray(arr.fill_null(0))
        return data, valid, DataType.from_numpy_dtype(data.dtype), None
    raise TypeError(f"unsupported arrow type {t}")


def promote_encoded_shards(shards: List["OrderedDict[str, Tuple]"]) -> None:
    """When per-shard encoding/inference disagrees on a column's logical
    type, promote every shard to a common type in place (numeric mix ->
    float64; any string -> string with numbers re-formatted). Without this,
    one shard's dictionary codes would sit next to another shard's integer
    values. (Reference: each rank's Arrow table must share a schema.)"""
    if not shards:
        return
    live = [s for s in shards if s is not None]
    for name in list(live[0].keys()):
        types = {s[name][2].type for s in live}
        if len(types) == 1:
            continue
        if Type.STRING in types:
            for s in live:
                data, valid, dtype, _d = s[name]
                if dtype.type == Type.STRING:
                    continue
                if dtype.type == Type.BOOL:
                    vals = np.where(data.astype(bool), "true", "false")
                elif dtype.type == Type.DOUBLE:
                    vals = np.array([repr(float(x)) for x in data])
                else:
                    vals = np.array([str(int(x)) for x in data])
                dic, codes = np.unique(np.asarray(vals, str), return_inverse=True)
                s[name] = (codes.astype(np.int32), valid, DataType(Type.STRING), dic)
        else:
            for s in live:
                data, valid, dtype, _d = s[name]
                if dtype.type == Type.DOUBLE:
                    continue
                s[name] = (data.astype(np.float64), valid, DataType(Type.DOUBLE), None)


def _dict_union(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Union of two dictionaries. Both are sorted and unique (the Column
    invariant): the native merge is O(sum) where union1d re-sorts the
    concat every fold."""
    from . import native as _native

    got = _native.dict_union(np.asarray(a), np.asarray(b))
    return got[0] if got is not None else np.union1d(a, b)


def _same_dict(a: np.ndarray, b: np.ndarray) -> bool:
    return a is b or (len(a) == len(b) and bool((a == b).all()))


def unify_encoded_shards(shards: List["OrderedDict[str, Tuple]"]) -> None:
    """Promote disagreeing types, then remap per-shard dictionary codes onto
    the union dictionary in place, so string columns from different shards
    compare/hash consistently."""
    promote_encoded_shards(shards)
    live = [s for s in shards if s is not None]
    if not live:
        return
    for name in list(live[0].keys()):
        if not live[0][name][2].is_dictionary:
            continue
        dicts = [s[name][3] for s in live]
        union = dicts[0]
        for d in dicts[1:]:
            union = _dict_union(union, d)
        for s in live:
            data, valid, dtype, d = s[name]
            remap = np.searchsorted(union, d).astype(np.int32)
            codes = remap[data] if len(d) else data
            s[name] = (codes, valid, dtype, union)


def _plan_join_fusion(left: "Table", l_names, right: "Table", r_names):
    """Sort-word fusion plan for a join pair's factorize lanes, or None.

    Declines when: lane packing is off; the pair takes ops/join's
    single-uint32-key fast path (already one lane — skip the stats
    kernel); any key pair's physical dtypes differ (each side's stats
    describe a different encoding); or any key lacks measurable stats.
    The merged (both-sides) bounds size each value field, so every live
    key of either table fits its field."""
    if not _st.enabled():
        return None
    if len(l_names) == 1:
        ca = left._columns[l_names[0]]
        cb = right._columns[r_names[0]]
        if (
            ca.valid is None and cb.valid is None
            and np.dtype(ca.data.dtype).itemsize <= 4
            and np.dtype(cb.data.dtype).itemsize <= 4
            and ca.data.dtype != jnp.float64
            and cb.data.dtype != jnp.float64
        ):
            return None  # the uint32 fast path needs no factorize
    lstats = left.ensure_stats(l_names)
    rstats = right.ensure_stats(r_names)
    specs = []
    for ln, rn in zip(l_names, r_names):
        ca, cb = left._columns[ln], right._columns[rn]
        if ca.data.dtype != cb.data.dtype:
            return None
        a, b = lstats.get(ln), rstats.get(rn)
        if a is None or b is None:
            return None
        merged = a.merge(b)
        if merged is None:
            return None
        specs.append((
            merged.cls, _st.field_bits(merged),
            ca.valid is not None or cb.valid is not None, True,
        ))
    return _sort_mod.plan_lane_fusion(
        specs, pad_bits=1, prefix_bits=0,
        allow64=bool(jax.config.jax_enable_x64),
    )


def _key_ids_and_live(dp, l_on: bool, r_on: bool, join_fuse):
    """The opening of both keys-only programs (``join_semi``,
    ``join_semi_rows``), traced inside their kernels: the canonical key
    ids of both sides, and which rows are live, a padding slot and a row
    its side's mask dropped being neither. ``dp`` is ``(left keys, right
    keys, left count, right count, [the masks that are on])``. Returns
    (l_ids, r_ids, l_live, r_live, the right key columns)."""
    (lk, rk, nl, nr, masks) = dp
    cap_l, cap_r = lk[0][0].shape[0], rk[0][0].shape[0]
    l_ids, r_ids = _j._canonical_ids(
        lk, rk, nl[0], nr[0], cap_l, cap_r, fuse=join_fuse
    )
    masks = list(masks)
    l_live = jnp.arange(cap_l, dtype=jnp.int32) < nl[0]
    r_live = jnp.arange(cap_r, dtype=jnp.int32) < nr[0]
    if l_on:
        l_live = l_live & masks.pop(0)
    if r_on:
        r_live = r_live & masks.pop(0)
    return l_ids, r_ids, l_live, r_live, rk


def _check_join_count(totals: np.ndarray, shadows: np.ndarray) -> None:
    """Reject joins whose per-shard output count wrapped int32 (see
    ops.join.count_overflow_check)."""
    if (totals < 0).any() or (shadows > 2.0**31 - 1).any():
        raise ValueError(
            "join output exceeds 2^31 rows on at least one shard; "
            "repartition the inputs (distributed_join) or reduce the skew"
        )


def _suffix_names(lnames, rnames, suffixes):
    overlap = set(lnames) & set(rnames)
    out = [n + suffixes[0] if n in overlap else n for n in lnames]
    out += [n + suffixes[1] if n in overlap else n for n in rnames]
    return out


def _agg_name(oid: int) -> str:
    return {
        _g.SUM: "sum", _g.COUNT: "count", _g.MIN: "min", _g.MAX: "max",
        _g.MEAN: "mean", _g.VAR: "var", _g.STDDEV: "std", _g.NUNIQUE: "nunique",
        _g.QUANTILE: "quantile",
    }[oid]


def _remap_codes(col: Column, mapping: np.ndarray, dictionary: np.ndarray) -> Column:
    if not len(mapping):
        # an empty dictionary codes no live row: nothing to look up
        return Column(col.data, col.dtype, col.valid, dictionary)
    m = jnp.asarray(mapping)
    data = m[jnp.clip(col.data, 0, len(mapping) - 1)]
    return Column(data, col.dtype, col.valid, dictionary)


def _unify_dict_pair(
    a: "Table", b: "Table", a_cols: Sequence[str], b_cols: Sequence[str]
) -> Tuple["Table", "Table"]:
    """Remap dictionary codes of paired string columns onto their union
    dictionary so cross-table comparisons/hashes are valid."""
    new_a = OrderedDict(a._columns)
    new_b = OrderedDict(b._columns)
    changed = False
    for an, bn in zip(a_cols, b_cols):
        ca, cb = a._columns[an], b._columns[bn]
        if ca.dtype.is_dictionary != cb.dtype.is_dictionary:
            # without this, dictionary CODES would compare against numeric
            # VALUES (reference: arrow type validation rejects the pair)
            raise ValueError(f"cannot join string key {an!r} with numeric key {bn!r}")
        if not (ca.dtype.is_dictionary and cb.dtype.is_dictionary):
            continue
        if _same_dict(ca.dictionary, cb.dictionary):
            continue
        union, map_a, map_b = unify_dictionaries(ca, cb)
        new_a[an] = _remap_codes(ca, map_a, union)
        new_b[bn] = _remap_codes(cb, map_b, union)
        changed = True
    if not changed:
        return a, b
    # dictionary remap preserves code order (code order == value order
    # invariant), so any sortedness descriptor survives the rewrite; range
    # stats survive only on columns whose CODES were not rewritten
    changed_a = {n for n in a_cols if new_a[n] is not a._columns[n]}
    changed_b = {n for n in b_cols if new_b[n] is not b._columns[n]}
    return (
        a._replace(columns=new_a)._attach_ordering(a._ordering)._attach_stats(
            {n: v for n, v in a._stats.items() if n not in changed_a}
        ),
        b._replace(columns=new_b)._attach_ordering(b._ordering)._attach_stats(
            {n: v for n, v in b._stats.items() if n not in changed_b}
        ),
    )


def _promote_key_pair(
    a: "Table", b: "Table", a_cols: Sequence[str], b_cols: Sequence[str]
) -> Tuple["Table", "Table"]:
    """Cast paired numeric key columns to their common promoted dtype so both
    sides hash/compare identically (the reference instead *requires* matching
    key types — arrow type validation; promotion here is a superset)."""
    from .dtypes import promote_key_dtypes

    new_a = OrderedDict(a._columns)
    new_b = OrderedDict(b._columns)
    changed = False
    for an, bn in zip(a_cols, b_cols):
        ca, cb = a._columns[an], b._columns[bn]
        if ca.dtype.is_dictionary or cb.dtype.is_dictionary:
            # mixed string/numeric pairs are rejected by _unify_dict_pair
            continue
        if ca.data.dtype == cb.data.dtype:
            continue
        common = promote_key_dtypes(ca.data.dtype, cb.data.dtype)
        dt = DataType.from_numpy_dtype(np.dtype(common))
        new_a[an] = Column(ca.data.astype(common), dt, ca.valid, None)
        new_b[bn] = Column(cb.data.astype(common), dt, cb.valid, None)
        changed = True
    if not changed:
        return a, b
    # numeric widening is monotone: non-strict sortedness survives (equal
    # promoted values only merge runs, never split them). Range stats are
    # carried through _attach_stats, which drops any column whose encoding
    # class changed under the promotion (the enc_class re-check).
    return (
        a._replace(columns=new_a)._attach_ordering(a._ordering)._attach_stats(
            {n: v for n, v in a._stats.items()
             if new_a[n] is a._columns[n]}
        ),
        b._replace(columns=new_b)._attach_ordering(b._ordering)._attach_stats(
            {n: v for n, v in b._stats.items()
             if new_b[n] is b._columns[n]}
        ),
    )


#: tables one reassembly program takes. A longer list (``parallel/dag``'s
#: chunk outputs, a public ``concat`` of many tables) is folded in groups of
#: this many, so the programs a context compiles differ in at most this
#: many part counts a schema. Not a knob.
CONCAT_FAN_IN = 16


def _concat_tables(tables: Sequence["Table"]) -> "Table":
    """Row-wise concat of same-schema tables, per shard (reference Merge,
    table.cpp:267-289): every shard's output is its rows of the first
    table, then of the second, and so on. A shuffle of more than one round
    reassembles its rounds' outputs here (round-major, then the relay and
    the ring table), and every public concat lands here too."""
    tables = list(tables)
    assert len(tables) >= 1
    while len(tables) > 1:
        tables = [
            _concat_rows(tables[i:i + CONCAT_FAN_IN])
            for i in range(0, len(tables), CONCAT_FAN_IN)
        ]
    return tables[0]


def _unify_dicts(tables: List["Table"]) -> List["Table"]:
    """Remap every dictionary column of ``tables`` onto the union of the
    tables' dictionaries for it. Tables that already hold one dictionary (a
    shuffle's rounds hold the same object) come back as they are."""
    remapped = [{} for _ in tables]  # a table: name -> its remapped column
    for name in tables[0].column_names:
        parts = [t._columns[name] for t in tables]
        is_dict = parts[0].dtype.is_dictionary
        if any(c.dtype.is_dictionary != is_dict for c in parts):
            raise ValueError(
                f"cannot concat string column {name!r} with a numeric one"
            )
        if not is_dict:
            continue
        union = parts[0].dictionary
        for c in parts[1:]:
            if not _same_dict(c.dictionary, union):
                union = _dict_union(union, c.dictionary)
        for new, c in zip(remapped, parts):
            if not _same_dict(c.dictionary, union):
                remap = np.searchsorted(union, c.dictionary).astype(np.int32)
                new[name] = _remap_codes(c, remap, union)
    # as in _unify_dict_pair: the remap keeps code order, so a sortedness
    # descriptor survives; range stats only where the codes were not rewritten
    return [
        t if not new else t._replace(
            columns=OrderedDict(t._columns, **new)
        )._attach_ordering(t._ordering)._attach_stats(
            {n: v for n, v in t._stats.items() if n not in new}
        )
        for t, new in zip(tables, remapped)
    ]


def _concat_rows(tables: List["Table"]) -> "Table":
    """One program for all K tables: each one's buffers go as blocks at its
    running row offset (:func:`parallel.shuffle.reassemble_blocks`)."""
    if len(tables) == 1:
        return tables[0]
    names = tables[0].column_names
    if any(t.column_names != names for t in tables[1:]):
        raise ValueError("concat requires identical schemas")
    tables = _unify_dicts(tables)
    first = tables[0]
    new_counts = sum(t.row_counts for t in tables)
    cap_out = round_cap(int(new_counts.max()))
    flats = [t._flat_cols() for t in tables]
    key = ("shuffle_reassemble", len(tables), len(names))

    def build():
        def kern(dp, rep):
            (parts, counts) = dp
            (dummy,) = rep
            return _sh.reassemble_blocks(
                parts, [n[0] for n in counts], dummy.shape[0]
            )

        return kern

    out = get_kernel(first.ctx, key, build)(
        (flats, [t.counts_dev for t in tables]),
        (jnp.zeros((cap_out,), jnp.int8),),
    )
    # the counts are the sum of the inputs', which the host holds: no fetch
    return first._rebuild_cols(
        list(zip(names, first._columns.values())), out, new_counts, cap_out
    )


def concat(tables: Sequence["Table"]) -> "Table":
    """Public concat (pycylon Table.concat, data/table.pyx:2334)."""
    return _concat_tables(list(tables))


def merge(tables: Sequence["Table"]) -> "Table":
    """Reference Merge (table.cpp:267-289)."""
    return _concat_tables(list(tables))
