"""Device stage names: one vocabulary, and the table that maps every
instruction of every dispatched program to its stage.

The hot-path kernels wrap their work in ``with jax.named_scope(NAME):``
with the names below, so a compiled program's instructions carry
``op_name="jit(join_probe)/join.right_sort/sort_engine/jit(argsort)/sort"``.
A profiler trace read through ``jax.profiler.ProfileData`` names a device
operation by its HLO instruction text WITHOUT that metadata, so the stage
of a trace event is a join of the event's instruction name with the
compiled text of the program that ran — :func:`device_stage_table`.

A stage of an operation is the OUTERMOST vocabulary name on its
``op_name`` path (:func:`stage_of`); ``sort_engine`` is also reported on
its own wherever it occurs on the path (:func:`in_sort_engine`), since the
engine is called from inside three other stages.

Nothing here runs per dispatch: ``engine.get_kernel`` records the first
call's argument spec on a cache MISS (:func:`register_dispatch`), and the
table is built only when something asks for it.
"""
from __future__ import annotations

import re
import threading
import time
import weakref
from typing import Optional

import jax

JOIN_KEY_IDS = "join.key_ids"
JOIN_RIGHT_SORT = "join.right_sort"
JOIN_PROBE = "join.probe"
JOIN_EMIT = "join.emit"
JOIN_SEMI = "join.semi"
JOIN_SEMI_MASK = "join.semi_mask"
#: the replicate route of a distributed join: the small side's live rows
#: gathered to every chip and front-packed (``parallel/shuffle.
#: replicate_cols``); the local join's own stages follow it
JOIN_REPLICATE = "join.replicate"
SORT_KEYS = "sort.keys"
SORT_PERM = "sort.perm"
SORT_TOPK = "sort.topk"
SORT_ENGINE = "sort_engine"
SHUFFLE_COUNT = "shuffle.count"
SHUFFLE_PACK = "shuffle.pack"
SHUFFLE_ALL_TO_ALL = "shuffle.all_to_all"
SHUFFLE_COMPACT = "shuffle.compact"
SHUFFLE_REASSEMBLE = "shuffle.reassemble"
SEMI_SKETCH = "semi.sketch"
GROUPBY_SEGMENT_SUM = "groupby.segment_sum"
GROUPBY_KEY_IDS = "groupby.key_ids"
GROUPBY_DENSE_AGG = "groupby.dense_agg"
GROUPBY_COMBINE = "groupby.combine"
#: the two programs either side of a group-by's exchange of partial rows
#: (``Table.distributed_groupby``): a shard's rows reduced to one partial
#: row a group, and the received partial rows combined and finished. The
#: sort-and-segment stages nest inside both, so in a trace of such a query
#: these two are the outermost names and take the time.
GROUPBY_PARTIAL = "groupby.partial"
GROUPBY_MERGE = "groupby.merge"
EXPR_EVAL = "expr.eval"

VOCABULARY = (
    JOIN_KEY_IDS, JOIN_RIGHT_SORT, JOIN_PROBE, JOIN_EMIT, JOIN_SEMI,
    JOIN_SEMI_MASK, JOIN_REPLICATE,
    SORT_KEYS, SORT_PERM, SORT_TOPK, SORT_ENGINE,
    SHUFFLE_COUNT, SHUFFLE_PACK, SHUFFLE_ALL_TO_ALL, SHUFFLE_COMPACT,
    SHUFFLE_REASSEMBLE,
    SEMI_SKETCH, GROUPBY_SEGMENT_SUM, GROUPBY_KEY_IDS, GROUPBY_DENSE_AGG,
    GROUPBY_COMBINE, GROUPBY_PARTIAL, GROUPBY_MERGE, EXPR_EVAL,
)
_VOCABULARY = frozenset(VOCABULARY)


def stage_of(op_name: str) -> Optional[str]:
    """The outermost vocabulary name on an ``op_name`` path, or None."""
    for part in op_name.split("/"):
        if part in _VOCABULARY:
            return part
    return None


def in_sort_engine(op_name: str) -> bool:
    return SORT_ENGINE in op_name.split("/")


# ----------------------------------------------------------------------
# fetch sites: where the host waits for the device
# ----------------------------------------------------------------------
#: every ``table._fetch(arr, site)`` call site, by name, with whether the
#: fetch is a COUNT SYNC (it bumps the rollup counter ``host_sync``, the
#: census that ``analysis/contracts.py`` pins and ``host_syncs`` reads) or
#: a transfer of rows that rides a sync already counted. ``obs.trace``
#: times each site as the rollup span ``host_sync.<site>`` and names the
#: device's idle time after it ``host.gap.<site>``; a site outside this
#: table is a ``KeyError`` at the fetch (``tests/test_host_timeline.py``
#: scans the package for literals).
FETCH_SITES = {
    "table.counts": True,         # Table._materialize_counts, the deferred one
    "stats.measure": True,        # Table.ensure_stats
    "to_numpy": False,            # Table._host_physical, a column's data
    "to_numpy.valid": False,      # ... and its validity lane
    "join.speculative": True,     # Table.join, the speculative program's totals
    "join.exact_counts": True,    # ... the exact path's probe counts
    "join.semi": True,            # Table._semi_reduced, the three counts
    "join.semi_join": True,       # Table._semi_join, the kept rows' count
    "join.pallas_pk": True,       # Table._pallas_pk_join
    "join.fused": True,           # distributed_join(mode="fused")
    "shuffle.counts": True,       # _shuffle_many, the count kernel's matrix
    "shuffle.round_counts": True,  # _shuffle_many_rounds, every round's counts
    "spill.stage_lanes": True,    # parallel/spill.stage_table
    "spill.stage_passthrough": False,
    "spill.relay_lanes": True,    # parallel/spill.fetch_relay
    "spill.relay_passthrough": False,
    "task.counts": True,          # parallel/task.task_partition
}


# ----------------------------------------------------------------------
# which programs ran, with which shapes and shardings
# ----------------------------------------------------------------------
#: programs remembered per context (a context's ``_jit_cache`` is not
#: bounded either; this only keeps a runaway key sweep from growing a
#: spec per value)
MAX_PROGRAMS = 512

# contexts that hold a ``_dispatch_specs`` map. The map lives ON the
# context (like ``_jit_cache``), so it dies with it: a module-level map
# would keep every context alive through the jitted closures it holds.
_CONTEXTS: "weakref.WeakSet" = weakref.WeakSet()
_LOCK = threading.Lock()


def arg_spec(args):
    """Shapes in place of arrays — the ONE copy of the rule, shared by the
    roofline recorder (``engine.record_dispatch``) and the stage table.
    Besides shape and dtype a spec carries ``weak_type`` and the sharding
    of every COMMITTED array: without them ``fn.lower(*spec)`` is another
    program than the one that ran, with other instruction numbers.
    Uncommitted arrays (the replicated capacity dummies) keep ``None``;
    giving those a sharding fails with "incompatible devices"."""

    def one(x):
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            return x
        sharding = (
            x.sharding
            if isinstance(x, jax.Array) and getattr(x, "committed", False)
            else None
        )
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding,
            weak_type=bool(getattr(x, "weak_type", False)),
        )

    return jax.tree.map(one, args)


def register_dispatch(ctx, key, fn, args) -> None:
    """Remember ``(fn, spec)`` of a program's first dispatch on ``ctx``."""
    with _LOCK:
        specs = ctx.__dict__.setdefault("_dispatch_specs", {})
        if key not in specs and len(specs) < MAX_PROGRAMS:
            specs[key] = (fn, arg_spec(args))
        _CONTEXTS.add(ctx)


def dispatched_programs(ctx=None) -> list:
    """``(key, fn, spec)`` of every program registered by a live context
    (by ``ctx`` alone where one is given)."""
    with _LOCK:
        return [
            (key, fn, spec)
            for c in ([ctx] if ctx is not None else list(_CONTEXTS))
            for key, (fn, spec) in c.__dict__.get(
                "_dispatch_specs", {}
            ).items()
        ]


# ----------------------------------------------------------------------
# compiled text -> rows
# ----------------------------------------------------------------------
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.MULTILINE)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_METADATA = re.compile(r",?\s*metadata=\{[^}]*\}")
#: a callee whose instructions never run as operations of their own: the
#: body of a fusion, the scalar region of a reduce / sort / scatter
_INLINED = re.compile(r"\s(\w[\w\-]*)\(.*?(?:calls|to_apply)=%?([\w.\-]+)")
_PATH = re.compile(r'"([^"\s]*/[^"\s]*)"')


def _compiled_text(lowered) -> str:
    """Optimized HLO of the executable a lowering compiles to (a
    persistent-cache load when warm)."""
    return lowered.compile().as_text()


def _lowered_text(lowered) -> str:
    """StableHLO of a fresh lowering with its locations: never cached."""
    return lowered.as_text(debug_info=True)


def parse_compiled(text: str) -> tuple:
    """``(module name, [(instruction text without metadata, op_name)])``
    for every instruction of ``text`` that can run as an operation of its
    own; ``op_name`` is "" where the compiler left none."""
    found = _MODULE.search(text)
    module = found[1] if found else ""
    inlined, computations, current = set(), {}, None
    for line in text.splitlines():
        if not line.startswith(" "):
            # `%fused_computation.4 (p: s32[16]) -> s32[16] {`, `ENTRY ...`
            current = None
            if line.endswith("{") and not line.startswith("HloModule"):
                name = line.split()[1 if line.startswith("ENTRY") else 0]
                current = computations.setdefault(name.lstrip("%"), [])
            continue
        body = line.strip()
        if current is None or " = " not in body:
            continue
        op_name = _OP_NAME.search(body)
        body = _METADATA.sub("", body)
        callee = _INLINED.search(body)
        if callee and callee[1] != "call":
            inlined.add(callee[2])
        current.append(
            (body[5:] if body.startswith("ROOT ") else body,
             op_name[1] if op_name else "")
        )
    rows = [
        row for name, rows in computations.items()
        if name not in inlined for row in rows
    ]
    return module, rows


def _names_in(paths) -> set:
    return {
        part for path in paths for part in path.split("/")
        if part in _VOCABULARY
    }


def device_stage_table(ctx=None) -> dict:
    """The stage of every instruction of every program this process (or
    ``ctx`` alone) has dispatched: ``{"rows": [(module, instruction text,
    op_name)], "stale": [module], "programs": n, "seconds": s}``.

    ``stale`` names the modules whose compiled text carries stage names
    the program no longer has: JAX's persistent compile cache keys strip
    debug info, so an executable compiled before a scope moved comes back
    with the old names. A name in the compiled text that a fresh lowering
    lacks, or a fresh lowering with names and a compiled text with none,
    says so (the compiler may drop a stage whole, so a name missing from
    the compiled text alone proves nothing). A program that re-specialised
    to a second shape under one key is here only with its first."""
    t0 = time.perf_counter()
    rows, stale, programs = [], [], 0
    for _key, fn, spec in dispatched_programs(ctx):
        lowered = fn.lower(*spec)
        module, found = parse_compiled(_compiled_text(lowered))
        compiled = _names_in(op for _text, op in found)
        fresh = _names_in(_PATH.findall(_lowered_text(lowered)))
        if compiled - fresh or (fresh and not compiled):
            stale.append(module)
        rows.extend((module, text, op) for text, op in found)
        programs += 1
    return {
        "rows": rows, "stale": sorted(set(stale)), "programs": programs,
        "seconds": time.perf_counter() - t0,
    }
