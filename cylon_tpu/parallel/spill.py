"""Spill tiers + skew-adaptive round scheduling: ONE budget-driven planner
for every table that does not fit a single padded exchange.

The reference streams arbitrarily large tables through fixed-size buffers
(arrow_all_to_all.cpp:83-141). Our TPU engine used to have two disjoint
answers to "table doesn't fit": the chunked ``_shuffle_many`` rounds
(tier 0) and ``parallel/ooc.py``'s private Grace-style spill rounds that
saw none of the engine's header fusion / lane packing / semi filtering.
Per Exoshuffle (PAPERS.md), shuffle should be ONE application-level
composition whose spill tiers are policy — this module is that policy:

tier 0 (HBM)
    Today's K bounded rounds; every round's compacted output stays
    device-resident until the final concat. Chosen when the measured
    received rows fit the device spill budget.
tier 1 (host RAM)
    The same K rounds, but each round's compacted output is fetched into
    a host :class:`HostArena` as soon as the NEXT round is dispatched
    (one-deep overlap), so peak device memory is the round buffers plus
    at most two staged outputs — never the whole table.
tier 2 (disk)
    Tier 1 with ``np.memmap``-backed arenas under ``CYLON_TPU_SPILL_DIR``
    (or a tempdir); engaged when the host budget is exceeded, or forced.

The tier is chosen PER SHUFFLE from the per-bucket counts the fused count
pass already returns for free (:func:`choose_tier`), so every
``Distributed*`` op transparently scales past HBM through the same
``_shuffle_many`` loop.

Skew-adaptive round splitting (:func:`plan_schedule`) rides the same
measured counts: an equal-chunk ``all_to_all`` must ship
``K x world^2 x cap`` rows no matter how empty the cold buckets are, so a
one-hot key distribution pays a ``world``-fold padding tax that no cap
choice can remove. The adaptive schedule therefore keeps the collective
rounds sized for the COLD buckets (cap, K and the per-bucket quota
``K*cap`` derived from the histogram — the ``(cap, bucket-slice)``
schedule threaded through ``build_send_slots_round`` / ``round_counts``,
whose round windows already implement the quota clamp) and moves each
heavy bucket's tail through the spill machinery instead: a relay
extraction kernel packs the over-quota rows once, they cross through host
RAM, and land directly on their owner shard. A one-hot distribution then
ships O(rows) bytes instead of O(world x max-bucket) — ``_shuffle_many``
emits the traced ``shuffle.skew_split`` counter and non-skewed plans stay
byte-identical to :func:`~cylon_tpu.parallel.shuffle.plan_rounds`.

From which world size on the split can engage: a destination is heavy
when one of its buckets is OVER ``SKEW_MIN_RATIO`` = 4 times the mean
bucket. Sources that hold equal rows R (every table loaded by an even row
split) give a mean bucket of ``R / world``, and no bucket holds more than
R, so on up to four shards nothing is ever heavy: an eager shuffle there
pays for skew with rounds and padded slots alone (``shuffle.coll_rows``
over ``shuffle.coll_slots`` says how many), and the split and the host
relay start at five shards, in practice eight. Only unevenly loaded
sources or a tuned trigger under 4 (``plan/feedback.tuned_skew_trigger``,
inside a lowered plan) engage it on four.
``tests/test_fkjoin_skew.py`` holds both halves of that.
"""
from __future__ import annotations

import errno
import os
import shutil
import tempfile
import threading
import time as _time
from collections import OrderedDict, deque
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from ..fault import inject as _fault
from ..fault.errors import SpillIOError
from ..ops import gather as _g
from ..utils import envgate as _envgate
from ..utils.tracing import bump, gauge, span
from . import shuffle as _sh

TIER_HBM = 0
TIER_HOST = 1
TIER_DISK = 2
TIER_NAMES = {TIER_HBM: "hbm", TIER_HOST: "host", TIER_DISK: "disk"}

# ----------------------------------------------------------------------
# knobs (registered in utils/envgate.py; resolvers mirror config.py's
# shuffle_byte_budget pattern)
# ----------------------------------------------------------------------

# kill switch for the skew-adaptive schedule: the padded-plan oracle for
# differentials. Host-only by construction — the gate changes which
# (cap, K) the HOST picks and whether the separately-keyed ('relay',)
# extraction program dispatches; no kernel body ever reads it.
skew_enabled, skew_disabled = _envgate.env_gate(
    "CYLON_TPU_NO_SKEW_SPLIT",
    keyed_via="host round planning only: cap/K reach kernels as operand "
    "shapes + traced round scalars, and the relay extraction dispatches "
    "under its own ('relay',) cache-key suffix; no kernel body reads the "
    "gate",
    note="=1 disables skew-adaptive round splitting (padded-plan oracle)",
)

#: a heavy bucket exceeds this multiple of the mean bucket count
SKEW_MIN_RATIO = 4
#: apply the adaptive schedule only when it cuts decision cost >= 25%
SKEW_MIN_SAVINGS = 0.25
#: host-relayed bytes cross PCIe twice (fetch + restage), so they count
#: double against the collective bytes they replace
RELAY_COST_FACTOR = 2.0


def forced_tier() -> Optional[int]:
    """The CYLON_TPU_SPILL_TIER override (None = measured decision)."""
    v = _envgate.SPILL_TIER.get()
    if v == "":
        return None
    t = int(v)
    if t not in (TIER_HBM, TIER_HOST, TIER_DISK):
        raise ValueError(f"CYLON_TPU_SPILL_TIER must be 0/1/2, got {v!r}")
    return t


def device_spill_budget() -> Optional[int]:
    """Per-shard staged-output bytes above which a shuffle spills its
    rounds off-device (None = never: tier 0 unless forced)."""
    v = _envgate.SPILL_DEVICE_BUDGET.get()
    return int(v) if v else None


def host_spill_budget() -> Optional[int]:
    """Total live host-arena bytes above which NEW arena growth goes to
    disk-backed buffers (None = unlimited host RAM)."""
    v = _envgate.SPILL_HOST_BUDGET.get()
    return int(v) if v else None


def spill_dir() -> Optional[str]:
    return _envgate.SPILL_DIR.get() or None


#: every engine spill directory is named <prefix><host>-<pid>_<random>:
#: the host+pid stamp makes dead-owner reclamation provable (mirrors the
#: obs store's journal-<pid>.jsonl dead-writer reaping). The HOST tag
#: matters on shared volumes (NFS scratch): pid liveness is only
#: decidable on the owning host, so reaping is strictly same-host.
SPILL_DIR_PREFIX = "cylon_spill_"
#: a dead-pid spill dir must be at least this stale before reaping — the
#: age guard against a dir whose owner died between mkdtemp and first
#: write racing its own cleanup, and against coarse pid recycling
REAP_MIN_AGE_S = 60.0


def _host_tag() -> str:
    """This host's stamp: alnum-only (unambiguous '-pid' parsing),
    bounded length."""
    import platform

    node = platform.node() or "host"
    tag = "".join(c for c in node if c.isalnum()).lower()
    return (tag or "host")[:32]


def reap_stale_spill(
    directory: Optional[str] = None, min_age_s: Optional[float] = None
) -> int:
    """Reclaim spill directories orphaned by dead SAME-HOST processes:
    every ``<SPILL_DIR_PREFIX><host>-<pid>_*`` entry of the spill volume
    stamped with THIS host whose pid no longer exists and whose mtime is
    older than the age guard is removed. Called (best-effort, never
    raising) at context init — the same lifecycle point the obs store
    reaps dead-writer journals — so a crashed job's tier-2 leftovers
    cannot fill the volume forever. Returns the number removed.

    Live pids, other hosts' dirs (their pid namespace is not ours —
    a shared NFS spill volume must never cross-reap), unparseable names
    (pre-stamp legacy dirs), fresh dirs, and anything ``os.kill(pid,
    0)`` cannot prove dead are left alone: reclamation must never eat a
    live process's arenas."""
    root = directory or spill_dir() or tempfile.gettempdir()
    if min_age_s is None:
        min_age_s = REAP_MIN_AGE_S
    reaped = 0
    try:
        names = os.listdir(root)
    except OSError:
        return 0
    now = _time.time()
    own = os.getpid()
    host = _host_tag()
    for name in names:
        if not name.startswith(SPILL_DIR_PREFIX):
            continue
        owner = name[len(SPILL_DIR_PREFIX):].split("_", 1)[0]
        if "-" not in owner:
            continue  # pre-stamp legacy dir: owner unknowable
        dir_host, pid_s = owner.rsplit("-", 1)
        if dir_host != host or not pid_s.isdigit() or int(pid_s) == own:
            continue
        try:
            os.kill(int(pid_s), 0)
            continue  # alive (or recycled): never touch it
        except ProcessLookupError:
            pass
        except OSError:
            continue  # cannot prove dead: assume alive
        path = os.path.join(root, name)
        try:
            if not os.path.isdir(path):
                continue
            if now - os.path.getmtime(path) < min_age_s:
                continue
        except OSError:
            continue
        shutil.rmtree(path, ignore_errors=True)
        reaped += 1
    if reaped:
        bump("shuffle.spill.reaped_dirs", rows=reaped)
    return reaped


def spill_retries() -> int:
    """Bounded-backoff retries for a failed spill write/read before the
    degradation ladder engages (CYLON_TPU_SPILL_RETRIES, default 2)."""
    v = _envgate.SPILL_RETRIES.get()
    try:
        return max(int(v), 0) if v else 2
    except ValueError:
        return 2


#: first-retry backoff; doubles per attempt (bounded by the retry count)
RETRY_BACKOFF_S = 0.01


def _retry_io(what: str, fn, sink=None):
    """The spill I/O degradation ladder (the ISSUE's 'retry -> tier
    fallback -> typed query-scoped failure'):

    1. retry ``fn`` up to ``spill_retries()`` times with doubling
       backoff (``shuffle.spill.io_retries``) — transient ENOSPC/EIO
       heal here;
    2. exhausted: if ``sink`` can re-plan its disk arenas onto the
       host-RAM tier within the host budget
       (:meth:`ShardArenaSink.degrade_to_host`,
       ``shuffle.spill.tier_degraded``), do so and try once more;
    3. still failing: raise :class:`SpillIOError` — the typed,
       query-scoped failure (``shuffle.spill.io_failures``). The caller
       (``table._shuffle_many``) closes the sink arenas so the ledger
       returns to baseline; the process and every other query proceed.

    Only ``OSError`` rides the ladder — real spill-volume failures and
    the injected seam faults look identical here by design."""
    retries = spill_retries()
    delay = RETRY_BACKOFF_S
    attempt = 0
    while True:
        try:
            return fn()
        except SpillIOError:
            raise  # already typed (a nested ladder gave up): pass through
        except OSError as e:
            attempt += 1
            if attempt <= retries:
                bump("shuffle.spill.io_retries")
                _time.sleep(delay)
                delay *= 2
                continue
            if sink is not None and sink.degrade_to_host():
                bump("shuffle.spill.tier_degraded")
                try:
                    return fn()
                except OSError as e2:
                    e = e2
            bump("shuffle.spill.io_failures")
            raise SpillIOError(what, e) from e


def gate_state() -> tuple:
    """The spill-policy component of the plan fingerprint
    (plan/lazy.gated_fingerprint): forced tier + skew-split gate. Both
    are host-side dispatch policy, but a cached executor built under one
    state must not serve the other (the tier changes the staging path a
    lowered shuffle takes; the skew gate changes its round plan)."""
    return (_envgate.SPILL_TIER.get(), skew_enabled())


def choose_tier(staged_bytes: int, tuned: Optional[int] = None) -> int:
    """Tier for a shuffle whose measured received rows stage
    ``staged_bytes`` per shard: forced knob wins; else tier 0 while the
    device spill budget (unset = unlimited) holds, tier 1 beyond it.
    (Tier 1 arenas self-promote to disk when the HOST budget is exceeded
    — see :meth:`HostArena._alloc` — so the 1 vs 2 split is a property
    of the arena backing, not of this decision.)

    ``tuned`` is the feedback re-coster's decision (plan/feedback.py,
    observed peak staged bytes near the budget line): it can only
    PROMOTE past the measured decision — spilling early is a memory
    policy; demoting below the measured need would OOM."""
    f = forced_tier()
    if f is not None:
        return f
    budget = device_spill_budget()
    tier = (
        TIER_HBM
        if budget is None or staged_bytes <= budget
        else TIER_HOST
    )
    if tuned is not None and tuned > tier:
        bump("autotune.tier_promoted")
        tier = tuned
    return tier


# ----------------------------------------------------------------------
# skew-adaptive round schedule
# ----------------------------------------------------------------------

class RoundSchedule(NamedTuple):
    """One shuffle's planned rounds. ``relay=None`` means the plan is the
    uniform padded plan, bit-for-bit what :func:`plan_rounds` returns.
    With ``relay`` (a [src, dst] row matrix), each bucket ships only its
    first ``quota = n_rounds * bucket_cap`` rows through the collective
    rounds (the existing round windows enforce exactly that) and the
    tails cross through the host relay."""

    bucket_cap: int
    n_rounds: int
    relay: Optional[np.ndarray]  # [world, world] over-quota rows, or None

    @property
    def adaptive(self) -> bool:
        return self.relay is not None

    @property
    def quota(self) -> int:
        return self.bucket_cap * self.n_rounds

    def coll_row_slots(self, world: int) -> int:
        """Global collective row slots shipped: K x world^2 x cap."""
        return self.n_rounds * world * world * self.bucket_cap

    def relay_rows(self) -> int:
        return 0 if self.relay is None else int(self.relay.sum())

    def relay_cap(self) -> int:
        """Static per-source relay buffer rows (pow2, engine minimum 8)."""
        if self.relay is None:
            return 0
        from ..engine import round_cap

        return round_cap(int(self.relay.sum(axis=1).max()))


def plan_schedule(
    send_counts: np.ndarray,
    row_bytes: int,
    world: int,
    byte_budget: int,
    max_rounds: int = _sh.DEFAULT_MAX_ROUNDS,
    trigger: Optional[int] = None,
) -> RoundSchedule:
    """The budget-driven round schedule for a measured [src, dst] count
    matrix. Non-skewed distributions return exactly ``plan_rounds``'
    (cap, K) with no relay — byte-identical plans, same compiled kernels.

    Heavy buckets (above ``trigger`` x the mean bucket; default the
    static ``SKEW_MIN_RATIO`` = 4) re-plan the collective rounds against
    the COLD histogram and relay their tails through the host, but only
    when that cuts the cost model (collective slots +
    ``RELAY_COST_FACTOR`` x relayed rows) by at least
    ``SKEW_MIN_SAVINGS`` — marginal skew keeps the padded plan.

    ``trigger`` is the feedback re-coster's tuned engagement ratio
    (``Decisions.skew_trigger``, plan/feedback.py): observed straggler
    evidence lowers it so MILD skew the 4x default ignores still sheds
    its padded slots through the relay. Policy only — relayed rows reach
    the same destinations, results are bit-identical either way — and
    the tuned value rides the plan fingerprint (the Decisions component)
    so a flip recompiles, never aliases.

    With the static trigger the split engages from five shards on: over
    ``world`` sources of equal rows R the mean bucket is ``R / world``
    and a bucket is at most R, which is never over 4 times the mean while
    ``world <= 4`` (the module docstring).
    """
    cap0, k0 = _sh.plan_rounds(
        send_counts, row_bytes, world, byte_budget, max_rounds
    )
    base = RoundSchedule(cap0, k0, None)
    # lint: key=CYLON_TPU_NO_SKEW_SPLIT -- the gate decides HOST planning
    # only: cap/K reach every round kernel through operand shapes (jit
    # shape specialization) and the relay extraction dispatches under its
    # own ('relay',) cache-key suffix, so no compiled program can alias
    # across a gate flip; the plan fingerprint carries the gate via
    # spill.gate_state (plan/lazy.gated_fingerprint)
    if not skew_enabled():
        return base
    m = np.asarray(send_counts, np.int64).reshape(-1, world)
    if m.size == 0 or m.max() == 0:
        return base
    mean_bucket = -(-int(m.sum()) // m.size)
    heavy_thresh = max(
        max(int(trigger), 1) if trigger else SKEW_MIN_RATIO, 1
    ) * mean_bucket
    heavy_thresh = max(heavy_thresh, 8)
    heavy_cols = m.max(axis=0) > heavy_thresh
    if not heavy_cols.any() or heavy_cols.all():
        # all-heavy == uniformly large: nothing to rebalance against
        return base
    cold_max = int(m[:, ~heavy_cols].max()) if (~heavy_cols).any() else 0
    clipped = np.minimum(m, max(cold_max, 1))
    cap_c, k_c = _sh.plan_rounds(
        clipped, row_bytes, world, byte_budget, max_rounds
    )
    quota = cap_c * k_c
    relay = np.maximum(m - quota, 0)
    if int(relay.sum()) == 0:
        return base
    adaptive = RoundSchedule(cap_c, k_c, relay)
    cost_base = base.coll_row_slots(world)
    cost_adapt = (
        adaptive.coll_row_slots(world)
        + RELAY_COST_FACTOR * adaptive.relay_rows()
    )
    if cost_adapt > (1.0 - SKEW_MIN_SAVINGS) * cost_base:
        return base
    return adaptive


# ----------------------------------------------------------------------
# host / disk arenas
# ----------------------------------------------------------------------

_arena_lock = threading.Lock()
_ARENA_LIVE_BYTES = 0
_ARENA_PEAK_BYTES = 0
_ARENA_DISK_BYTES = 0
_ARENA_DISK_PEAK = 0
#: ``(bytes, disk bytes)`` of arenas the collector took unclosed. Their
#: ``__del__`` only appends here (the finalizer rule, ``obs/__init__.py``);
#: the next touch of the accounts below gives the bytes back
_ARENA_DEAD: "deque" = deque()


def _arena_adjust(delta: int, disk_delta: int) -> None:
    """Track total live arena bytes and, separately, their memmap-backed
    (tier-2) slice, so the resource ledger reports host RAM and spill
    disk as distinct watermarks; each gauge's max is the process peak
    (the satellite's 'report peak host bytes' evidence)."""
    global _ARENA_LIVE_BYTES, _ARENA_PEAK_BYTES
    global _ARENA_DISK_BYTES, _ARENA_DISK_PEAK
    with _arena_lock:
        while _ARENA_DEAD:  # popped under the lock only: no other taker
            nbytes, disk = _ARENA_DEAD.popleft()
            delta -= nbytes
            disk_delta -= disk
        _ARENA_LIVE_BYTES += delta
        _ARENA_PEAK_BYTES = max(_ARENA_PEAK_BYTES, _ARENA_LIVE_BYTES)
        _ARENA_DISK_BYTES += disk_delta
        _ARENA_DISK_PEAK = max(_ARENA_DISK_PEAK, _ARENA_DISK_BYTES)
        live, disk = _ARENA_LIVE_BYTES, _ARENA_DISK_BYTES
    gauge("shuffle.spill.host_bytes", live)
    gauge("shuffle.spill.disk_bytes", disk)


def arena_bytes() -> tuple:
    """(live, peak, disk_live, disk_peak) total arena bytes — the
    resource ledger's host/disk axis (obs/resource.py wraps these beside
    the ``shuffle.spill.*`` gauges). A collected arena's bytes leave the
    live figures, and the gauges, here at the latest."""
    if _ARENA_DEAD:
        _arena_adjust(0, 0)
    with _arena_lock:
        return (
            _ARENA_LIVE_BYTES, _ARENA_PEAK_BYTES,
            _ARENA_DISK_BYTES, _ARENA_DISK_PEAK,
        )


class HostArena:
    """Preallocated columnar arena for spilled rows.

    ``schema``: ``[(name, np_dtype, has_valid)]``. Growth is by explicit
    :meth:`reserve` (callers size it from the fused count pass, so the
    steady state never copies) with geometric doubling as the fallback.
    RAM-backed by default; buffers allocate as ``np.memmap`` under the
    spill dir when ``backing=TIER_DISK`` or when total live arena bytes
    exceed the host spill budget (automatic tier-1 -> tier-2 promotion).
    Object-dtype columns (decoded dictionary values) always stay in RAM
    — only fixed-width columns can spill to disk.

    :meth:`close` is how an operator gives the bytes back, with the
    gauges reported at once. An arena the collector takes unclosed
    follows the finalizer rule (``obs/__init__.py``): ``__del__`` takes
    no lock, reports no gauge and touches no trace; it unlinks its files
    and its directory and leaves its byte counts on ``_ARENA_DEAD`` for
    the next touch of the accounts."""

    def __init__(
        self,
        schema: Sequence[Tuple[str, np.dtype, bool]],
        backing: int = TIER_HOST,
        directory: Optional[str] = None,
    ) -> None:
        self.schema = [(n, np.dtype(d), bool(v)) for n, d, v in schema]
        self.backing = backing
        self.rows = 0
        self._cap = 0
        self._dir = directory
        self._owns_dir = False
        self._nfiles = 0
        self._bytes = 0
        self._disk = 0
        # set by to_host(): this arena degraded off a failing spill
        # volume — never allocate (or budget-promote) back onto disk
        self._no_disk = False
        # per column: [data buffer, valid buffer | None]
        self._bufs: List[List[Optional[np.ndarray]]] = [
            [None, None] for _ in self.schema
        ]

    # -- allocation ----------------------------------------------------
    def _ensure_dir(self) -> str:
        if self._dir is None:
            # host+pid-stamped (SPILL_DIR_PREFIX): context init reaps
            # same-host dead-pid leftovers (reap_stale_spill) the way
            # the obs store reaps dead-writer journals — a crashed
            # process's spill files must not accumulate on the volume
            # forever, and a shared volume must never cross-reap
            self._dir = tempfile.mkdtemp(
                prefix=f"{SPILL_DIR_PREFIX}{_host_tag()}-{os.getpid()}_",
                dir=spill_dir(),
            )
            self._owns_dir = True
        return self._dir

    def _alloc(self, dtype: np.dtype, n: int) -> np.ndarray:
        _fault.check("arena.alloc")
        if self._no_disk:
            want_disk = False  # degraded arena: disk is pinned off
            hb = host_spill_budget()
            if hb is not None and arena_bytes()[0] >= hb:
                # the degradation escape is closed (this arena already
                # fled a failing volume) AND the host budget is spent:
                # growing regardless would trade a typed query failure
                # for the host OOM the failure model forbids. The raise
                # rides the same `except OSError` ladder as a real
                # ENOSPC — retries exhaust, degrade_to_host() finds
                # nothing left to move, SpillIOError fails ONLY this
                # query with its arenas closed.
                raise OSError(
                    errno.ENOSPC,
                    "host spill budget exhausted on a disk-degraded "
                    f"arena (CYLON_TPU_SPILL_HOST_BUDGET={hb}, live "
                    f"{_ARENA_LIVE_BYTES})",
                )
        else:
            want_disk = self.backing == TIER_DISK
            if not want_disk:
                hb = host_spill_budget()
                if hb is not None and arena_bytes()[0] >= hb:
                    want_disk = True
                    bump("shuffle.spill.tier2_promotions")
        if want_disk and dtype != np.dtype(object):
            self._nfiles += 1
            path = os.path.join(
                self._ensure_dir(), f"col{self._nfiles}.bin"
            )
            return np.memmap(path, dtype=dtype, mode="w+", shape=(n,))
        return np.empty((n,), dtype)

    @staticmethod
    def _release_buf(buf) -> None:
        """Drop a superseded buffer's disk backing: growth/promotion
        replaces memmaps with fresh files, and the dead generation must
        not accumulate on the spill volume (POSIX unlink-while-mapped is
        safe; the mapping dies with the last array reference)."""
        if isinstance(buf, np.memmap):
            try:
                os.unlink(buf.filename)
            except OSError:
                pass

    def _recount_bytes(self) -> None:
        """Re-derive live bytes from the actual buffers (growth AND
        dtype promotion both land here, so the host-budget check and the
        ``shuffle.spill.host_bytes`` gauge never understate memory)."""
        total = 0
        disk = 0
        for (name, dtype, _hv), (d, v) in zip(self.schema, self._bufs):
            if d is not None:
                total += self._cap * 8 if dtype == np.dtype(object) else d.nbytes
                if isinstance(d, np.memmap):
                    disk += d.nbytes
            if v is not None:
                total += v.nbytes
                if isinstance(v, np.memmap):
                    disk += v.nbytes
        _arena_adjust(total - self._bytes, disk - self._disk)
        self._bytes = total
        self._disk = disk

    def reserve(self, extra: int) -> None:
        """Ensure capacity for ``extra`` more rows (count-pass sizing:
        call with the exact incoming total and no growth copy happens)."""
        target = self.rows + int(extra)
        if target <= self._cap:
            return
        new_cap = max(target, 2 * self._cap)
        for ci, (name, dtype, has_valid) in enumerate(self.schema):
            old_d, old_v = self._bufs[ci]
            d = self._alloc(dtype, new_cap)
            if old_d is not None:
                d[: self.rows] = old_d[: self.rows]
                self._release_buf(old_d)
            self._bufs[ci][0] = d
            if has_valid:
                v = self._alloc(np.dtype(bool), new_cap)
                if old_v is not None:
                    v[: self.rows] = old_v[: self.rows]
                    self._release_buf(old_v)
                self._bufs[ci][1] = v
        self._cap = new_cap
        self._recount_bytes()

    def promote(self, ci: int, new_dtype) -> None:
        """Widen one column's buffer dtype in place. Decoded-value sinks
        (parallel/ooc.py) need this: a later batch may carry nulls that
        decode wider (int32 -> float64-with-NaN) or strings that decode
        to object — the arena follows the widest batch seen."""
        name, old, has_valid = self.schema[ci]
        new_dtype = np.dtype(new_dtype)
        if new_dtype == old:
            return
        self.schema[ci] = (name, new_dtype, has_valid)
        buf = self._bufs[ci][0]
        if buf is not None:
            nb = self._alloc(new_dtype, self._cap)
            nb[: self.rows] = buf[: self.rows]
            self._release_buf(buf)
            self._bufs[ci][0] = nb
            self._recount_bytes()

    def touches_disk(self) -> bool:
        """Does this arena hold — or would its next allocation target —
        disk-backed buffers? The spill.write/read seams fire only here:
        a RAM write cannot ENOSPC, and the tier-degradation escape must
        GENUINELY escape a persistently failing volume."""
        return self._disk > 0 or (
            self.backing == TIER_DISK and not self._no_disk
        )

    def to_host(self) -> bool:
        """Migrate every disk-backed buffer into RAM and pin this arena
        off disk (the tier 2 -> tier 1 DEGRADATION, inverse of the
        budget promotion). Returns False — arena unchanged beyond any
        already-copied columns — when the migration itself fails."""
        try:
            for pair in self._bufs:
                for j in (0, 1):
                    buf = pair[j]
                    if isinstance(buf, np.memmap):
                        pair[j] = np.array(buf)
                        self._release_buf(buf)
        except OSError:
            return False
        self.backing = TIER_HOST
        self._no_disk = True
        self._recount_bytes()
        return True

    # -- data path -----------------------------------------------------
    def append_batch(self, cols: Sequence[Tuple[np.ndarray, Optional[np.ndarray]]]) -> None:
        """Append one batch of physical columns (order = schema order)."""
        n = len(cols[0][0]) if cols else 0
        if n == 0:
            return
        if self.touches_disk():
            _fault.check("spill.write")
        self.reserve(n)
        lo, hi = self.rows, self.rows + n
        for ci, (data, valid) in enumerate(cols):
            self._bufs[ci][0][lo:hi] = data
            vb = self._bufs[ci][1]
            if vb is not None:
                vb[lo:hi] = True if valid is None else valid
        self.rows = hi

    def columns(self) -> List[Tuple[np.ndarray, Optional[np.ndarray]]]:
        """Zero-copy live views, schema order."""
        if self._disk > 0:
            _fault.check("spill.read")
        out = []
        for ci, (_n, _d, has_valid) in enumerate(self.schema):
            d, v = self._bufs[ci]
            if d is None:
                d = self._alloc(self.schema[ci][1], 0)
            out.append(
                (d[: self.rows], v[: self.rows] if v is not None else None)
            )
        return out

    @property
    def nbytes(self) -> int:
        return self._bytes

    def close(self) -> None:
        _arena_adjust(-self._bytes, -self._disk)
        self._bytes = 0
        self._disk = 0
        self._free()
        self._bufs = [[None, None] for _ in self.schema]
        self._cap = 0
        self.rows = 0

    def _free(self) -> None:
        """Release what only this arena owns: its memmap files and the
        spill directory it made. No lock is behind either (``os.unlink``
        and ``shutil.rmtree`` are plain system calls)."""
        for pair in self._bufs:
            self._release_buf(pair[0])
            self._release_buf(pair[1])
        if self._owns_dir and self._dir is not None:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None
            self._owns_dir = False

    def __del__(self):
        # the finalizer rule (obs/__init__.py): the collector runs this
        # on a thread that may hold any lock, the accounts' included
        try:
            if self._bytes:  # the disk bytes are a part of them
                _ARENA_DEAD.append((self._bytes, self._disk))
                self._bytes = self._disk = 0
            self._free()
        except Exception:
            pass


class ShardArenaSink:
    """The engine-internal tier-1/2 sink: one PHYSICAL-encoding arena per
    destination shard; :func:`arena_result` rebuilds the device table at
    the end with the source table's dtype/dictionary metadata, so a
    spilled shuffle's result is bit-identical to the in-HBM path.

    ``quant``: the lossy-tier column map ``{col_index: original
    np.dtype}`` (ops/quant.py q8). Quantized columns LIVE in the arenas
    as uint8 codes — 1 byte/row instead of 4-8, so the host/disk spill
    budgets stretch ~4x on float-heavy tables — with one block scale
    recorded per appended batch; :func:`arena_result` dequantizes at
    rebuild. Staged batches arrive pre-encoded from the device pack
    (codes + scale); host-side float batches (the skew relay's decoded
    tails) are re-encoded here with their own batch max-abs scale."""

    def __init__(self, world: int, schema, backing: int, quant=None) -> None:
        self.arenas = [HostArena(schema, backing) for _ in range(world)]
        self.quant = dict(quant) if quant else {}
        #: per (shard, col): [(row_end, scale)] quantized-batch segments
        self.qsegs = [
            {ci: [] for ci in self.quant} for _ in range(world)
        ]
        self.device_rows_peak = 0  # engine-reported, per shard

    def accept(self, table, shard_cols, counts, scales=None) -> None:
        """``shard_cols[s]`` = physical (data, valid) pairs of shard s's
        rows (host arrays); ``table`` carries metadata only. For
        quantized columns the data is either uint8 codes with
        ``scales[s][ci]`` supplied (the staged-round path), or float
        values to re-encode here (the relay path).

        Runs under the spill I/O degradation ladder (:func:`_retry_io`):
        a disk-full/EIO mid-append rolls the arenas back to the batch
        boundary and retries — then degrades the arenas to host RAM —
        then fails the one owning query typed (:class:`SpillIOError`).
        The rollback is a row-pointer + scale-segment reset; retried
        writes simply overwrite the partial batch."""
        rows0 = [a.rows for a in self.arenas]
        qsegs0 = [
            {ci: len(segs) for ci, segs in per.items()}
            for per in self.qsegs
        ]

        def attempt():
            for s, a in enumerate(self.arenas):
                a.rows = rows0[s]
                for ci, nseg in qsegs0[s].items():
                    del self.qsegs[s][ci][nseg:]
            self._accept_once(table, shard_cols, counts, scales)

        _retry_io("spill arena write", attempt, sink=self)

    def _accept_once(self, table, shard_cols, counts, scales=None) -> None:
        from ..ops import quant as _q

        for s, cols in enumerate(shard_cols):
            if not int(counts[s]):
                continue
            if self.quant:
                cols = list(cols)
                for ci in self.quant:
                    data, valid = cols[ci]
                    if data.dtype == np.uint8:
                        scale = float(scales[s][ci])
                    else:
                        scale = _q.np_maxabs(data)
                        data = _q.np_encode_q8(data, scale)
                        bump("shuffle.quant.spill_reencoded")
                    cols[ci] = (data, valid)
                    self.qsegs[s][ci].append(
                        (self.arenas[s].rows + int(counts[s]), scale)
                    )
            self.arenas[s].append_batch(cols)

    def dequantized_columns(self, s: int):
        """Shard ``s``'s physical columns with quantized columns decoded
        back to their original float dtype (segment-by-segment, each
        with its recorded block scale)."""
        from ..ops import quant as _q

        cols = self.arenas[s].columns()
        if not self.quant:
            return cols
        out = list(cols)
        for ci, dt in self.quant.items():
            codes, valid = out[ci]
            data = np.empty(codes.shape, dt)
            lo = 0
            for end, scale in self.qsegs[s][ci]:
                data[lo:end] = _q.np_decode_q8(codes[lo:end], scale, dt)
                lo = end
            assert lo == len(codes), "quantized segment bookkeeping hole"
            out[ci] = (data, valid)
        return out

    def counts(self) -> np.ndarray:
        return np.asarray([a.rows for a in self.arenas], np.int64)

    def degrade_to_host(self) -> bool:
        """Re-plan every disk-backed arena onto the host-RAM tier (the
        ladder's middle rung): allowed only when the host spill budget
        can absorb the migrated bytes — degrading past the budget would
        trade a typed query failure for a host OOM, the one outcome the
        failure model forbids. Returns True when at least one arena
        actually moved (i.e. a retry is worth making)."""
        hb = host_spill_budget()
        if hb is not None:
            live, _pk, _d, _dp = arena_bytes()
            if live > hb:
                return False
        moved = False
        for a in self.arenas:
            if a.touches_disk():
                if not a.to_host():
                    return False
                moved = True
        return moved

    def close(self) -> None:
        for a in self.arenas:
            a.close()


# ----------------------------------------------------------------------
# the spill-aware lane fetch (ops/gather host codec consumers)
# ----------------------------------------------------------------------

def _table_lane_parts(table):
    """(plan, pt_order, flat) of a table's columns under the lane codec."""
    flat = table._flat_cols()
    plan = _g.lane_plan(flat)
    pt_order = tuple(ci for ci, (tag, _nl, _hv) in enumerate(plan) if tag is None)
    return plan, pt_order, flat


def _unpack_host_shard(plan, pt_order, mat_s, pts_s, n):
    """One shard's physical columns from its fetched lane rows."""
    lanes = [
        np.ascontiguousarray(mat_s[:n, j]) for j in range(mat_s.shape[1])
    ]
    pt_map = {ci: pts_s[k][:n] for k, ci in enumerate(pt_order)}
    return _g.host_unpack_cols(plan, lanes, lambda ci: pt_map[ci])


def stage_table(sink, table, counts: np.ndarray, qspec=None) -> None:
    """Fetch one staged round's table into ``sink`` through the
    spill-aware lane codec: every int32-lane column rides ONE packed
    [rows, L] transfer (plus one per f64 passthrough column) and is
    decoded on the host (ops/gather.host_unpack_cols) — instead of one
    device round-trip per column. ``counts`` are the host-known received
    rows per shard (the engine's planned expectation; no extra count
    fetch).

    ``qspec``: the quantized-tier column signature (ops/quant.py; 'q8'
    entries only). Quantized float columns leave the int32 lane matrix
    as a uint8 code matrix + one block scale per (shard, column) — the
    PCIe crossing and the arena both hold 1 byte/row — and the codes
    ride into the sink still encoded (the arena stores quantized bytes;
    arena_result decodes). This function owns the spill staging sync
    sites (analysis/contracts.py 'spill.stage_table'); the quantized
    extras ride the existing passthrough fetch, adding no site."""
    from ..table import _fetch, get_kernel
    import jax.numpy as jnp

    ctx = table.ctx
    world = ctx.world_size
    plan, pt_order, flat = _table_lane_parts(table)
    if qspec is not None and not any(c == "q8" for c in qspec):
        qspec = None
    qplan, q_cols = (
        _g.quant_lane_parts(plan, qspec)
        if qspec is not None
        else (tuple(plan), ())
    )
    pt_eff = tuple(
        ci for ci in pt_order
        if qspec is None or qspec[ci] != "q8"
    )
    key = ("spill_pack", tuple(qplan))

    def build():
        def kern(dp, rep):
            # lint: keyed=q_cols -- pure function of the quantized lane
            # plan, which is the ("spill_pack", qplan) cache key itself
            if q_cols:
                (cols, cnts) = dp
                cap = cols[0][0].shape[0]
                live = jnp.arange(cap, dtype=jnp.int32) < cnts[0]
                lanes, passthrough, qcodes, qscales = _g.pack_cols_quant(
                    list(cols), qplan, q_cols, live=live
                )
            else:
                (cols,) = dp
                _plan, lanes, passthrough = _g.pack_cols(list(cols))
                cap = cols[0][0].shape[0]
            mat = (
                jnp.stack(lanes, axis=1)
                if lanes
                else jnp.zeros((cap, 0), jnp.int32)
            )
            # lint: keyed=pt_eff -- pure function of the (quantized) lane
            # plan, which is the ("spill_pack", qplan) cache key itself
            pts = tuple(passthrough[ci] for ci in pt_eff)
            if q_cols:
                pts = pts + (qcodes, qscales)
            return mat, pts

        return kern

    with span("shuffle.spill.stage", rows=int(np.sum(counts))):
        dp = (flat, table.counts_dev) if q_cols else (flat,)
        mat, pts = get_kernel(ctx, key, build)(dp, ())
        mat_np = np.asarray(_fetch(mat, "spill.stage_lanes"))
        pts_np = [
            np.asarray(_fetch(p, "spill.stage_passthrough")) for p in pts
        ]
    cap = mat_np.shape[0] // world
    mat_np = mat_np.reshape(world, cap, mat_np.shape[1])
    qmat_np = qsc_np = None
    if q_cols:
        qsc_np = pts_np[-1].reshape(world, len(q_cols))
        qmat_np = pts_np[-2].reshape(world, cap, len(q_cols))
        pts_np = pts_np[:-2]
    pts_np = [p.reshape(world, cap) for p in pts_np]
    shard_cols = []
    scales = []
    staged = 0
    for s in range(world):
        n = int(counts[s])
        if q_cols:
            qmap = {
                ci: np.ascontiguousarray(qmat_np[s, :n, k])
                for k, (ci, _dt) in enumerate(q_cols)
            }
            shard_cols.append(
                _g.host_unpack_cols_quant(
                    qplan,
                    [
                        np.ascontiguousarray(mat_np[s, :n, j])
                        for j in range(mat_np.shape[2])
                    ],
                    lambda ci, _pt=dict(
                        zip(pt_eff, [p[s][:n] for p in pts_np])
                    ): _pt[ci],
                    lambda ci, _dt: qmap[ci],
                )
            )
            scales.append(
                {
                    ci: float(qsc_np[s, k])
                    for k, (ci, _dt) in enumerate(q_cols)
                }
            )
        else:
            shard_cols.append(
                _unpack_host_shard(
                    plan, pt_order, mat_np[s], [p[s] for p in pts_np], n
                )
            )
        staged += n
    bump("shuffle.spill.staged_rounds")
    row_bytes = _sh.exchange_row_bytes(flat)
    bump("shuffle.spill.staged_bytes", rows=staged * row_bytes)
    if q_cols:
        # each quantized column staged 1 byte/row where the plain lane
        # codec ships 4 (8 for f64) — the arena-budget stretch evidence
        saved = sum(
            (8 if dt == "float64" else 4) - 1 for _ci, dt in q_cols
        )
        bump("shuffle.quant.spill_bytes_saved", rows=staged * saved)
    if q_cols:
        sink.accept(table, shard_cols, counts, scales=scales)
    else:
        # caller-owned sinks (the out-of-core ingestion path) keep the
        # original 3-arg accept contract
        sink.accept(table, shard_cols, counts)


def fetch_relay(
    ctx, plan, pt_order, mat, pts, relay: np.ndarray, qspec=None
):
    """Fetch the relay extraction kernel's output and regroup rows by
    DESTINATION shard on the host. ``relay`` is the planner's [src, dst]
    over-quota row matrix — the per-source buffers are destination-major
    (shuffle.relay_send_slots), so regrouping is pure slicing. Returns
    ``(per_dst_cols, per_dst_counts)`` where ``per_dst_cols[d]`` holds
    physical (data, valid) pairs of every row relayed to shard d.

    ``qspec``: the quantized-tier 'q8' signature — quantized float
    columns arrive as uint8 codes + one block scale per source shard
    (1 byte/row over PCIe) and are decoded here; a relayed row pays
    exactly one lossy crossing. Owns the relay fetch sync sites
    ('spill.fetch_relay'); the quantized extras ride the existing
    passthrough fetch, adding no site."""
    from ..ops import quant as _q
    from ..table import _fetch

    world = ctx.world_size
    if qspec is not None and not any(c == "q8" for c in qspec):
        qspec = None
    qplan, q_cols = (
        _g.quant_lane_parts(plan, qspec)
        if qspec is not None
        else (tuple(plan), ())
    )
    pt_eff = tuple(
        ci for ci in pt_order if qspec is None or qspec[ci] != "q8"
    )
    mat_np = np.asarray(_fetch(mat, "spill.relay_lanes"))
    pts_np = [np.asarray(_fetch(p, "spill.relay_passthrough")) for p in pts]
    cap = mat_np.shape[0] // world
    mat_np = mat_np.reshape(world, cap, mat_np.shape[1])
    qmat_np = qsc_np = None
    if q_cols:
        qsc_np = pts_np[-1].reshape(world, len(q_cols))
        qmat_np = pts_np[-2].reshape(world, cap, len(q_cols))
        pts_np = pts_np[:-2]
        bump(
            "shuffle.quant.relay_bytes_saved",
            rows=int(relay.sum())
            * sum((8 if dt == "float64" else 4) - 1 for _c, dt in q_cols),
        )
    pts_np = [p.reshape(world, cap) for p in pts_np]
    pieces: List[List[list]] = [[] for _ in range(world)]
    for s in range(world):
        n_s = int(relay[s].sum())
        if n_s == 0:
            continue
        if q_cols:
            qdec = {
                ci: _q.np_decode_q8(
                    np.ascontiguousarray(qmat_np[s, :n_s, k]),
                    float(qsc_np[s, k]),
                    dt,
                )
                for k, (ci, dt) in enumerate(q_cols)
            }
            cols_s = _g.host_unpack_cols_quant(
                qplan,
                [
                    np.ascontiguousarray(mat_np[s, :n_s, j])
                    for j in range(mat_np.shape[2])
                ],
                lambda ci, _pt=dict(
                    zip(pt_eff, [p[s][:n_s] for p in pts_np])
                ): _pt[ci],
                lambda ci, _dt: qdec[ci],
            )
        else:
            cols_s = _unpack_host_shard(
                plan, pt_order, mat_np[s], [p[s] for p in pts_np], n_s
            )
        offs = np.concatenate([[0], np.cumsum(relay[s])]).astype(np.int64)
        for d in range(world):
            lo, hi = int(offs[d]), int(offs[d + 1])
            if hi > lo:
                pieces[d].append(
                    [
                        (dd[lo:hi], None if vv is None else vv[lo:hi])
                        for dd, vv in cols_s
                    ]
                )
    per_dst: List[Optional[list]] = []
    for d in range(world):
        if not pieces[d]:
            per_dst.append(None)
            continue
        ncols = len(pieces[d][0])
        merged = []
        for ci in range(ncols):
            data = np.concatenate([p[ci][0] for p in pieces[d]])
            vs = [p[ci][1] for p in pieces[d]]
            if any(v is not None for v in vs):
                valid = np.concatenate(
                    [
                        v if v is not None else np.ones(len(p[ci][0]), bool)
                        for v, p in zip(vs, pieces[d])
                    ]
                )
            else:
                valid = None
            merged.append((data, valid))
        per_dst.append(merged)
    counts = relay.sum(axis=0).astype(np.int64)
    bump("shuffle.skew_split", rows=int(counts.sum()))
    return per_dst, counts


def shards_to_table(template, per_shard_cols, counts: np.ndarray):
    """Rebuild a device table from per-destination-shard PHYSICAL host
    columns, reusing ``template``'s dtype/dictionary metadata (the relay
    and arena paths both land here; 'spill.shards_to_table' owns the
    staging syncs inside ``Table.from_encoded_shards``)."""
    from ..table import Table

    names = template.column_names
    cols_meta = [template._columns[n] for n in names]
    world = template.ctx.world_size
    shards = []
    for s in range(world):
        od = OrderedDict()
        got = per_shard_cols[s]
        for ci, name in enumerate(names):
            meta = cols_meta[ci]
            if got is None:
                data = np.empty((0,), np.dtype(meta.data.dtype))
                valid = None
            else:
                data, valid = got[ci]
            od[name] = (data, valid, meta.dtype, meta.dictionary)
        shards.append(od)
    return Table.from_encoded_shards(
        template.ctx, shards, counts=np.asarray(counts, np.int64)
    )


def arena_result(sink: ShardArenaSink, template):
    """A spilled shuffle's final device table, rebuilt from the sink's
    per-shard arenas (tier-1/2 counterpart of the in-HBM round concat).
    Quantized-tier columns decode from their staged uint8 codes here —
    the arenas never held the full-width floats.

    The read-back rides the same degradation ladder as the writes
    (:func:`_retry_io`): a tier-2 EIO retries, then migrates the arenas
    to host RAM and re-reads, then fails the one query typed. The sink
    is closed on EVERY exit — success, typed failure, or anything else —
    so arena bytes always return to the ledger baseline."""

    def read():
        per_shard = [
            sink.dequantized_columns(s) if a.rows else None
            for s, a in enumerate(sink.arenas)
        ]
        return shards_to_table(template, per_shard, sink.counts())

    try:
        return _retry_io("spill arena read", read, sink=sink)
    finally:
        sink.close()
