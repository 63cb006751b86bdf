"""The sort engine: every ordering is the chip's native stable sort.

There is one engine (chained stable ``jax.lax.sort`` passes, ops/sort.py)
and no switch for it. The oracle of every result here lies outside the
program: pandas, or numpy's stable argsort.

  1. results — every consumer shape (multi-key sorts with NaN-last and
     descending floats, null sentinels, dictionary string codes, fused
     sort words past 32 bits, unique, group-by, join, shuffle) at worlds
     {1, 4, 8}: a sort in EXACT emitted order against pandas' stable
     ``sort_values`` of each shard's rows, the others against pandas'
     result, and every result against the order its own ``Ordering``
     claims;
  2. the primitives that had no test of their own: the join probe's
     kv-sort, the right side's argsort, the shuffle's partition grouping;
  3. the programs — every ``sort`` instruction lies under the
     ``sort_engine`` scope (what the ``sort_engine_ms`` metric is trusted
     for), and the two retired environment variables change no program;
  4. what went with the second engine stays gone: no switch, no autopilot
     field, no census on the dispatch path, and a journal an older
     process wrote with those fields still loads.
"""
import importlib.util
import json
import re

import numpy as np
import pandas as pd
import pandas.testing as pdt
import pytest

import jax.numpy as jnp

import cylon_tpu as ct
import ride_cases
from cylon_tpu.obs import stages
from cylon_tpu.obs import store as obs_store
from cylon_tpu.ops import join as join_ops
from cylon_tpu.ops import sort as sort_ops
from cylon_tpu.parallel import shuffle as shuffle_ops
from cylon_tpu.plan import feedback as fb
from cylon_tpu.utils import envgate, tracing

#: the variables that selected the deleted second engine (PR 29)
RETIRED = {"CYLON_TPU_SORT_IMPL": "radix", "CYLON_TPU_NO_RADIX": "1"}


@pytest.fixture(autouse=True)
def clean_env(monkeypatch):
    for name in RETIRED:
        monkeypatch.delenv(name, raising=False)


def _ctx(devices, world):
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )


# ---------------------------------------------------------------------------
# helpers: pandas as the oracle
# ---------------------------------------------------------------------------

def _shards(df, counts):
    """``df`` cut into the shards' row ranges (a table made of a frame
    holds its rows in order, ``counts[i]`` of them on shard i)."""
    ends = np.cumsum(np.asarray(counts, np.int64))
    assert ends[-1] == len(df)
    return [df.iloc[e - c:e] for c, e in zip(counts, ends)]


def _cells(series):
    """A column as objects with every missing value None, so an int column
    that came back as floats beside its nulls still compares equal."""
    vals = series.to_numpy(dtype=object)
    return np.where(pd.isna(vals), None, vals)


def _same_rows_in_order(got, want):
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want)
    for name in got.columns:
        g, w = _cells(got[name]), _cells(want[name])
        assert np.array_equal(g, w), name


def _canon(df):
    """Rows in one fixed order whatever order they were emitted in."""
    df = df.reset_index(drop=True)
    return df.sort_values(
        list(df.columns), kind="stable", na_position="last"
    ).reset_index(drop=True)


def _same_rows(got, want, **kw):
    pdt.assert_frame_equal(
        _canon(got), _canon(want[list(got.columns)]),
        check_dtype=False, **kw,
    )


def _pandas_sort(df, keys, ascending):
    return df.sort_values(
        keys, ascending=ascending, kind="stable", na_position="last"
    )


def _claimed_order_holds(table):
    """The rows are in the order the table's own ``Ordering`` claims:
    per shard, and across shards where the scope is global."""
    claim = table.ordering
    if claim is None:
        return
    assert claim.nulls_last
    frame = table.to_pandas().reset_index(drop=True)
    keys, asc = list(claim.keys), list(claim.ascending)
    parts = _shards(frame, table.row_counts)
    for part in parts + ([frame] if claim.scope == "global" else []):
        want = _pandas_sort(part, keys, asc)
        _same_rows_in_order(part[keys], want[keys])


def _sort_against_pandas(ctx, df, keys, ascending=True):
    asc = [ascending] * len(keys) if isinstance(ascending, bool) else ascending
    t = ct.Table.from_pandas(ctx, df)
    got = t.sort(keys, ascending=ascending)
    want = pd.concat(
        [_pandas_sort(part, keys, asc) for part in _shards(df, t.row_counts)]
    )
    # no re-sort on either side: a stability or permutation fault must show
    _same_rows_in_order(got.to_pandas(), want)
    assert got.ordering is not None and got.ordering.keys == tuple(keys)
    _claimed_order_holds(got)


# ---------------------------------------------------------------------------
# 1. results against pandas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("world", [1, 4, 8])
def test_nan_last_floats(world, devices, rng):
    n = 900
    vals = rng.normal(size=n).astype(np.float64)
    vals[rng.random(n) < 0.15] = np.nan
    df = pd.DataFrame({
        "g": rng.integers(0, 12, n).astype(np.int32),
        "f": vals,
        "v": np.arange(n, dtype=np.int64),
    })
    _sort_against_pandas(_ctx(devices, world), df, ["g", "f"])


@pytest.mark.parametrize("world", [1, 4, 8])
def test_descending_floats(world, devices, rng):
    n = 800
    vals = rng.normal(size=n).astype(np.float32)
    vals[rng.random(n) < 0.1] = np.nan
    df = pd.DataFrame({
        "f": vals,
        "k": rng.integers(-40, 40, n).astype(np.int32),
        "v": np.arange(n, dtype=np.int64),
    })
    # NaN after every number in either direction
    _sort_against_pandas(
        _ctx(devices, world), df, ["f", "k"], ascending=[False, False]
    )


@pytest.mark.parametrize("world", [1, 4, 8])
def test_null_sentinels(world, devices, rng):
    n = 1000
    k1 = rng.integers(0, 30, n).astype(object)
    k1[rng.random(n) < 0.2] = None
    k2 = rng.integers(-500, 500, n).astype(object)
    k2[rng.random(n) < 0.2] = None
    df = pd.DataFrame({"k1": k1, "k2": k2,
                       "v": np.arange(n, dtype=np.int64)})
    _sort_against_pandas(_ctx(devices, world), df, ["k1", "k2"])


@pytest.mark.parametrize("world", [1, 4, 8])
def test_dict_codes(world, devices, rng):
    n = 900
    words = np.array([f"w{i:03d}" for i in range(40)], dtype=object)
    k = rng.choice(words, n)
    k[rng.random(n) < 0.1] = None
    df = pd.DataFrame({
        "s": k,
        "k": rng.integers(0, 9, n).astype(np.int8),
        "v": np.arange(n, dtype=np.int64),
    })
    _sort_against_pandas(
        _ctx(devices, world), df, ["s", "k"], ascending=[True, False]
    )


@pytest.mark.parametrize("world", [1, 4, 8])
def test_straddled_64bit_fused_word(world, devices, rng):
    # ~20+16+7 key bits + null/pad lanes fuse into ONE uint64 sort word
    # whose fields straddle the 32-bit boundary
    n = 1100
    df = pd.DataFrame({
        "a": rng.integers(0, 1_000_000, n).astype(np.int32),
        "b": rng.integers(0, 60_000, n).astype(np.int32),
        "c": rng.integers(0, 120, n).astype(np.int32),
        "v": np.arange(n, dtype=np.int64),
    })
    ctx = _ctx(devices, world)
    _sort_against_pandas(ctx, df, ["a", "b", "c"])
    _sort_against_pandas(ctx, df, ["a", "b", "c"], ascending=[True, False, True])


def _unique_against_pandas(t, df, keys):
    got = t.unique(keys)
    want = pd.concat([
        part.drop_duplicates(keys, keep="first")
        for part in _shards(df, t.row_counts)
    ])
    _same_rows(got.to_pandas(), want)
    _claimed_order_holds(got)


def _groupby_against_pandas(t, df, keys, col):
    got = t.distributed_groupby(keys, {col: "sum"})
    want = df.groupby(keys, as_index=False)[col].sum()
    frame = got.to_pandas()
    frame.columns = list(want.columns)  # the aggregate's name is its own
    _same_rows(frame, want, rtol=1e-4)
    _claimed_order_holds(got)


def _join_against_pandas(t, r, df, rdf, how):
    got = t.distributed_join(r, on="k", how=how)
    want = df.merge(rdf, on="k", how=how)
    frame = got.to_pandas()
    assert len(frame) == len(want)
    # the join keeps both key columns (k_x / k_y); pandas keeps one
    frame = frame.rename(columns={"k_x": "k"}).drop(columns=["k_y"])
    _same_rows(frame[list(want.columns)], want)
    _claimed_order_holds(got)


def _shuffle_against_input(t, df, key):
    got = t.shuffle([key])
    _same_rows(got.to_pandas(), df)
    # a key's rows all land on one shard
    homes = pd.concat([
        part[[key]].drop_duplicates().assign(shard=i)
        for i, part in enumerate(
            _shards(got.to_pandas().reset_index(drop=True), got.row_counts)
        )
    ])
    assert not homes[key].duplicated().any()
    _claimed_order_holds(got)


@pytest.mark.parametrize("world", [1, 4, 8])
def test_unique_groupby_join_shuffle(world, devices, rng):
    n = 800
    df = pd.DataFrame({
        "k": rng.integers(0, 60, n).astype(np.int32),
        "j": rng.integers(-9, 9, n).astype(np.int64),
        "v": rng.normal(size=n).astype(np.float32),
    })
    rdf = pd.DataFrame({
        "k": rng.integers(0, 60, n // 2).astype(np.int32),
        "w": rng.normal(size=n // 2).astype(np.float32),
    })
    ctx = _ctx(devices, world)
    t = ct.Table.from_pandas(ctx, df)
    r = ct.Table.from_pandas(ctx, rdf)
    _unique_against_pandas(t, df, ["k", "j"])
    _groupby_against_pandas(t, df, ["k", "j"], "v")
    _join_against_pandas(t, r, df, rdf, "inner")
    if world > 1:
        _shuffle_against_input(t, df, "k")


def _frames(rng):
    """The benchmark's widths (int64 key, float64 value) beside narrow
    ones."""
    n = 900
    df = pd.DataFrame({
        "k": rng.integers(0, 70, n).astype(np.int64),
        "j": rng.integers(-9, 9, n).astype(np.int32),
        "v": rng.random(n),
        "f": rng.normal(size=n).astype(np.float32),
    })
    rdf = pd.DataFrame({
        "k": rng.integers(0, 70, n // 2).astype(np.int64),
        "w": rng.random(n // 2),
    })
    return df, rdf


def _dist_sort_against_pandas(t, df):
    got = t.distributed_sort(["k", "j"])
    frame = got.to_pandas().reset_index(drop=True)
    _same_rows(frame, df)
    assert got.ordering.scope == "global" or t.world_size == 1
    _claimed_order_holds(got)
    # the keys in pandas' order position for position (a tie's rows may
    # come from any shard, so the other columns are compared as a set)
    want = _pandas_sort(df, ["k", "j"], [True, True])
    _same_rows_in_order(frame[["k", "j"]], want[["k", "j"]])


_OPS = {
    "sort": lambda t, r, df, rdf: _dist_sort_against_pandas(t, df),
    "sort_desc": lambda t, r, df, rdf: _sort_against_pandas(
        t.ctx, df, ["j", "k"], ascending=[False, True]
    ),
    "join": lambda t, r, df, rdf: _join_against_pandas(t, r, df, rdf, "inner"),
    "join_left": lambda t, r, df, rdf: _join_against_pandas(
        t, r, df, rdf, "left"
    ),
    "groupby": lambda t, r, df, rdf: _groupby_against_pandas(
        t, df, ["k", "j"], "v"
    ),
    "unique": lambda t, r, df, rdf: _unique_against_pandas(t, df, ["k", "j"]),
    "shuffle": lambda t, r, df, rdf: _shuffle_against_input(t, df, "k"),
}


@pytest.mark.parametrize("op,world", [
    (op, world) for world in (1, 4) for op in _OPS
    if (op, world) != ("shuffle", 1)  # one shard: nothing is shuffled
], ids=lambda p: str(p))
def test_hot_path_ops_agree_with_pandas(devices, rng, op, world):
    df, rdf = _frames(rng)
    ctx = _ctx(devices, world)
    t, r = ct.Table.from_pandas(ctx, df), ct.Table.from_pandas(ctx, rdf)
    _OPS[op](t, r, df, rdf)


# ---------------------------------------------------------------------------
# 2. the primitives
# ---------------------------------------------------------------------------

def test_kv_sort_is_the_stable_sort(rng):
    keys = rng.integers(0, 40, 700).astype(np.int32)
    skey, spay = sort_ops.kv_sort(
        jnp.asarray(keys), jnp.arange(700, dtype=jnp.int32)
    )
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(skey), keys[order])
    np.testing.assert_array_equal(np.asarray(spay), order)


@pytest.mark.parametrize("parts", [2, 4, 8, 64])
def test_shuffle_gather_order_is_the_stable_grouping(rng, parts):
    # ids in [0, parts); `parts` itself is the padding / dropped id
    pid = rng.integers(0, parts + 1, 3000).astype(np.int32)
    pid[-40:] = parts
    order = np.asarray(shuffle_ops.shuffle_gather_order(jnp.asarray(pid)))
    assert order.dtype == np.int32
    np.testing.assert_array_equal(order, np.argsort(pid, kind="stable"))
    grouped = pid[order]
    live = int((pid < parts).sum())
    assert (grouped[:live] < parts).all() and (grouped[live:] == parts).all()


@pytest.mark.parametrize("dtype", [np.uint32, np.int32])
def test_right_order_is_the_stable_argsort(rng, dtype):
    n = 2048
    if dtype == np.uint32:
        # the single-key fast path: raw orderable keys over the whole
        # width, the padding rows at the largest value
        ids = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
        ids[rng.random(n) < 0.3] = ids[0]
        ids[-100:] = np.uint32(0xFFFFFFFF)
    else:
        # factorized dense ids, the padding rows at cap_l + cap_r
        ids = rng.integers(0, 300, n).astype(np.int32)
        ids[-100:] = 2 * n
    order = np.asarray(join_ops._right_order(jnp.asarray(ids)))
    assert order.dtype == np.int32
    np.testing.assert_array_equal(order, np.argsort(ids, kind="stable"))


# ---------------------------------------------------------------------------
# 3. the programs
# ---------------------------------------------------------------------------

def _suite_tables(ctx, rows=2048, seed=5):
    """The benchmark's widths: int64 key over the row count, float64
    value; ``g`` has four values, which is what the dense group-by takes."""
    rng = np.random.default_rng(seed)
    return tuple(
        ct.Table.from_numpy(
            ctx, ["k", "g", name],
            [rng.integers(0, rows, rows).astype(np.int64),
             rng.integers(0, 4, rows).astype(np.int8), rng.random(rows)],
        )
        for name in ("v", "w")
    )


_DISPATCH = {
    "sort": lambda a, b: a.distributed_sort("k"),
    "join": lambda a, b: a.distributed_join(b, on="k", how="inner"),
    "groupby": lambda a, b: (
        a.distributed_groupby(["k"], {"v": "sum"}).row_count,
        a.distributed_groupby(["g"], {"v": "sum"}),
    )[1],
    "unique": lambda a, b: a.unique(["k"]),
    "union": lambda a, b: a.project(["k", "g"]).distributed_union(
        b.project(["k", "g"])
    ),
    "shuffle": lambda a, b: a.shuffle(["k"]),
}


def _dispatched(devices, op, world):
    """Run ``op`` on a context of its own; its programs as
    ``[(name, fn, spec)]``."""
    ctx = _ctx(devices, world)
    ta, tb = _suite_tables(ctx)
    assert _DISPATCH[op](ta, tb).row_count > 0
    return [
        (fn.__name__, fn, spec)
        for _key, fn, spec in stages.dispatched_programs(ctx)
    ]


_SORT = re.compile(r"\ssort\(")

#: sorts that lie outside the ``sort_engine`` scope today, by program and
#: stage: ``sort_engine_ms`` does not count them (ROADMAP.md, named debt).
#: Both are argsorts of a liveness flag: a two-hop receive (and the ring
#: relay) packs its live rows to the front, the dense group-by its occupied
#: slots. A flat mesh's receive side sorts nothing since PR 47 (block
#: writes: ``tests/test_shuffle_compact.py`` reads its program).
OUTSIDE_THE_ENGINE = {
    ("shuffle_compact", stages.SHUFFLE_COMPACT),
    ("groupby_dense", stages.GROUPBY_DENSE_AGG),
}


@pytest.mark.parametrize("op,world", [
    (op, world) for world in (1, 4)
    for op in ("sort", "join", "groupby", "unique", "union")
] + [("shuffle", 4)], ids=lambda p: str(p))
def test_every_sort_instruction_is_under_sort_engine(devices, op, world):
    sorts = outside = 0
    for name, fn, spec in _dispatched(devices, op, world):
        _module, rows = stages.parse_compiled(
            fn.lower(*spec).compile().as_text()
        )
        for text, op_name in rows:
            if not _SORT.search(text):
                continue
            sorts += 1
            if stages.in_sort_engine(op_name):
                continue
            outside += 1
            assert (name, stages.stage_of(op_name)) in OUTSIDE_THE_ENGINE, (
                name, op_name
            )
    assert sorts > outside, "the operation sorted nothing under the engine"


@pytest.mark.parametrize("op,world,must", [
    ("sort", 1, ["sort"]),
    ("join", 1, ["join_spec"]),
    ("sort", 4, ["sort", "shuffle_pack"]),
    ("join", 4, ["join_spec", "shuffle_pack"]),
], ids=["sort-w1", "join-w1", "sort-w4", "join-w4"])
def test_retired_sort_variables_change_no_program(
    devices, monkeypatch, op, world, must
):
    def texts():
        out = {}
        for name, fn, spec in _dispatched(devices, op, world):
            out.setdefault(name, []).append(fn.lower(*spec).as_text())
        return out

    clean = texts()
    for name in must:
        assert name in clean, (name, sorted(clean))
    for name, value in RETIRED.items():
        monkeypatch.setenv(name, value)
    forced = texts()
    assert forced == clean
    for name, programs in forced.items():
        for text in programs:
            assert "radix_pass" not in text, name


# ---------------------------------------------------------------------------
# 4. what went with the second engine
# ---------------------------------------------------------------------------

def test_no_sort_switch_is_left():
    import cylon_tpu.plan.lazy  # noqa: F401  (every gate module is loaded)
    import cylon_tpu.table  # noqa: F401

    for name in RETIRED:
        assert name not in envgate.REGISTRY
    assert not hasattr(envgate, "SORT_IMPL")
    assert "sort_impl" not in fb.Decisions._fields
    # seven since PR 44, which took the shuffle's Pallas kernels the same way
    assert len(fb.Decisions._fields) == 7
    assert not hasattr(fb, "tuned_sort_impl")
    for module in ("radix", "pallas_radix"):
        assert importlib.util.find_spec(f"cylon_tpu.ops.{module}") is None


def test_old_journal_with_sort_fields_loads(tmp_path, monkeypatch):
    """A directory an older process wrote: a snapshot whose profile holds
    ``sort_ev`` and a decided and a pending ``sort_impl``, and a journal
    whose exec record holds a ``sort`` entry. It loads, the fields decide
    nothing, and the profile keeps absorbing."""
    monkeypatch.setenv("CYLON_TPU_AUTOTUNE_MIN_OBS", "2")
    d = tmp_path / "obs"
    d.mkdir()
    old = obs_store.new_profile()
    old.update(
        n=7, world=4,
        sort_ev={"bitonic": [7, 12.5, 231, 77], "radix": [3, 90.0, 33, 99]},
        dec={"sort_impl": "bitonic", "shuffle_budget": 1 << 20},
        pend={"sort_impl": ["'static'", 1]},
    )
    (d / "snapshot.json").write_text(json.dumps(
        {"v": 2, "jseqs": {"old": 3}, "profiles": {"aaaa": old}, "hists": {}}
    ))
    (d / "journal-old.jsonl").write_text(json.dumps({
        "k": "exec", "fp": "aaaa", "i": 4, "world": 4, "row_bytes": 16,
        "hot": 10, "sort": {"bitonic": [1, 2.0, 33, 11]},
    }) + "\n")
    s = obs_store.ObsStore(str(d), writer_id="new")
    try:
        assert s.skipped_lines == 0
        p = s.profiles["aaaa"]
        assert p["n"] == 8  # the old journal's record was absorbed
        assert p["sort_ev"]["bitonic"] == [7, 12.5, 231, 77]  # and not folded
        tup = s.dec_tuple("aaaa")
        assert len(tup) == len(fb.Decisions._fields)
        decisions = fb.Decisions(*tup)
        assert decisions.shuffle_budget == 1 << 20
        assert "bitonic" not in tup
        for _ in range(4):
            s.record({"k": "exec", "fp": "aaaa", "world": 4, "row_bytes": 16,
                      "hot": 10, "sort": {"radix": [1, 9.0, 3, 9]}})
        assert s.profiles["aaaa"]["n"] == 12
        assert len(s.dec_tuple("aaaa")) == len(fb.Decisions._fields)
        s.compact()
        assert s.profiles["aaaa"]["n"] == 12
    finally:
        s.close()


def test_sort_dispatch_does_no_census(devices, tmp_path, monkeypatch):
    monkeypatch.setenv("CYLON_TPU_OBS_DIR", str(tmp_path / "obs"))
    obs_store.reset_stores()
    try:
        ta, _tb = _suite_tables(_ctx(devices, 1))
        before = tracing.report("radix.")
        with obs_store.exec_obs("bbbb") as rec:
            assert rec is not None  # a plan execution's record is open
            assert ta.sort(["k", "g"]).row_count == ta.row_count
        assert "sort" not in rec
        assert tracing.report("radix.") == before == {}
        assert "sort_ev" not in obs_store.store().profiles["bbbb"]
    finally:
        obs_store.reset_stores()


# ---------------------------------------------------------------------------
# 5. every column rides the sort that orders it (PR 30): 64-bit ones as
#    their two halves, bit for bit; many in batches; nothing is gathered
# ---------------------------------------------------------------------------

def _stable_reference(cols, counts, keys):
    """Each shard's rows in numpy's stable order of ``keys``."""
    ends = np.cumsum(np.asarray(counts, np.int64))
    orders = [
        (e - c) + np.lexsort([cols[k][0][e - c:e] for k in reversed(keys)])
        for c, e in zip(counts, ends)
    ]
    order = np.concatenate(orders)
    return {
        name: (data[order], None if valid is None else valid[order])
        for name, (data, valid) in cols.items()
    }


def _ride_delta(fn):
    """``fn()``'s result and the (lanes, batches) its rides counted."""
    before = ride_cases.ride_counts(tracing)
    out = fn()
    after = ride_cases.ride_counts(tracing)
    return out, (after[0] - before[0], after[1] - before[1])


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("schema", ride_cases.SCHEMAS)
def test_sorted_columns_come_out_bit_for_bit(devices, rng, schema, world):
    """``Table.sort`` of int64, float64, nullable float64 and mixed 32/64-bit
    columns: every column equals numpy's stable order of the shard's rows as
    bits (NaN payloads, -0.0, infinities, a subnormal, int64's extremes),
    and the whole table rode one sort."""
    cols = ride_cases.columns(rng, 1500, schema, "")
    t = ride_cases.table(_ctx(devices, world), cols)
    got, (lanes, batches) = _ride_delta(lambda: t.sort("k"))
    ride_cases.assert_same_bits(
        ride_cases.physical(got), _stable_reference(cols, t.row_counts, ["k"])
    )
    assert batches == 1
    assert lanes == sum(
        max(1, a.dtype.itemsize // 4)
        for d, v in cols.values() for a in ((d,) if v is None else (d, v))
    )


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("keys", [["k"], ["k", "f0"]], ids=["fused", "two-pass"])
def test_wide_table_rides_the_sort_in_batches(devices, rng, keys, world):
    """Twelve lanes (an int64 key and five float64 columns) are more than
    one sort carries: they ride in batches, of the one fused pass or of the
    whole chained sort of an unfused key pair, and equal the reference."""
    cols = ride_cases.columns(rng, 1500, "wide5", "")
    cols["f0"] = (rng.normal(size=1500), None)  # a key: plain values
    t = ride_cases.table(_ctx(devices, world), cols)
    got, (lanes, batches) = _ride_delta(lambda: t.sort(keys))
    ride_cases.assert_same_bits(
        ride_cases.physical(got), _stable_reference(cols, t.row_counts, keys)
    )
    assert (lanes, batches) == (12, 3)  # the int64 stack, two of float64


_OPCODE = re.compile(r" = (?:\([^=]*?\)|\S+) ([a-z][\w\-]*)\(")


def _opcodes_under(text, scope):
    """Opcodes of the instructions (fused ones too) of an HLO text whose
    ``op_name`` path holds ``scope``."""
    out = []
    for line in text.splitlines():
        op_name = stages._OP_NAME.search(line)
        found = _OPCODE.search(stages._METADATA.sub("", line))
        if found and op_name and scope in op_name[1].split("/"):
            out.append(found[1])
    return out


@pytest.mark.parametrize("op,program,scope", [
    ("sort", "sort", stages.SORT_PERM),
    ("join", "join_spec", stages.JOIN_RIGHT_SORT),
])
def test_suite_schema_gathers_nothing_by_an_order(devices, op, program, scope):
    """The benchmark's widths (int64 key, float64 value): the program that
    orders them holds a sort under ``scope`` and no gather there; in
    ``jit_sort`` no gather at all (``sort.gather`` named the last one)."""
    ctx = _ctx(devices, 1)
    rng = np.random.default_rng(5)
    ta, tb = (
        ct.Table.from_numpy(ctx, ["k", name], [
            rng.integers(0, 2048, 2048).astype(np.int64), rng.random(2048),
        ])
        for name in ("v", "w")
    )
    assert _DISPATCH[op](ta, tb).row_count > 0
    texts = [
        fn.lower(*spec).compile().as_text()
        for _key, fn, spec in stages.dispatched_programs(ctx)
        if fn.__name__ == program
    ]
    assert texts
    for text in texts:
        under = _opcodes_under(text, scope)
        assert "sort" in under and "gather" not in under, under
        if program == "sort":
            assert not re.search(r"\sgather\(", text)
    assert "sort.gather" not in stages.VOCABULARY


@pytest.mark.parametrize("op", ["join", "sort", "shuffle"])
def test_pack_rides_one_sort_under_the_engine(devices, op):
    """Every kind of shuffle (hash with the semi filter, range, plain
    hash) packs by the ride sort: ``jit_shuffle_pack`` holds one sort,
    under ``sort_engine`` within ``shuffle.pack``, that carries more than
    the partition ids and an order, and no scatter is left: the bucket
    counts into ``[world]`` and the range partition's 64 bins, the last
    two, are a compare and a sum (``ops.partition.bin_counts``)."""
    packs = [
        fn.lower(*spec).compile().as_text()
        for name, fn, spec in _dispatched(devices, op, 4)
        if name == "shuffle_pack"
    ]
    assert packs
    for text in packs:
        _module, rows = stages.parse_compiled(text)
        sorts = [(t, o) for t, o in rows if _SORT.search(t)]
        assert len(sorts) == 1, sorts
        sort_text, op_name = sorts[0]
        assert stages.in_sort_engine(op_name), op_name
        assert stages.stage_of(op_name) == stages.SHUFFLE_PACK, op_name
        operands = re.findall(r"\w+\[\d+\]", sort_text.split(" sort(")[0])
        assert len(operands) > 2, operands
        assert not re.search(r"\sscatter\(", text)


def test_pack_dispatch_counts_what_rides(devices, rng, monkeypatch):
    """A four-shard ``distributed_join`` bumps ``shuffle.pack.ride_lanes``
    and ``shuffle.pack.ride_batches`` once a pack dispatch, by
    ``ride_census`` of what that table's pack really carried (seen where
    the program is traced: its int32 lanes and float64 passthroughs)."""
    traced = []
    real = shuffle_ops.pack_by_sort

    def spy(lanes, passthrough, *args, **kwargs):
        traced.append(sort_ops.ride_census(
            [a.dtype for a in list(lanes) + list(passthrough)]
        ))
        return real(lanes, passthrough, *args, **kwargs)

    monkeypatch.setattr(shuffle_ops, "pack_by_sort", spy)
    ctx = _ctx(devices, 4)
    # keys over all 64 bits, so that no wire plan narrows them: the lane
    # plan alone says what rides (two key lanes, the value's two halves)
    pool = rng.integers(-2**62, 2**62, 300)
    ta, tb = (
        ct.Table.from_numpy(
            ctx, ["k", name], [rng.choice(pool, 1500), rng.random(1500)]
        )
        for name in ("v", "w")
    )

    def counted():
        got = tracing.report("shuffle.")
        return tuple(
            int(got[name][field]) if name in got else 0
            for name, field in (
                ("shuffle.pack.ride_lanes", "rows"),
                ("shuffle.pack.ride_batches", "rows"),
                ("shuffle.pack.ride_lanes", "count"),
                ("shuffle.round.pack", "count"),
            )
        )

    before = counted()
    assert ta.distributed_join(tb, on="k", how="inner").row_count > 0
    lanes, batches, bumps, dispatches = (
        a - b for a, b in zip(counted(), before)
    )
    assert traced == [(4, 1), (4, 1)]  # one program a table
    assert bumps == dispatches == 2  # one round a table
    assert (lanes, batches) == tuple(map(sum, zip(*traced)))


@pytest.mark.parametrize("seed", [1, 7, 2147530001, 987654321])
def test_sample_sort_cuts_evenly_filled_bins_at_their_middle(devices, seed):
    """Uniform keys fill the 64 bins of the sample sort evenly: the bin at
    a boundary goes where its middle lies, so whatever the seed no shard
    holds half a bin (1/32 of a shard) more than its share. By the rows in
    front of the bin, as the reference cuts, most seeds gave one shard a
    whole bin more (1/16) and with it twice the capacities downstream."""
    ctx = _ctx(devices, 4)
    rows = 1 << 16
    rng = np.random.default_rng(seed)
    t = ct.Table.from_numpy(ctx, ["k", "v"], [
        rng.integers(0, rows, rows).astype(np.int64), rng.random(rows),
    ])
    before = {
        name: tracing.snapshot().get(name, {}).get("rows", 0)
        for name in (
            "shuffle.range.shard_rows_max", "shuffle.range.shard_rows_mean",
        )
    }
    got = t.distributed_sort("k")
    after = tracing.snapshot()
    fullest, mean = (
        after[name]["rows"] - before[name]
        for name in before
    )
    assert mean == rows // 4
    assert fullest / mean - 1 < 1 / 32, (fullest, mean)
    keys = got.to_pandas()["k"].to_numpy()
    assert (np.diff(keys) >= 0).all() and len(keys) == rows
