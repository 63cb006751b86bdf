"""Long-running randomized pandas-parity fuzz campaign.

Extends tests/test_fuzz_ops.py's fixed sweep into an open-ended campaign:
random (seed, size, keyspace, dtype, null density, world size) per round,
covering join (all hows x eager/fused x sort/pallas_pk), set ops, unique,
groupby, distributed sort, and the out-of-core join — each checked against
pandas. Prints one line per round; on a mismatch prints REPRO with the
exact parameters and keeps going (exit code 1 at the end if any failed).

Usage: python tools/fuzz_campaign.py [--minutes 30] [--seed0 0]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as ge

DEVICES = ge._force_cpu_mesh(8)

import numpy as np
import pandas as pd

import cylon_tpu as ct

CTXS = {}


def ctx_for(world):
    if world not in CTXS:
        CTXS[world] = ct.CylonContext.init_distributed(
            ct.TPUConfig(devices=DEVICES[:world])
        )
    return CTXS[world]


def topo_ctx_for(world, mesh):
    # tuple-keyed beside the flat contexts so the cache-clearing loop in
    # main() covers these meshes too
    key = (world, mesh)
    if key not in CTXS:
        CTXS[key] = ct.CylonContext.init_distributed(
            ct.TPUConfig(devices=DEVICES[:world], mesh_shape=mesh)
        )
    return CTXS[key]


def rand_frame(rng, n, keyspace, dtype, null_p, vname="v"):
    if dtype == "int32":
        k = rng.integers(-keyspace, keyspace, n).astype(np.int32).astype(object)
    elif dtype == "int64":
        k = (rng.integers(-keyspace, keyspace, n).astype(np.int64) * 3).astype(object)
    elif dtype == "float32":
        base = rng.integers(-keyspace, keyspace, n).astype(np.float32)
        base = np.where(rng.random(n) < 0.1, -0.0, base).astype(np.float32)
        k = base.astype(object)
    else:
        k = rng.choice([f"s{i}" for i in range(keyspace)], n).astype(object)
    if null_p:
        k[rng.random(n) < null_p] = None
    return pd.DataFrame({"k": k, vname: rng.normal(size=n).astype(np.float32)})


def canon(v):
    if v is None or (isinstance(v, float) and np.isnan(v)):
        return "\x00null"
    if isinstance(v, (int, float, np.integer, np.floating)):
        f = float(v)
        if f == 0:
            return "0.0"
        if np.isfinite(f) and f == int(f):
            return str(int(f))  # 21.0 (nullable-int float bounce) == 21
    return str(v)


def norm(df):
    out = df.copy()

    for c in out.columns:
        if out[c].dtype == object or c.startswith("k"):
            out[c] = out[c].map(canon)
        else:
            # f64 first: round(4) of a float32 column can't hit the same
            # representable values as the f64 it is compared against
            out[c] = out[c].astype(np.float64).round(4)
    out = out.fillna("\x00null")  # NaN != NaN would flag equal frames
    return out.sort_values(list(out.columns), kind="mergesort").reset_index(drop=True)


def check(got_df, want_df, what, params):
    if set(got_df.columns) != set(want_df.columns):
        print(f"MISMATCH {what} columns params={params} "
              f"got={list(got_df.columns)} want={list(want_df.columns)}",
              flush=True)
        return False
    want_df = want_df[list(got_df.columns)]  # align column order
    g, w = norm(got_df), norm(want_df)
    g, w = g.astype(str), w.astype(str)  # dtype-blind (empty frames too)
    if len(g) != len(w) or not g.equals(w):
        print(f"MISMATCH {what} params={params} got={len(g)} want={len(w)}",
              flush=True)
        return False
    return True


MAX_N = 400


def expected_join(ldf, rdf, how):
    """pandas oracle for our join output schema: both key columns kept
    (k_x/k_y), with the unmatched side's key nulled on outer rows — ONE
    definition shared by every fuzz profile so the oracles cannot drift."""
    want = ldf.merge(rdf, on="k", how=how)
    want = want.assign(k_x=want["k"], k_y=want["k"]).drop(columns=["k"])
    if how in ("left", "outer"):
        want.loc[want["w"].isna() & ~want["k_x"].isin(rdf["k"]), "k_y"] = None
    if how in ("right", "outer"):
        want.loc[want["v"].isna() & ~want["k_y"].isin(ldf["k"]), "k_x"] = None
    return want


def skew_round_once(seed) -> bool:
    """Hard-mode adversarial-skew round (VERDICT r3 item 8): ONE key owns
    ~50% of the rows on both sides, world in {4, 8}, and the fused join runs
    with a deliberately undersized capacity_factor and respill in {0..3} so
    hot buckets must drain over >=3 in-program rounds and/or host retries.
    Exact pandas parity asserted on every how; the retry loop's bound
    (max_retries) is asserted implicitly — an unconverged join raises."""
    rng = np.random.default_rng(seed)
    n_l = int(rng.integers(200, max(MAX_N, 201)))
    n_r = int(rng.integers(200, max(MAX_N, 201)))
    keyspace = int(rng.integers(4, 64))
    world = int(rng.choice([4, 8]))
    hot = np.int32(rng.integers(-keyspace, keyspace))
    # every ~3rd round: STRING keys (dictionary-encoded) so hot-key skew
    # also drives the dict-unify + fused-capacity machinery (VERDICT r4
    # item 8: string keys in the distributed-join fuzz mix)
    as_str = bool(rng.random() < 0.34)
    params = dict(seed=seed, profile="skew", n_l=n_l, n_r=n_r,
                  keyspace=keyspace, world=world, hot=int(hot),
                  string_keys=as_str)
    ctx = ctx_for(world)

    def skewed(n, vname):
        k = rng.integers(-keyspace, keyspace, n).astype(np.int32)
        k[rng.random(n) < 0.5] = hot  # ~half the rows on one key
        if as_str:
            k = np.array([f"key_{v}" for v in k], dtype=object)
        return pd.DataFrame({"k": k, vname: rng.normal(size=n).astype(np.float32)})

    ldf = skewed(n_l, "v")
    rdf = skewed(n_r, "w")
    lt = ct.Table.from_pandas(ctx, ldf)
    rt = ct.Table.from_pandas(ctx, rdf)
    ok = True
    capf = float(rng.choice([0.125, 0.25, 0.5]))
    resp = int(rng.choice([0, 1, 2, 3]))
    k_sl = int(rng.choice([1, 2, 4]))
    for how in ("inner", "left", "right", "outer"):
        want = expected_join(ldf, rdf, how)
        got = lt.distributed_join(
            rt, on="k", how=how, mode="fused",
            capacity_factor=capf, respill=resp, max_retries=6,
            num_slices=k_sl,
        ).to_pandas()
        ok &= check(
            got, want,
            f"skewjoin/{how}/capf{capf}/resp{resp}/sl{k_sl}", params,
        )
        # eager path under the same skew: multi-round _shuffle_impl drain
        got = lt.distributed_join(rt, on="k", how=how).to_pandas()
        ok &= check(got, want, f"skewjoin/{how}/eager", params)
    # skewed groupby-sum cross-check (pre-combine must stay associative
    # under a giant hot group)
    got = lt.distributed_groupby("k", {"v": "sum"}).to_pandas()
    want = ldf.groupby("k", as_index=False)["v"].sum().rename(
        columns={"v": "v_sum"})
    go = got.sort_values("k").reset_index(drop=True)
    wo = want.sort_values("k").reset_index(drop=True)
    if not (len(go) == len(wo)
            and (go["k"].to_numpy() == wo["k"].to_numpy()).all()
            and np.allclose(go["v_sum"].to_numpy(), wo["v_sum"].to_numpy(),
                            rtol=1e-3, atol=1e-3)):
        print(f"MISMATCH skew_groupby params={params}", flush=True)
        ok = False
    return ok


def shuffle_round_once(seed) -> bool:
    """Chunked-shuffle oracle round (ISSUE 2 satellite): randomize round
    count K (via the byte budget), dtype mix, null density and skew shape,
    and differential-check the chunked shuffle against the EAGER UNCHUNKED
    result (a huge-budget shuffle = one padded round wherever the skew
    heuristic allows). Also cross-checks a distributed join run under the
    same random budget against pandas."""
    from cylon_tpu.parallel import shuffle as _sh
    from cylon_tpu.utils.tracing import report, reset_trace

    rng = np.random.default_rng(seed)
    n = int(rng.integers(64, max(MAX_N, 65)))
    keyspace = int(rng.integers(2, 128))
    world = int(rng.choice([2, 4, 8]))
    dtype = str(rng.choice(["int32", "int64", "float32", "string"]))
    null_p = float(rng.choice([0.0, 0.2]))
    skew = str(rng.choice(["uniform", "one_hot", "hot_key", "empty_shards"]))
    k_target = int(rng.choice([1, 2, 3, 4, 8, 16]))
    # extra value columns stress the lane codec width mix (bool lane,
    # 64-bit hi/lo split, f64 passthrough when x64 is live)
    import jax as _jax

    # dtype-mix draws dictionary-encoded STRING lanes too (ISSUE 3
    # satellite): a "str" extra column rides the shuffle's lane codec as
    # int32 dictionary codes, and with dtype == "string" the join
    # cross-check below runs the fused single-uint32-key fast path
    # (ops/join._fast_path_ok) over dictionary keys DISTRIBUTED — the
    # numeric-only mix never exercised it
    extra_cols = list(rng.choice(
        ["i64", "bool", "f64", "str"], size=int(rng.integers(0, 3)),
        replace=False,
    ))
    params = dict(seed=seed, profile="shuffle", n=n, keyspace=keyspace,
                  world=world, dtype=dtype, null_p=null_p, skew=skew,
                  k_target=k_target, extra=extra_cols)
    ctx = ctx_for(world)

    df = rand_frame(rng, n, keyspace, dtype, null_p)
    # reshape skew via numpy object arrays: pandas scalar assignment would
    # silently upcast the object key column (float64) and desync the oracle.
    # The hot value must be NON-NULL (an all-None key column would encode
    # as string and make the join cross-check unjoinable by construction)
    karr = df["k"].to_numpy(copy=True)
    non_null = [v for v in karr if v is not None]
    hot = non_null[0] if non_null else None
    if skew == "one_hot" and hot is not None:
        karr[:] = hot
        df["k"] = karr
    elif skew == "hot_key" and hot is not None:
        karr[rng.random(n) < 0.6] = hot
        df["k"] = karr
    for c in extra_cols:
        if c == "i64":
            df["i64"] = (rng.integers(-(2**40), 2**40, n)).astype(np.int64)
        elif c == "bool":
            df["flag"] = rng.random(n) < 0.5
        elif c == "f64" and _jax.config.jax_enable_x64:
            df["f64"] = rng.normal(size=n)  # float64 passthrough lane
        elif c == "str":
            # dictionary-encoded string value column (int32 code lane)
            df["s"] = rng.choice([f"tag{i}" for i in range(17)], n)

    if skew == "empty_shards":
        shards = [{c: df[c].to_numpy() for c in df.columns}] + [
            {c: df[c].to_numpy()[:0] for c in df.columns}
            for _ in range(world - 1)
        ]
        t = ct.Table.from_shards(ctx, shards)
    else:
        t = ct.Table.from_pandas(ctx, df)

    # budget targeting ~k_target rounds over the hottest possible bucket
    # (the planner's own inverse — shuffle.budget_for_rounds)
    max_bucket = max(int(t.row_counts.max()), 1)
    budget = _sh.budget_for_rounds(
        max_bucket, k_target, world, _sh.exchange_row_bytes(t._flat_cols())
    )

    reset_trace()
    got = t.shuffle(["k"], byte_budget=budget)
    rounds = int(report("shuffle.")["shuffle.rounds"]["rows"])
    want = t.shuffle(["k"], byte_budget=1 << 40)
    params["rounds"] = rounds
    ok = True
    if not (got.row_counts == want.row_counts).all():
        print(f"MISMATCH shuffle_routing params={params} "
              f"got={got.row_counts} want={want.row_counts}", flush=True)
        ok = False
    ok &= check(got.to_pandas(), want.to_pandas(), "shuffle_chunked", params)
    if skew != "empty_shards":
        # content vs the source frame; skipped for the shard-built table,
        # whose per-shard ingest may promote nullable columns' host
        # REPRESENTATION (an ingest property the chunked-vs-unchunked
        # differential above is independent of)
        ok &= check(want.to_pandas(), df, "shuffle_content", params)

    # a distributed join under the same random budget vs pandas. Both sides
    # are re-ingested via from_pandas so they share one encoding (the
    # empty-shard ingest can promote a nullable-int key to string on the
    # shard-built table — an ingest property, not a shuffle one). When
    # nulls are in play, force one into EACH frame: a side that randomly
    # drew zero nulls would encode its key numerically while the other
    # side's null-bearing keys encode as strings, and the pair is then
    # unjoinable by construction (same reason the default profile's two
    # frames share one null density)
    rdf = rand_frame(rng, max(n // 2, 1), keyspace, dtype, null_p, "w")
    jdf = df[["k", "v"]].copy()
    if null_p > 0:
        for fr in (jdf, rdf):
            ka = fr["k"].to_numpy(copy=True)
            ka[0] = None
            fr["k"] = ka
    lt2 = ct.Table.from_pandas(ctx, jdf)
    rt = ct.Table.from_pandas(ctx, rdf)
    prev = os.environ.get("CYLON_TPU_SHUFFLE_BUDGET")
    os.environ["CYLON_TPU_SHUFFLE_BUDGET"] = str(budget)
    try:
        gotj = lt2.distributed_join(rt, on="k", how="inner").to_pandas()
    finally:
        if prev is None:
            os.environ.pop("CYLON_TPU_SHUFFLE_BUDGET", None)
        else:
            os.environ["CYLON_TPU_SHUFFLE_BUDGET"] = prev
    wantj = expected_join(jdf, rdf, "inner")
    ok &= check(gotj, wantj, "shuffle_join", params)
    return ok


def plan_round_once(seed) -> bool:
    """Plan-vs-eager oracle round: build a random LazyFrame pipeline
    (join [+ filter] -> groupby | sort | project), collect it through the
    optimizer, and compare against the same pipeline composed from the
    EAGER ops. The eager path is the oracle: the optimizer must never
    change a result, only the work done to produce it."""
    from cylon_tpu import col
    from cylon_tpu.plan.expr import filter_mask

    rng = np.random.default_rng(seed)
    n_l = int(rng.integers(2, MAX_N))
    n_r = int(rng.integers(2, MAX_N))
    keyspace = int(rng.integers(1, 40))
    dtype = str(rng.choice(["int32", "int64", "string"]))
    null_p = float(rng.choice([0.0, 0.15]))
    world = int(rng.choice([1, 2, 4, 8]))
    how = str(rng.choice(["inner", "left", "right"]))
    filt = bool(rng.integers(0, 2))
    tail = str(rng.choice(["groupby", "sort", "project"]))
    agg_op = str(rng.choice(["sum", "min", "max", "count", "mean"]))
    params = dict(seed=seed, profile="plan", n_l=n_l, n_r=n_r,
                  keyspace=keyspace, dtype=dtype, null_p=null_p, world=world,
                  how=how, filt=filt, tail=tail, agg=agg_op)
    ctx = ctx_for(world)
    ldf = rand_frame(rng, n_l, keyspace, dtype, null_p, "v")
    rdf = rand_frame(rng, n_r, keyspace, dtype, null_p, "w").rename(
        columns={"k": "rk"})
    lt = ct.Table.from_pandas(ctx, ldf)
    rt = ct.Table.from_pandas(ctx, rdf)

    lazy = lt.lazy().join(rt.lazy(), left_on="k", right_on="rk", how=how)
    eager = lt.distributed_join(rt, left_on=["k"], right_on=["rk"], how=how)
    if filt:
        expr = col("v") > 0.0
        lazy = lazy.filter(expr)
        eager = eager.filter(filter_mask(
            expr, {c: eager.column(c) for c in eager.column_names}))
    if tail == "groupby":
        lazy = lazy.groupby("k", {"v": agg_op})
        eager = eager.distributed_groupby("k", {"v": agg_op})
    elif tail == "sort":
        lazy = lazy.sort("k")
        eager = eager.distributed_sort("k")
    else:
        lazy = lazy.select(["k", "v"])
        eager = eager.project(["k", "v"])
    fired = lazy.explain()
    got = lazy.collect().to_pandas()
    want = eager.to_pandas()
    ok = check(got, want, f"plan/{how}/{tail}", params)
    if not ok:
        print(fired, flush=True)
    return ok


def _ordering_off(fn):
    """Run ``fn`` with every order-property consumer gate disabled
    (``cylon_tpu.ordering.disabled()`` — the one shared toggle; the chosen
    path is part of each kernel cache key, so flipping mid-process
    recompiles instead of aliasing). The fuzz oracle: fast path vs generic
    path on the same data."""
    from cylon_tpu.ordering import disabled

    with disabled():
        return fn()


def ordering_round_once(seed) -> bool:
    """Order-property oracle round (ISSUE 3): randomize (size, keyspace,
    dtype, null density, world, keep/agg/how), establish sortedness via
    ``sort``, and differential-check every sorted-input fast path —
    groupby run-detect, sort no-op/suffix, unique run-detect, single-column
    set-op searchsorted probe, key-order join emit, presorted-right probe —
    against the generic paths with the gates disabled. Also asserts the
    descriptor lifecycle: set by sort, dropped by the chunked shuffle."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, MAX_N))
    keyspace = int(rng.integers(1, 60))
    dtype = str(rng.choice(["int32", "int64", "float32", "string"]))
    null_p = float(rng.choice([0.0, 0.15]))
    world = int(rng.choice([1, 2, 4]))
    agg_op = str(rng.choice(["sum", "count", "mean", "min"]))
    keep = str(rng.choice(["first", "last"]))
    how = str(rng.choice(["inner", "left"]))
    params = dict(seed=seed, profile="ordering", n=n, keyspace=keyspace,
                  dtype=dtype, null_p=null_p, world=world, agg=agg_op,
                  keep=keep, how=how)
    ctx = ctx_for(world)
    ldf = rand_frame(rng, n, keyspace, dtype, null_p, "v")
    rdf = rand_frame(rng, max(n // 2, 1), keyspace, dtype, null_p, "w")
    lt = ct.Table.from_pandas(ctx, ldf)
    rt = ct.Table.from_pandas(ctx, rdf)
    ok = True

    s = lt.sort("k")
    if s.ordering is None:
        print(f"MISMATCH ordering_not_set params={params}", flush=True)
        ok = False

    # groupby run-detect vs factorize
    got = s.groupby("k", {"v": agg_op}).to_pandas()
    want = _ordering_off(lambda: s.groupby("k", {"v": agg_op}).to_pandas())
    ok &= check(got, want, "ordering/groupby", params)

    # sort no-op (idempotence) and suffix-only multi-key sort
    got = s.sort("k").to_pandas()
    want = _ordering_off(lambda: s.sort("k").to_pandas())
    ok &= check(got, want, "ordering/sort_noop", params)
    got = s.sort(["k", "v"]).to_pandas()
    want = _ordering_off(lambda: s.sort(["k", "v"]).to_pandas())
    ok &= check(got, want, "ordering/sort_suffix", params)

    # unique run-detect
    got = s.unique(["k"], keep=keep).to_pandas()
    want = _ordering_off(lambda: s.unique(["k"], keep=keep).to_pandas())
    ok &= check(got, want, "ordering/unique", params)

    # single-column set ops (searchsorted probe when mask-free)
    lk = lt.project(["k"]).sort("k")
    rk = rt.project(["k"]).sort("k")
    for op in ("union", "subtract", "intersect"):
        got = getattr(lk, op)(rk).to_pandas()
        want = _ordering_off(lambda: getattr(lk, op)(rk).to_pandas())
        ok &= check(got, want, f"ordering/{op}", params)

    # key-order join emit vs pandas (content) — and vs the plain emit
    want = expected_join(ldf, rdf, how)
    got = lt.distributed_join(rt, on="k", how=how,
                              emit_order="key").to_pandas()
    ok &= check(got, want, f"ordering/join_key_order/{how}", params)

    # presorted-right probe (local join: the descriptor survives to the
    # probe only without an intervening shuffle)
    ctx1 = ctx_for(1)
    lt1 = ct.Table.from_pandas(ctx1, ldf)
    rs1 = ct.Table.from_pandas(ctx1, rdf).sort("k")
    got = lt1.join(rs1, on="k", how=how).to_pandas()
    want = _ordering_off(lambda: lt1.join(rs1, on="k", how=how).to_pandas())
    ok &= check(got, want, f"ordering/join_presorted/{how}", params)

    # invalidation: a (possibly multi-round) chunked shuffle drops the claim
    if world > 1:
        shuffled = s.shuffle(["k"], byte_budget=int(rng.choice([512, 1 << 20])))
        if shuffled.ordering is not None:
            print(f"MISMATCH ordering_survived_shuffle params={params}",
                  flush=True)
            ok = False
    return ok


def round_once(seed) -> bool:
    rng = np.random.default_rng(seed)
    n_l = int(rng.integers(1, MAX_N))
    n_r = int(rng.integers(1, MAX_N))
    keyspace = int(rng.integers(1, 40))
    dtype = str(rng.choice(["int32", "int64", "float32", "string"]))
    null_p = float(rng.choice([0.0, 0.15, 0.4]))
    world = int(rng.choice([1, 2, 4, 8]))
    params = dict(seed=seed, n_l=n_l, n_r=n_r, keyspace=keyspace,
                  dtype=dtype, null_p=null_p, world=world)
    ctx = ctx_for(world)
    ldf = rand_frame(rng, n_l, keyspace, dtype, null_p, "v")
    rdf = rand_frame(rng, n_r, keyspace, dtype, null_p, "w")
    lt = ct.Table.from_pandas(ctx, ldf)
    rt = ct.Table.from_pandas(ctx, rdf)
    ok = True

    # joins: pandas matches None/NaN keys like values in merge object cols
    for how in ("inner", "left", "right", "outer"):
        want = expected_join(ldf, rdf, how)
        for mode in ("eager", "fused"):
            got = lt.distributed_join(rt, on="k", how=how, mode=mode).to_pandas()
            ok &= check(got, want, f"join/{how}/{mode}", params)
    # pallas_pk: dedicated int32 tables, rounds alternating between
    # unique right keys (the kernel path actually executes) and duplicated
    # right keys (fallback path); full-content compare vs the exact join
    pk_rng = np.random.default_rng(seed + 10_000)
    n_pk = int(pk_rng.integers(2, 300))
    if seed % 2 == 0:
        rk_pk = pk_rng.permutation(4 * n_pk).astype(np.int32)[:n_pk]  # unique
    else:
        rk_pk = pk_rng.integers(0, max(n_pk // 3, 1), n_pk).astype(np.int32)
    lk_pk = pk_rng.choice(rk_pk, n_pk).astype(np.int32)
    lk_pk[:: max(n_pk // 7, 1)] = (
        10_000_000 + np.arange(len(lk_pk[:: max(n_pk // 7, 1)]))
    )
    lt_pk = ct.Table.from_pydict(
        ctx, {"k": lk_pk, "v": pk_rng.normal(size=n_pk).astype(np.float32)}
    )
    rt_pk = ct.Table.from_pydict(
        ctx, {"k": rk_pk, "w": pk_rng.normal(size=n_pk).astype(np.float32)}
    )
    got = lt_pk.distributed_join(rt_pk, on="k", how="inner",
                                 algorithm="pallas_pk").to_pandas()
    want = lt_pk.distributed_join(rt_pk, on="k", how="inner").to_pandas()
    ok &= check(got, want, "join/pallas_pk", params)

    # windowed Pallas emit (interpret mode on the CPU mesh): every 5th
    # round re-runs one join under CYLON_TPU_EMIT_IMPL=windowed — the
    # env is read at trace time and impl_tag() keys the cache, so this
    # compiles the windowed program fresh and full-content-compares it
    if seed % 5 == 0:
        prev_emit = os.environ.get("CYLON_TPU_EMIT_IMPL")
        os.environ["CYLON_TPU_EMIT_IMPL"] = "windowed"
        try:
            got = lt.distributed_join(rt, on="k", how="left").to_pandas()
        finally:
            # restore (not pop): an operator-level override must survive
            if prev_emit is None:
                os.environ.pop("CYLON_TPU_EMIT_IMPL", None)
            else:
                os.environ["CYLON_TPU_EMIT_IMPL"] = prev_emit
        ok &= check(got, expected_join(ldf, rdf, "left"),
                    "join/windowed_emit", params)

    # set ops over the key column only
    lk, rk = lt.project(["k"]), rt.project(["k"])
    lkd = ldf[["k"]].drop_duplicates()
    rkd = rdf[["k"]].drop_duplicates()
    inr = lkd["k"].map(lambda v: any(
        (v is w) or (v == w) or (
            isinstance(v, float) and isinstance(w, float)
            and np.isnan(v) and np.isnan(w))
        for w in rdf["k"])
    )
    ok &= check(lk.distributed_union(rk).to_pandas(),
                pd.concat([lkd, rkd]).drop_duplicates(), "union", params)
    ok &= check(lk.distributed_subtract(rk).to_pandas(), lkd[~inr],
                "subtract", params)
    ok &= check(lk.distributed_intersect(rk).to_pandas(), lkd[inr],
                "intersect", params)

    # unique keep first
    ok &= check(lt.distributed_unique(["k"], keep="first").to_pandas(),
                ldf.drop_duplicates(subset=["k"], keep="first"),
                "unique", params)

    # groupby sum (nulls: our groupby keeps null-key group; pandas drops —
    # compare non-null groups only). Keys are unique per group, so sort by
    # key and allclose the sums: float32 pre-combine order differs from
    # pandas' single-pass order in the last digits, legitimately.
    got = lt.distributed_groupby("k", {"v": "sum"}).to_pandas()
    got = got[got["k"].notna()] if null_p else got
    want = ldf.dropna(subset=["k"]).groupby("k", as_index=False)["v"].sum()
    want = want.rename(columns={"v": "v_sum"})
    gk = got["k"].map(canon).to_numpy()
    wk = want["k"].map(canon).to_numpy()
    go, wo = np.argsort(gk, kind="stable"), np.argsort(wk, kind="stable")
    if not (
        len(got) == len(want)
        and (gk[go] == wk[wo]).all()
        and np.allclose(
            got["v_sum"].to_numpy()[go], want["v_sum"].to_numpy()[wo],
            rtol=1e-3, atol=1e-3,
        )
    ):
        print(f"MISMATCH groupby_sum params={params}", flush=True)
        ok = False

    # distributed sort on v (total order)
    got = lt.distributed_sort("v").to_pandas()["v"].to_numpy()
    if not (np.diff(got) >= 0).all():
        print(f"MISMATCH sort order params={params}", flush=True)
        ok = False

    # out-of-core join (chunked, spill, bucket pairs) vs pandas inner
    if null_p == 0.0 and dtype in ("int32", "int64"):
        from cylon_tpu.parallel.ooc import OutOfCoreJoin

        chunk = max(int(rng.integers(8, 64)), 1)
        nb = int(rng.choice([4, 8, 16]))
        lo = ldf.copy()
        ro = rdf.copy()
        lo["k"] = lo["k"].astype(np.int64)
        ro["k"] = ro["k"].astype(np.int64)
        job = OutOfCoreJoin(ctx, on="k", how="inner", num_buckets=nb)
        sink = job.execute(
            ({c: lo[c].to_numpy()[i:i + chunk] for c in lo.columns}
             for i in range(0, len(lo), chunk)),
            ({c: ro[c].to_numpy()[i:i + chunk] for c in ro.columns}
             for i in range(0, len(ro), chunk)),
        )
        if sink.rows != len(lo.merge(ro, on="k", how="inner")):
            print(f"MISMATCH ooc_join params={params} chunk={chunk} nb={nb}",
                  flush=True)
            ok = False

    # loc[list] on a (possibly duplicated) index vs pandas order/duplication
    if null_p == 0.0:
        ti = lt.set_index("k")
        pdi = ldf.set_index("k")
        labels = list(rng.choice(ldf["k"].to_numpy(), size=3, replace=True))
        want_loc = pdi.loc[labels, "v"]
        got_loc = ti.loc[labels].to_pandas()["v"]
        if not np.allclose(
            got_loc.to_numpy(), want_loc.to_numpy(), rtol=1e-4, atol=1e-5
        ):
            print(f"MISMATCH loc_list params={params} labels={labels}",
                  flush=True)
            ok = False

    # multi-key sort with mixed directions vs pandas (nulls last, stable)
    asc2 = bool(rng.integers(0, 2))
    got = lt.distributed_sort(["k", "v"], ascending=[True, asc2]).to_pandas()
    want = ldf.sort_values(
        ["k", "v"], ascending=[True, asc2], kind="mergesort",
        na_position="last",
    )
    gk = got["k"].map(canon).tolist()
    wk = want["k"].map(canon).tolist()
    gv = got["v"].to_numpy()
    wv = want["v"].to_numpy()
    if gk != wk or not np.allclose(gv, wv, rtol=1e-4, atol=1e-5):
        print(f"MISMATCH multikey_sort params={params} asc2={asc2}", flush=True)
        ok = False
    return ok


def semi_round_once(seed) -> bool:
    """Semi-join sketch filter oracle round (ISSUE 4): randomize
    (sizes, keyspace overlap fraction, dtype, null density, sketch bits,
    world) and run distributed joins + set ops twice — filter enabled vs
    the CYLON_TPU_NO_SEMI_FILTER=1 oracle — demanding EXACT sorted-output
    equality. The bloom's false positives and the range words' pruning
    must never change a row; null keys (which MATCH in this engine, pandas
    merge semantics) and dictionary string keys ride the same rounds."""
    from cylon_tpu.ops.sketch import disabled as _semi_off
    from cylon_tpu.utils.tracing import get_count, reset_trace

    rng = np.random.default_rng(seed)
    n_l = int(rng.integers(200, max(8 * MAX_N, 240)))
    n_r = int(rng.integers(200, max(8 * MAX_N, 240)))
    overlap = float(rng.choice([0.0, 0.05, 0.3, 1.0]))
    dtype = str(rng.choice(["int32", "int64", "float32", "string"]))
    null_p = float(rng.choice([0.0, 0.15]))
    world = int(rng.choice([1, 2, 4, 8]))
    bits = int(rng.choice([4096, 8192, 16384]))
    params = dict(seed=seed, profile="semi", n_l=n_l, n_r=n_r,
                  overlap=overlap, dtype=dtype, null_p=null_p, world=world,
                  bits=bits)
    ctx = ctx_for(world)

    def frame(n, lo_frac, vname):
        """Keys drawn from a window starting at lo_frac of the combined
        keyspace; overlap controls how much the two windows share."""
        K = max((n_l + n_r) // 2, 8)
        lo = int(lo_frac * K)
        keys = rng.integers(lo, lo + K, n)
        if dtype == "int64":
            k = (keys.astype(np.int64) * 3).astype(object)
        elif dtype == "float32":
            k = keys.astype(np.float32).astype(object)
        elif dtype == "string":
            k = np.array([f"s{v:07d}" for v in keys], dtype=object)
        else:
            k = keys.astype(np.int32).astype(object)
        if null_p:
            k[rng.random(n) < null_p] = None
        return pd.DataFrame({
            "k": k,
            vname: rng.normal(size=n).astype(np.float32),
            vname + "2": rng.normal(size=n).astype(np.float32),
        })

    ldf = frame(n_l, 0.0, "v")
    rdf = frame(n_r, 1.0 - overlap, "w")
    lt = ct.Table.from_pandas(ctx, ldf)
    rt = ct.Table.from_pandas(ctx, rdf)

    prev_bits = os.environ.get("CYLON_TPU_SKETCH_BITS")
    os.environ["CYLON_TPU_SKETCH_BITS"] = str(bits)
    ok = True
    try:
        reset_trace()
        for how in ("inner", "left", "right"):
            got = lt.distributed_join(rt, on="k", how=how).to_pandas()
            with _semi_off():
                want = lt.distributed_join(rt, on="k", how=how).to_pandas()
            ok &= check(got, want, f"semi/join/{how}", params)
        la, lb = lt.project(["k", "v"]), rt.rename(["k", "v", "v2"]).project(["k", "v"])
        for op in ("intersect", "subtract", "union"):
            got = getattr(la, f"distributed_{op}")(lb).to_pandas()
            with _semi_off():
                want = getattr(la, f"distributed_{op}")(lb).to_pandas()
            ok &= check(got, want, f"semi/{op}", params)
        params["filters_applied"] = get_count("shuffle.semi_filter.applied")
    finally:
        if prev_bits is None:
            os.environ.pop("CYLON_TPU_SKETCH_BITS", None)
        else:
            os.environ["CYLON_TPU_SKETCH_BITS"] = prev_bits
    return ok


def _packing_off(fn):
    """Run ``fn`` with lane packing disabled (sort-word fusion, canonical
    fusion, wire narrowing, stats establishment all off) — the
    CYLON_TPU_NO_LANE_PACK=1 differential oracle."""
    from cylon_tpu.ops.stats import disabled

    with disabled():
        return fn()


def _rand_key_col(rng, n, spec, null_p):
    """One random key column of a given (dtype, bit-width) spec as an
    object array (None = null)."""
    kind, bits = spec
    lo = -(1 << (bits - 1)) if kind.startswith("i") else 0
    hi = (1 << bits) - 1 + lo
    if kind == "bool":
        k = rng.integers(0, 2, n).astype(bool).astype(object)
    elif kind == "str":
        k = rng.choice([f"s{i}" for i in range(min(max(1 << bits, 2), 4096))], n).astype(object)
    elif kind == "f32":
        k = rng.integers(lo, max(hi, lo + 1), n).astype(np.float32).astype(object)
    elif kind == "f64":
        k = rng.integers(lo, max(hi, lo + 1), n).astype(np.float64).astype(object)
    else:
        dt = {"i8": np.int8, "i16": np.int16, "i32": np.int32,
              "i64": np.int64}[kind]
        k = rng.integers(lo, max(hi, lo + 1), n).astype(dt).astype(object)
    if null_p:
        k[rng.random(n) < null_p] = None
    return k


def packing_round_once(seed) -> bool:
    """Lane-packing oracle round (ISSUE 5): random key bit-widths, dtype
    mixes (narrow/wide ints, bool, dict strings, f32, f64 — the latter
    must decline), null densities and world sizes; multi-key sort,
    distributed join, groupby and shuffle each differential-checked
    against the CYLON_TPU_NO_LANE_PACK=1 oracle on the same inputs."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(4, MAX_N))
    world = int(rng.choice([1, 2, 4, 8]))
    null_p = float(rng.choice([0.0, 0.1, 0.3]))
    nkeys = int(rng.integers(1, 4))
    kinds = ["i8", "i16", "i32", "i64", "bool", "str", "f32", "f64"]
    specs = [
        (str(rng.choice(kinds)), int(rng.integers(1, 21)))
        for _ in range(nkeys)
    ]
    asc = [bool(rng.integers(0, 2)) for _ in range(nkeys)]
    params = dict(seed=seed, profile="packing", n=n, world=world,
                  null_p=null_p, specs=specs, asc=asc)
    ctx = ctx_for(world)
    knames = [f"k{i}" for i in range(nkeys)]
    data = {kn: _rand_key_col(rng, n, sp, null_p)
            for kn, sp in zip(knames, specs)}
    data["v"] = rng.normal(size=n).astype(np.float32)
    df = pd.DataFrame(data)
    rdf = pd.DataFrame({
        **{kn: _rand_key_col(rng, max(n // 2, 1), sp, null_p)
           for kn, sp in zip(knames, specs)},
        "w": rng.normal(size=max(n // 2, 1)).astype(np.float32),
    })
    ok = True

    t = ct.Table.from_pandas(ctx, df)
    got = t.sort(knames, ascending=asc).to_pandas()
    want = _packing_off(
        lambda: ct.Table.from_pandas(ctx, df)
        .sort(knames, ascending=asc).to_pandas()
    )
    # the oracle is OUR OWN unpacked lexsort on identical data: the packed
    # permutation must match row-for-row, so compare in emitted order
    # (check() would re-sort and mask an order bug)
    g = got.astype(str).reset_index(drop=True)
    w = want.astype(str).reset_index(drop=True)
    if len(g) != len(w) or not g.equals(w):
        print(f"MISMATCH packing/sort_order params={params}", flush=True)
        ok = False

    got = t.distributed_groupby(knames, {"v": "sum"}).to_pandas()
    want = _packing_off(
        lambda: ct.Table.from_pandas(ctx, df)
        .distributed_groupby(knames, {"v": "sum"}).to_pandas()
    )
    ok &= check(got, want, "packing/groupby", params)

    rt = ct.Table.from_pandas(ctx, rdf)
    got = t.distributed_join(rt, on=knames, how="inner").to_pandas()
    want = _packing_off(
        lambda: ct.Table.from_pandas(ctx, df).distributed_join(
            ct.Table.from_pandas(ctx, rdf), on=knames, how="inner"
        ).to_pandas()
    )
    ok &= check(got, want, "packing/join", params)

    if world > 1:
        got = t.shuffle([knames[0]]).to_pandas()
        want = _packing_off(
            lambda: ct.Table.from_pandas(ctx, df)
            .shuffle([knames[0]]).to_pandas()
        )
        ok &= check(got, want, "packing/shuffle", params)
    return ok


def quant_round_once(seed) -> bool:
    """Quantized-wire oracle round (ISSUE 13): random tolerance tier
    (q8 / qb16 / qf32 / off), dtype mix (f32 / f64 / f16 payloads beside
    int/string keys), world size, keyspace selectivity and optional
    forced spill tier — join, groupby-SUM and shuffle each checked
    against the CYLON_TPU_NO_QUANT=1 exact oracle on identical inputs:
    join/groupby keys, row identity and group identity must match
    EXACTLY; float payload columns must sit within the per-column
    relative error bound of the engaged tier (rows aligned by exact
    integer row ids, never by the lossy payload)."""
    from cylon_tpu.ops.quant import disabled as quant_off

    rng = np.random.default_rng(seed)
    n = int(rng.integers(32, MAX_N))
    world = int(rng.choice([1, 2, 4, 8]))
    keyspace = int(rng.integers(2, max(n // 2, 3)))
    tol = float(rng.choice([1e-2, 5e-2, 5e-3, 1e-6, 0.0]))
    pdt = str(rng.choice(["float32", "float64", "float16"]))
    spill = int(rng.choice([0, 0, 1]))  # 1-in-3 rounds force tier 1
    params = dict(seed=seed, profile="quant", n=n, world=world,
                  keyspace=keyspace, tol=tol, payload=pdt, spill=spill)
    ctx = ctx_for(world)
    np_pdt = np.dtype(pdt)
    ldf = pd.DataFrame({
        "k": rng.integers(-keyspace, keyspace, n).astype(np.int32),
        "v": (rng.normal(size=n) * 10).astype(np_pdt),
        "rid": np.arange(n, dtype=np.int64),
    })
    rdf = pd.DataFrame({
        "rk": rng.integers(-keyspace, keyspace, max(n // 2, 1)).astype(np.int32),
        "w": (rng.normal(size=max(n // 2, 1)) * 10).astype(np_pdt),
        "sid": np.arange(max(n // 2, 1), dtype=np.int64),
    })

    def run_all():
        lt = ct.Table.from_pandas(ctx, ldf)
        rt = ct.Table.from_pandas(ctx, rdf)
        join = lt.distributed_join(
            rt, left_on=["k"], right_on=["rk"], how="inner"
        ).to_pandas().sort_values(["rid", "sid"]).reset_index(drop=True)
        gb = ct.Table.from_pandas(ctx, ldf).distributed_groupby(
            ["k"], {"v": "sum"}
        ).to_pandas().sort_values("k").reset_index(drop=True)
        shuf = None
        if world > 1:
            shuf = ct.Table.from_pandas(ctx, ldf).shuffle(
                ["k"]
            ).to_pandas().sort_values("rid").reset_index(drop=True)
        return join, gb, shuf

    prev_tol = os.environ.get("CYLON_TPU_QUANT_TOL")
    prev_tier = os.environ.get("CYLON_TPU_SPILL_TIER")
    try:
        with quant_off():
            ej, eg, es = run_all()
        if tol:
            os.environ["CYLON_TPU_QUANT_TOL"] = str(tol)
        if spill:
            os.environ["CYLON_TPU_SPILL_TIER"] = str(spill)
        gj, gg, gs = run_all()
    finally:
        for var, prev in (("CYLON_TPU_QUANT_TOL", prev_tol),
                          ("CYLON_TPU_SPILL_TIER", prev_tier)):
            if prev is None:
                os.environ.pop(var, None)
            else:
                os.environ[var] = prev

    ok = True

    def bound_for(val_tol):
        # the engaged tier's END-TO-END bound (2 lossy crossings max)
        return val_tol if val_tol else 0.0

    def cmp_float(name, e, g, scale_ref):
        nonlocal ok
        # NaN passthrough is part of the codec contract (q8 reserves
        # codes for NaN/±inf): the masks must MATCH exactly — comparing
        # nan_to_num'd deltas would zero a NaN-vs-finite corruption
        if not (np.isnan(e) == np.isnan(g)).all():
            print(f"MISMATCH quant/{name} nan-mask params={params}",
                  flush=True)
            ok = False
            return
        fin = np.isfinite(e)
        if not (fin == np.isfinite(g)).all() or not (
            np.sign(e[~fin & ~np.isnan(e)])
            == np.sign(g[~fin & ~np.isnan(g)])
        ).all():
            print(f"MISMATCH quant/{name} inf params={params}", flush=True)
            ok = False
            return
        err = float(np.abs(e[fin] - g[fin]).max()) if fin.any() else 0.0
        ref = float(np.abs(scale_ref[np.isfinite(scale_ref)]).max()) if (
            np.isfinite(scale_ref).any()
        ) else 1.0
        ref = ref or 1.0
        if err > bound_for(tol) * ref + 1e-12:
            print(f"MISMATCH quant/{name} err={err} ref={ref} "
                  f"params={params}", flush=True)
            ok = False

    # join: exact identity on keys/ids, bounded payload error
    if len(ej) != len(gj) or not (
        (ej["rid"].values == gj["rid"].values).all()
        and (ej["sid"].values == gj["sid"].values).all()
        and (ej["k"].values == gj["k"].values).all()
    ):
        print(f"MISMATCH quant/join_identity params={params}", flush=True)
        ok = False
    else:
        for c in ("v", "w"):
            cmp_float(f"join.{c}", ej[c].values.astype(np.float64),
                      gj[c].values.astype(np.float64),
                      ej[c].values.astype(np.float64))
    # groupby-SUM: exact group identity, error budget scales with the
    # summed magnitudes (per-value errors accumulate across a group)
    if not (eg["k"].values == gg["k"].values).all():
        print(f"MISMATCH quant/group_identity params={params}", flush=True)
        ok = False
    else:
        e = eg["v_sum"].values.astype(np.float64)
        g = gg["v_sum"].values.astype(np.float64)
        budget = bound_for(tol) * float(
            np.abs(ldf["v"].values.astype(np.float64)).sum()
        )
        if float(np.abs(e - g).max()) > budget + 1e-9:
            print(f"MISMATCH quant/groupby params={params}", flush=True)
            ok = False
    # shuffle: pure routing — rid identity exact, payload bounded
    if es is not None:
        if not (es["rid"].values == gs["rid"].values).all():
            print(f"MISMATCH quant/shuffle_identity params={params}",
                  flush=True)
            ok = False
        else:
            cmp_float("shuffle.v", es["v"].values.astype(np.float64),
                      gs["v"].values.astype(np.float64),
                      es["v"].values.astype(np.float64))
    return ok


def serve_round_once(seed) -> bool:
    """Serving-batch oracle round (ISSUE 9): a random set of
    same-fingerprint parameter bindings (random per-binding sizes, shared
    random shape/dtype/null density/world/batch cap) executed through the
    ServeScheduler's stacked batch program and checked binding-by-binding
    against the serial ``collect()`` oracle. Payload values are
    integer-valued f32 so the batch's different reduction order cannot
    perturb sums — the oracle stays exact equality."""
    from cylon_tpu import col
    from cylon_tpu.serve import ServeScheduler

    rng = np.random.default_rng(seed)
    nb = int(rng.integers(2, 9))
    keyspace = int(rng.integers(1, 40))
    dtype = str(rng.choice(["int32", "int64", "string"]))
    null_p = float(rng.choice([0.0, 0.15]))
    world = int(rng.choice([1, 2, 4, 8]))
    how = str(rng.choice(["inner", "left", "right"]))
    filt = bool(rng.integers(0, 2))
    tail = str(rng.choice(["groupby", "sort", "project"]))
    agg_op = str(rng.choice(["sum", "min", "max", "count", "mean"]))
    batch_max = int(rng.choice([2, 4, 8, 16]))
    params = dict(seed=seed, profile="serve", nb=nb, keyspace=keyspace,
                  dtype=dtype, null_p=null_p, world=world, how=how,
                  filt=filt, tail=tail, agg=agg_op, batch_max=batch_max)
    ctx = ctx_for(world)

    def binding_frames():
        n_l = int(rng.integers(2, MAX_N))
        n_r = int(rng.integers(2, MAX_N))
        ldf = rand_frame(rng, n_l, keyspace, dtype, null_p, "v")
        rdf = rand_frame(rng, n_r, keyspace, dtype, null_p, "w").rename(
            columns={"k": "rk"})
        ldf["v"] = rng.integers(-50, 50, n_l).astype(np.float32)
        rdf["w"] = rng.integers(-50, 50, n_r).astype(np.float32)
        return ldf, rdf

    def build(lt, rt):
        lazy = lt.lazy().join(rt.lazy(), left_on="k", right_on="rk", how=how)
        if filt:
            lazy = lazy.filter(col("v") > 0.0)
        if tail == "groupby":
            return lazy.groupby("k", {"v": agg_op})
        if tail == "sort":
            return lazy.sort("k")
        return lazy.select(["k", "v"])

    plans = []
    for _ in range(nb):
        ldf, rdf = binding_frames()
        plans.append(build(
            ct.Table.from_pandas(ctx, ldf), ct.Table.from_pandas(ctx, rdf)
        ))
    oracle = [p.collect().to_pandas() for p in plans]

    prev = os.environ.get("CYLON_TPU_SERVE_BATCH_MAX")
    os.environ["CYLON_TPU_SERVE_BATCH_MAX"] = str(batch_max)
    try:
        sched = ServeScheduler(ctx, auto_start=False)
        futs = [sched.submit(p) for p in plans]
        sched.run_pending()
        got = [f.result(timeout=300).to_pandas() for f in futs]
    finally:
        if prev is None:
            os.environ.pop("CYLON_TPU_SERVE_BATCH_MAX", None)
        else:
            os.environ["CYLON_TPU_SERVE_BATCH_MAX"] = prev
    ok = True
    for i, (g, w) in enumerate(zip(got, oracle)):
        ok &= check(g, w, f"serve/{how}/{tail}[{i}/{nb}]", params)
    return ok


def spill_round_once(seed) -> bool:
    """Spill-tier rounds (ISSUE 10): random (world, forced tier 1/2 or
    measured auto-tier, chunking K, skew profile, dtype) push join + sort
    + shuffle through the spill-tiered planner and assert exact equality
    with the in-core tier-0 run (and transitively pandas — the tier-0
    path is the default profile's subject). The skew-split schedule runs
    LIVE here; ~half the rounds also flip the CYLON_TPU_NO_SKEW_SPLIT
    oracle to pin padded-vs-adaptive equality under random histograms."""
    from cylon_tpu.parallel import shuffle as _sh

    rng = np.random.default_rng(seed)
    n = int(rng.integers(100, max(MAX_N, 101)))
    keyspace = int(rng.integers(2, 200))
    world = int(rng.choice([1, 4, 8]))
    tier = int(rng.choice([0, 1, 2]))  # 0 = auto via tiny device budget
    dtype = str(rng.choice(["int32", "int64", "str"]))
    skew = str(rng.choice(["none", "one_hot", "hot_key"]))
    k_target = int(rng.choice([1, 4, 16]))
    oracle_skew = bool(rng.random() < 0.5)
    params = dict(seed=seed, profile="spill", n=n, keyspace=keyspace,
                  world=world, tier=tier, dtype=dtype, skew=skew,
                  k_target=k_target, oracle_skew=oracle_skew)
    ctx = ctx_for(world)

    ldf = rand_frame(rng, n, keyspace, dtype, 0.0)
    rdf = rand_frame(rng, max(n // 2, 30), keyspace, dtype, 0.0, vname="w")
    karr = ldf["k"].to_numpy(copy=True)
    hot = karr[0]
    if skew == "one_hot":
        karr[:] = hot
        ldf["k"] = karr
    elif skew == "hot_key":
        karr[rng.random(n) < 0.6] = hot
        ldf["k"] = karr
    lt = ct.Table.from_pandas(ctx, ldf)
    rt = ct.Table.from_pandas(ctx, rdf)
    max_bucket = max(int(lt.row_counts.max()), 1)
    budget = _sh.budget_for_rounds(
        max_bucket, k_target, world, _sh.exchange_row_bytes(lt._flat_cols())
    )

    base_join = lt.distributed_join(rt, on="k", how="inner").to_pandas()
    base_sort = lt.distributed_sort("k").to_pandas()["k"]
    base_shuf = lt.shuffle(["k"], byte_budget=budget).to_pandas()

    env = {"CYLON_TPU_SHUFFLE_BUDGET": str(budget)}
    if tier == 0:
        env["CYLON_TPU_SPILL_DEVICE_BUDGET"] = "64"
    else:
        env["CYLON_TPU_SPILL_TIER"] = str(tier)
    if oracle_skew:
        env["CYLON_TPU_NO_SKEW_SPLIT"] = "1"
    prev = {k: os.environ.get(k) for k in env}
    for k, v in env.items():
        os.environ[k] = v
    try:
        got_join = lt.distributed_join(rt, on="k", how="inner").to_pandas()
        got_sort = lt.distributed_sort("k").to_pandas()["k"]
        got_shuf = lt.shuffle(["k"], byte_budget=budget).to_pandas()
    finally:
        for k, p in prev.items():
            if p is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = p
    ok = check(got_join, base_join, "spill/join", params)
    ok &= check(got_shuf, base_shuf, "spill/shuffle", params)
    if not np.array_equal(
        np.asarray(got_sort.map(canon)), np.asarray(base_sort.map(canon))
    ):
        print(f"MISMATCH spill/sort params={params}", flush=True)
        ok = False
    return ok


def autotune_round_once(seed) -> bool:
    """Feedback-autopilot rounds (ISSUE 11): random (shape, selectivity,
    world, dtype, hysteresis depth) plans run against the
    CYLON_TPU_NO_AUTOTUNE=1 static-heuristic oracle, then TWICE through
    a fresh observation store — cold (explore/measure) and warm (tuned
    decisions active, after enough observations to flip) — asserting
    exact result equality in every regime. Roughly half the rounds also
    set a serving p99 target and/or a spill device budget so the
    serve-bucket and tier-promotion proposers exercise."""
    import shutil
    import tempfile

    from cylon_tpu.obs import store as obstore
    from cylon_tpu.plan.feedback import autotune_disabled

    rng = np.random.default_rng(seed)
    n_l = int(rng.integers(50, max(MAX_N, 51)))
    n_r = int(rng.integers(50, max(MAX_N, 51)))
    keyspace = int(rng.integers(2, 120))
    # selectivity lever: shift the right side's keyspace so only ~sel of
    # the left keys can find partners (drives the semi proposer across
    # its on/static/off bands)
    sel = float(rng.choice([0.05, 0.3, 0.7, 1.0]))
    world = int(rng.choice([1, 2, 4, 8]))
    dtype = str(rng.choice(["int32", "int64", "string"]))
    null_p = float(rng.choice([0.0, 0.1]))
    how = str(rng.choice(["inner", "left"]))
    tail = str(rng.choice(["groupby", "sort", "none"]))
    min_obs = int(rng.choice([1, 2, 3]))
    p99_target = bool(rng.random() < 0.5)
    spill_budget = bool(rng.random() < 0.5)
    warm_reps = min_obs + 2
    params = dict(seed=seed, profile="autotune", n_l=n_l, n_r=n_r,
                  keyspace=keyspace, sel=sel, world=world, dtype=dtype,
                  null_p=null_p, how=how, tail=tail, min_obs=min_obs,
                  p99_target=p99_target, spill_budget=spill_budget)
    ctx = ctx_for(world)

    ldf = rand_frame(rng, n_l, keyspace, dtype, null_p)
    rdf = rand_frame(rng, n_r, keyspace, dtype, null_p, vname="w").rename(
        columns={"k": "rk"})
    if sel < 1.0 and dtype != "string":
        # shift (1-sel) of the right keys out of the left keyspace
        mask = rng.random(n_r) >= sel
        shifted = rdf["rk"].to_numpy(copy=True)
        for i in np.nonzero(mask)[0]:
            if shifted[i] is not None:
                shifted[i] = shifted[i] + 10 * keyspace
        rdf["rk"] = shifted
    lt = ct.Table.from_pandas(ctx, ldf)
    rt = ct.Table.from_pandas(ctx, rdf)

    def build():
        lazy = lt.lazy().join(rt.lazy(), left_on="k", right_on="rk", how=how)
        if tail == "groupby":
            return lazy.groupby("k", {"v": "sum"})
        if tail == "sort":
            return lazy.sort("k")
        return lazy

    with autotune_disabled():
        oracle = build().collect().to_pandas()

    obs_dir = tempfile.mkdtemp(prefix="cylon_fuzz_obs_")
    env = {
        "CYLON_TPU_OBS_DIR": obs_dir,
        "CYLON_TPU_AUTOTUNE_MIN_OBS": str(min_obs),
    }
    if p99_target:
        env["CYLON_TPU_SERVE_P99_TARGET_MS"] = str(
            float(rng.choice([0.01, 50.0, 5000.0]))
        )
    if spill_budget:
        env["CYLON_TPU_SPILL_DEVICE_BUDGET"] = str(
            int(rng.choice([4096, 1 << 20]))
        )
    prev = {k: os.environ.get(k) for k in env}
    for k, v in env.items():
        os.environ[k] = v
    ok = True
    try:
        cold = build().collect().to_pandas()
        ok &= check(cold, oracle, f"autotune/cold/{how}/{tail}", params)
        for rep in range(warm_reps):
            warm = build().collect().to_pandas()
            ok &= check(
                warm, oracle, f"autotune/warm{rep}/{how}/{tail}", params
            )
        # a second process generation: reload the store from disk (the
        # journal/snapshot round-trip) and run once more
        obstore.reset_stores()
        reload_run = build().collect().to_pandas()
        ok &= check(
            reload_run, oracle, f"autotune/reload/{how}/{tail}", params
        )
    finally:
        for k, p in prev.items():
            if p is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = p
        obstore.reset_stores()
        shutil.rmtree(obs_dir, ignore_errors=True)
    return ok


def chaos_round_once(seed) -> bool:
    """Chaos rounds (ISSUE 14): one random seam armed at a random
    probability/kind/seed over a serving wave + a forced-spill-tier
    join, vs the faults-disabled oracle. The invariant under test is the
    failure model itself: every query must come back oracle-identical or
    raise a typed CylonError (nothing else — no wrong results, no
    untyped escapes, no hangs), and the admission leases + spill arenas
    must be back to baseline after the round."""
    import gc
    import shutil
    import tempfile

    from cylon_tpu import col, fault
    from cylon_tpu.fault import CylonError
    from cylon_tpu.parallel import spill as spill_mod
    from cylon_tpu.serve import ServeScheduler

    rng = np.random.default_rng(seed)
    seam = str(rng.choice(list(fault.SEAMS)))
    kind = str(rng.choice({
        "spill.write": ["ENOSPC", "EIO"],
        "spill.read": ["EIO", "ENOSPC"],
        "arena.alloc": ["ENOSPC", "ENOMEM"],
        "serve.batch_exec": ["exec", "timeout"],
        "serve.single_exec": ["exec", "timeout"],
        "serve.worker": ["die", "exec"],
        "obs.journal": ["EIO", "ENOSPC"],
    }[seam]))
    p = float(rng.choice([0.05, 0.3, 1.0]))
    n_cap = rng.choice([1, 3, 0])  # 0 = uncapped
    fseed = int(rng.integers(0, 1 << 16))
    world = int(rng.choice([1, 4, 8]))
    nb = int(rng.integers(2, 7))
    tier = int(rng.choice([1, 2]))
    retries = int(rng.choice([0, 1, 2]))
    params = dict(seed=seed, profile="chaos", seam=seam, kind=kind, p=p,
                  n=int(n_cap), fseed=fseed, world=world, nb=nb,
                  tier=tier, retries=retries)
    ctx = ctx_for(world)

    def mk_pair(n_l, n_r, ks):
        ldf = rand_frame(rng, n_l, ks, "int32", 0.0)
        rdf = rand_frame(rng, n_r, ks, "int32", 0.0, "w").rename(
            columns={"k": "rk"})
        ldf["v"] = rng.integers(-50, 50, n_l).astype(np.float32)
        rdf["w"] = rng.integers(-50, 50, n_r).astype(np.float32)
        return (ct.Table.from_pandas(ctx, ldf), ct.Table.from_pandas(ctx, rdf))

    plans = []
    for _ in range(nb):
        lt, rt = mk_pair(int(rng.integers(50, MAX_N)),
                         int(rng.integers(50, MAX_N)),
                         int(rng.integers(2, 40)))
        plans.append(
            lt.lazy().join(rt.lazy(), left_on="k", right_on="rk")
            .filter(col("w") > 0.0).groupby("k", {"v": "sum"})
        )
    sl, sr = mk_pair(MAX_N, MAX_N, 64)
    serve_oracle = [p_.collect().to_pandas() for p_ in plans]
    spill_dir = tempfile.mkdtemp(prefix="cylon_fuzz_chaos_")
    obs_dir = tempfile.mkdtemp(prefix="cylon_fuzz_chaos_obs_")

    spec = f"{seam}:p={p}:kind={kind}:seed={fseed}"
    if n_cap:
        spec += f":n={int(n_cap)}"
    env = {
        "CYLON_TPU_FAULTS": spec,
        "CYLON_TPU_SPILL_RETRIES": str(retries),
    }
    if seam == "obs.journal":
        env["CYLON_TPU_OBS_DIR"] = obs_dir
    prev = {k: os.environ.get(k) for k in env}
    prev_tier = {
        k: os.environ.get(k)
        for k in ("CYLON_TPU_SPILL_TIER", "CYLON_TPU_SPILL_DIR")
    }

    def spill_join():
        os.environ["CYLON_TPU_SPILL_TIER"] = str(tier)
        os.environ["CYLON_TPU_SPILL_DIR"] = spill_dir
        try:
            return sl.distributed_join(sr, left_on=["k"], right_on=["rk"])
        finally:
            for k, v in prev_tier.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    spill_oracle = spill_join().to_pandas()
    ok = True
    for k, v in env.items():
        os.environ[k] = v
    fault.reset()
    if seam == "obs.journal":
        # the oracle collects above already instantiated the store
        # singleton against the DEFAULT obs dir; re-create it so the
        # armed round journals (and degrades) in the throwaway obs_dir
        from cylon_tpu.obs import store as _obstore

        _obstore.reset_stores()
    sched = None
    try:
        sched = ServeScheduler(ctx, auto_start=True)
        futs = [sched.submit(p_) for p_ in plans]
        for i, f in enumerate(futs):
            try:
                got = f.result(timeout=180).to_pandas()
            except CylonError:
                continue  # typed failure: the legal degradation outcome
            ok &= check(got, serve_oracle[i], f"chaos/serve[{i}]", params)
        sched.close()
        st = sched.stats()
        if st["leases"] != 0 or st["inflight_bytes"] != 0:
            print(f"MISMATCH chaos/lease_leak params={params} st={st}",
                  flush=True)
            ok = False
        sched = None
        del futs
        gc.collect()
        try:
            got = spill_join().to_pandas()
            ok &= check(got, spill_oracle, "chaos/spill_join", params)
        except CylonError:
            pass  # typed failure: legal
        gc.collect()
        live, _pk, disk, _dp = spill_mod.arena_bytes()
        if live != 0 or disk != 0:
            print(f"MISMATCH chaos/arena_leak params={params} "
                  f"live={live} disk={disk}", flush=True)
            ok = False
    except CylonError:
        pass  # a typed submit-time failure (scheduler closed etc.): legal
    except Exception:
        print(f"UNTYPED ESCAPE params={params}", flush=True)
        traceback.print_exc()
        ok = False
    finally:
        if sched is not None:
            # an escape above jumped over close(): close NOW so the
            # round can't leak a live worker thread (or quarantine
            # state) into later rounds, and the lease watermark still
            # gets enforced on the escape path
            try:
                sched.close()
                st = sched.stats()
                if st["leases"] != 0 or st["inflight_bytes"] != 0:
                    print(f"MISMATCH chaos/lease_leak params={params} "
                          f"st={st}", flush=True)
                    ok = False
            except Exception:
                traceback.print_exc()
                ok = False
            sched = None
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        fault.reset()
        from cylon_tpu.obs import store as _obstore

        _obstore.reset_stores()
        shutil.rmtree(spill_dir, ignore_errors=True)
        shutil.rmtree(obs_dir, ignore_errors=True)
    return ok


def stream_round_once(seed) -> bool:
    """Streaming-IVM differential round (ISSUE 16): random appendable
    topology (scan / join / filter-only / mean-fallback), random append
    sizes, dtype mixes, null densities and worlds; EVERY refresh is
    checked against the ``CYLON_TPU_NO_IVM=1`` full-recompute oracle
    (a fresh view over the same sources). Payloads are integer-valued
    f32 so the incremental merge's different association cannot perturb
    sums — the oracle stays exact equality."""
    from cylon_tpu import col, stream

    rng = np.random.default_rng(seed)
    keyspace = int(rng.integers(2, 40))
    dtype = str(rng.choice(["int32", "int64", "float32", "string"]))
    null_p = float(rng.choice([0.0, 0.15]))
    world = int(rng.choice([1, 2, 4, 8]))
    topo = str(rng.choice(["scan", "join", "filter", "mean"]))
    ops = list(rng.choice(["sum", "min", "max", "count"],
                          size=int(rng.integers(1, 3)), replace=False))
    filt = bool(rng.integers(0, 2))
    n_refresh = int(rng.integers(1, 4))
    chunk = int(rng.choice([0, 7, 64]))  # 0 = default staging chunk
    params = dict(seed=seed, profile="stream", keyspace=keyspace,
                  dtype=dtype, null_p=null_p, world=world, topo=topo,
                  ops=ops, filt=filt, n_refresh=n_refresh, chunk=chunk)
    ctx = ctx_for(world)

    def mk_batch(n, key, vname, initial=False):
        n = max(int(n), 2)
        df = rand_frame(rng, n, keyspace, dtype, null_p, vname)
        k = df["k"].to_numpy()
        if initial and all(v is None for v in k):
            # the spec is inferred from the initial batch: keep it typed
            df2 = rand_frame(rng, 1, keyspace, dtype, 0.0, vname)
            k[0] = df2["k"].to_numpy()[0]
        return {key: k,
                vname: rng.integers(-50, 50, n).astype(np.float32)}

    prev_chunk = os.environ.get("CYLON_TPU_STREAM_CHUNK_ROWS")
    if chunk:
        os.environ["CYLON_TPU_STREAM_CHUNK_ROWS"] = str(chunk)
    try:
        left = stream.AppendableTable(
            ctx, mk_batch(rng.integers(8, MAX_N), "k", "v", initial=True))
        sources = [left]
        if topo == "join":
            right = stream.AppendableTable(
                ctx, mk_batch(rng.integers(8, MAX_N), "rk", "w",
                              initial=True))
            sources.append(right)

        def build(*tabs):
            lazy = tabs[0].lazy()
            if topo == "join":
                lazy = lazy.join(tabs[1].lazy(), left_on="k", right_on="rk")
            if filt:
                lazy = lazy.filter(col("v") > 0.0)
            if topo == "filter":
                return lazy
            if topo == "mean":
                return lazy.groupby("k", {"v": "mean"})
            return lazy.groupby("k", {"v": ops})

        v = stream.view(build, *sources)
        ok = True
        for r in range(n_refresh):
            for _ in range(int(rng.integers(1, 3))):
                src = sources[int(rng.integers(0, len(sources)))]
                key, vname = (("rk", "w") if src is not left else ("k", "v"))
                src.append(mk_batch(rng.integers(2, MAX_N // 2), key, vname))
            got = v.refresh()
            with stream.ivm_disabled():
                want = stream.view(build, *sources).refresh()
            ok &= check(got.to_pandas(), want.to_pandas(),
                        f"stream/{topo}[{r}/{n_refresh}]",
                        dict(params, stats=dict(v.stats)))
        # the FIRST refresh is always the initial full compute; any later
        # refresh of these topologies must have taken the delta path
        if topo in ("scan", "join") and n_refresh >= 2 and v.stats["inc"] == 0:
            print(f"MISMATCH stream/{topo} never took the incremental "
                  f"path params={params} stats={v.stats}", flush=True)
            ok = False
        for s in sources:
            s.close()
        return ok
    finally:
        if prev_chunk is None:
            os.environ.pop("CYLON_TPU_STREAM_CHUNK_ROWS", None)
        else:
            os.environ["CYLON_TPU_STREAM_CHUNK_ROWS"] = prev_chunk


def topo_round_once(seed) -> bool:
    """Two-hop topology oracle round (ISSUE 17): randomize the 2-D mesh
    factorization (2x2 / 4x2 / 2x4), dtype mix, null density, skew shape
    and round count, then differential-check the two-hop shuffle AND a
    distributed join against the CYLON_TPU_NO_TOPO flat oracle. The
    decomposition is a wire-level rewrite — exact row equality always,
    including the ppermute ring relay the skewed draws engage."""
    from cylon_tpu.parallel import shuffle as _sh
    from cylon_tpu.parallel import topo as _topo
    from cylon_tpu.utils.tracing import report, reset_trace

    rng = np.random.default_rng(seed)
    world, mesh = [(4, "2x2"), (8, "4x2"), (8, "2x4")][
        int(rng.integers(0, 3))
    ]
    n = int(rng.integers(64, max(MAX_N, 65)))
    keyspace = int(rng.integers(2, 128))
    dtype = str(rng.choice(["int32", "int64", "float32", "string"]))
    null_p = float(rng.choice([0.0, 0.2]))
    skew = str(rng.choice(["uniform", "one_hot", "hot_key", "empty_shards"]))
    k_target = int(rng.choice([1, 2, 4]))
    params = dict(seed=seed, profile="topo", world=world, mesh=mesh, n=n,
                  keyspace=keyspace, dtype=dtype, null_p=null_p, skew=skew,
                  k_target=k_target)
    ctx = topo_ctx_for(world, mesh)

    df = rand_frame(rng, n, keyspace, dtype, null_p)
    karr = df["k"].to_numpy(copy=True)
    non_null = [v for v in karr if v is not None]
    hot = non_null[0] if non_null else None
    if skew == "one_hot" and hot is not None:
        karr[:] = hot
        df["k"] = karr
    elif skew == "hot_key" and hot is not None:
        karr[rng.random(n) < 0.6] = hot
        df["k"] = karr
    if skew == "empty_shards":
        shards = [{c: df[c].to_numpy() for c in df.columns}] + [
            {c: df[c].to_numpy()[:0] for c in df.columns}
            for _ in range(world - 1)
        ]
        t = ct.Table.from_shards(ctx, shards)
    else:
        t = ct.Table.from_pandas(ctx, df)

    max_bucket = max(int(t.row_counts.max()), 1)
    budget = _sh.budget_for_rounds(
        max_bucket, k_target, world, _sh.exchange_row_bytes(t._flat_cols())
    )
    reset_trace()
    got = t.shuffle(["k"], byte_budget=budget)
    r = report("shuffle.")
    params["rounds"] = int(r["shuffle.rounds"]["rows"])
    params["ring_rows"] = int(
        r.get("shuffle.relay.ring_rows", {}).get("rows", 0)
    )
    with _topo.disabled():
        want = t.shuffle(["k"], byte_budget=budget)
    ok = True
    if not (got.row_counts == want.row_counts).all():
        print(f"MISMATCH topo_routing params={params} "
              f"got={got.row_counts} want={want.row_counts}", flush=True)
        ok = False
    ok &= check(got.to_pandas(), want.to_pandas(), "topo_shuffle", params)

    # distributed join on a fresh pair, two-hop vs flat oracle
    rdf = rand_frame(rng, max(n // 2, 1), keyspace, dtype, null_p, "w")
    jdf = df[["k", "v"]].copy()
    if null_p > 0:
        for fr in (jdf, rdf):
            ka = fr["k"].to_numpy(copy=True)
            ka[0] = None
            fr["k"] = ka
    lt2 = ct.Table.from_pandas(ctx, jdf)
    rt = ct.Table.from_pandas(ctx, rdf)
    gotj = lt2.distributed_join(rt, on="k", how="inner").to_pandas()
    with _topo.disabled():
        wantj = lt2.distributed_join(rt, on="k", how="inner").to_pandas()
    ok &= check(gotj, wantj, "topo_join", params)
    return ok


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=30.0)
    ap.add_argument("--seed0", type=int, default=0)
    ap.add_argument("--max-n", type=int, default=400,
                    help="upper bound on random table sizes (bigger stresses "
                         "respill/overflow/capacity-retry paths)")
    ap.add_argument("--profile",
                    choices=["default", "skew", "plan", "shuffle",
                             "ordering", "semi", "packing", "serve",
                             "spill", "autotune", "quant", "chaos",
                             "stream", "topo"],
                    default="default",
                    help="'skew': adversarial hot-key rounds (one key ~50%% "
                         "of rows, world {4,8}, undersized fused capacities); "
                         "'plan': LazyFrame-optimizer-vs-eager oracle rounds; "
                         "'shuffle': chunked-shuffle oracle (random K / byte "
                         "budget / dtype mix / skew vs the eager unchunked "
                         "result); 'ordering': sorted-input fast paths "
                         "(groupby run-detect, sort no-op/suffix, unique, "
                         "set-op probe, key-order join) vs the generic paths "
                         "with CYLON_TPU_NO_ORDERING=1; 'semi': semi-join "
                         "sketch filter (random selectivity / dtype / "
                         "sketch bits / world) vs the "
                         "CYLON_TPU_NO_SEMI_FILTER=1 oracle; 'serve': "
                         "random binding sets / batch sizes through the "
                         "stacked serving batch path vs the serial "
                         "collect() oracle; 'spill': forced/auto spill "
                         "tiers 1-2 + skew-split schedules (random world/"
                         "K/skew/dtype) vs the in-core tier-0 oracle; "
                         "'autotune': cold- and warm-store runs of random "
                         "shapes/selectivities/worlds (+ store reload) vs "
                         "the CYLON_TPU_NO_AUTOTUNE=1 static oracle; "
                         "'quant': lossy-wire-tier rounds (random "
                         "tolerance/dtype-mix/world/selectivity/spill "
                         "tier) vs the CYLON_TPU_NO_QUANT=1 exact oracle "
                         "— exact key/group identity, per-column error "
                         "bounds on float payloads; 'chaos': one random "
                         "fault seam armed (random probability/kind/"
                         "seed/retry depth, ISSUE 14) over a serving "
                         "wave + forced-spill join vs the faults-"
                         "disabled oracle — every query must be oracle-"
                         "identical or typed-failed, leases/arenas back "
                         "to baseline; 'stream': streaming-IVM rounds "
                         "(random appendable topology / append sizes / "
                         "dtype mix / staging chunk / world, ISSUE 16) — "
                         "every incremental refresh vs the "
                         "CYLON_TPU_NO_IVM=1 full-recompute oracle; "
                         "'topo': two-hop hierarchical-shuffle rounds "
                         "(random 2x2/4x2/2x4 mesh factorization, dtype "
                         "mix, nulls, skew, K, ISSUE 17) — shuffle + "
                         "distributed join vs the CYLON_TPU_NO_TOPO "
                         "flat-exchange oracle, exact row equality")
    args = ap.parse_args()
    global MAX_N
    MAX_N = args.max_n
    fn = {"skew": skew_round_once, "plan": plan_round_once,
          "shuffle": shuffle_round_once,
          "ordering": ordering_round_once,
          "semi": semi_round_once,
          "packing": packing_round_once,
          "serve": serve_round_once,
          "spill": spill_round_once,
          "autotune": autotune_round_once,
          "quant": quant_round_once,
          "chaos": chaos_round_once,
          "stream": stream_round_once,
          "topo": topo_round_once}.get(args.profile, round_once)
    t_end = time.time() + args.minutes * 60
    seed = args.seed0
    failures = 0
    rounds = 0
    while time.time() < t_end:
        try:
            if not fn(seed):
                failures += 1
        except Exception:
            print(f"EXCEPTION seed={seed}", flush=True)
            traceback.print_exc()
            failures += 1
        rounds += 1
        if rounds % 5 == 0:
            print(f"# {rounds} rounds, {failures} failures", flush=True)
        # every round compiles fresh program shapes; unbounded jit caches
        # OOM'd LLVM after ~15 rounds (and the skew profile — 4 hows x
        # capacity/respill/slice variants with retries — after ~55: the
        # r4 campaign died of 'LLVM compilation error: Cannot allocate
        # memory' + SIGSEGV). Clear aggressively; compile time is not
        # what a fuzz campaign optimizes for.
        if rounds % (3 if args.profile == "skew" else 10) == 0:
            for c in CTXS.values():
                c.__dict__.get("_plan_cache", {}).clear()
                c.__dict__.get("_serve_batch_cache", {}).clear()
            import jax

            jax.clear_caches()
            for c in CTXS.values():
                c.__dict__.get("_jit_cache", {}).clear()
                c.__dict__.get("_spec_cap_hints", {}).clear()
        seed += 1
    print(f"DONE rounds={rounds} failures={failures}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
