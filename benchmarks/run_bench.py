"""Benchmark suite + scaling harness.

The analog of the reference's benchmark drivers and scaling orchestrator
(cpp/src/examples/bench/table_join_dist_test.cpp — per-rank join timing;
cpp/src/experiments/run_dist_scaling.py:9-40 — weak/strong scaling sweeps;
python/examples/op_benchmark/*.py — per-op micro-benchmarks).

Covers BASELINE.md's benchmark configs:
  1. local inner join (single shard)
  2. distributed join + groupby aggregate (TPC-H Q3-style) over a mesh
  3. distributed sort (sample-sort shuffle)
  4. set ops (union/subtract/intersect) with hash repartition
plus weak/strong scaling of the distributed join over mesh size.

Usage:
  python benchmarks/run_bench.py                 # full suite on best backend
  python benchmarks/run_bench.py --rows 2000000  # scale problem size
  python benchmarks/run_bench.py --cpu           # force host-CPU backend
  python benchmarks/run_bench.py --scaling       # add the mesh-size sweep
  python benchmarks/run_bench.py --out BENCH.md  # write the markdown table

Each result prints as a JSON line; --out also renders a markdown table.
On CPU the mesh is virtual (xla_force_host_platform_device_count), so
"scaling" measures sharding overhead, not real ICI speedup — the numbers
are still the regression baseline the real-TPU run is compared against.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("CYLON_TPU_NO_X64", "1")

import numpy as np

BASELINE_JOIN_ROWS_PER_SEC = 400e6 / 141.5  # reference 1-worker rate



def _vs_baseline(work_rows: int, seconds: float, world: int) -> float:
    """Per-chip rate vs the reference's published 1-worker rate — the ONE
    definition every bench row's vs_baseline cell uses."""
    return round(work_rows / seconds / BASELINE_JOIN_ROWS_PER_SEC / max(world, 1), 3)


def _bench(fn, reps: int):
    """(best wall seconds, first-call seconds [compile], warm samples).

    The per-rep samples feed the obs.metrics latency histograms (the
    serving substrate, ISSUE 8) so every BENCH row carries p50/p99
    columns from the SAME histogram implementation the plan-fingerprint
    registry uses — quantiles over the warm reps, compile excluded."""
    t0 = time.perf_counter()
    fn()
    compile_s = time.perf_counter() - t0
    samples = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        samples.append(time.perf_counter() - t0)
    return (min(samples) if samples else float("inf")), compile_s, samples


# the ONE completion fence (dependent-scalar fetch: one dispatch + one
# fetch that depends on every output column)
from bench import fence as _sync  # noqa: E402


def _roofline_recorded(extra: dict, hbm: float, measured_s: float, op) -> None:
    """%membw for an EAGER op chain: record every kernel dispatch during one
    warm call (engine.record_kernels) and sum the traced models — the model
    covers exactly the programs the op executed.

    Collective-volume accounting (collectives / collective_mb) is attached
    even with hbm<=0: the traced byte counts are platform-independent, and
    per-world collective volume is the quantity that predicts real ICI
    scaling from a virtual-CPU-mesh run. Only the bandwidth-relative
    numbers (model_s, pct_membw) need the real chip's hbm."""
    try:
        from benchmarks.roofline import Report, analyze, model_seconds, pct_membw
        from cylon_tpu import engine

        engine.record_kernels(True)
        try:
            op()
        finally:
            kernels = engine.recorded_kernels()
            engine.record_kernels(False)
        if not kernels:
            return
        total = Report()
        for fn, args in kernels:
            rep = analyze(fn, *args)
            total.sort_count += rep.sort_count
            total.sort_bytes_per_pass += rep.sort_bytes_per_pass
            total.sort_pass_bytes += rep.sort_pass_bytes
            total.sort_passes += rep.sort_passes
            total.gather_bytes += rep.gather_bytes
            total.scatter_bytes += rep.scatter_bytes
            total.elementwise_bytes += rep.elementwise_bytes
            total.collective_bytes += rep.collective_bytes
            total.collective_count += rep.collective_count
        if hbm > 0:
            extra["model_s"] = round(model_seconds(total, hbm), 4)
            extra["pct_membw"] = round(
                100 * pct_membw(total, measured_s, hbm), 1
            )
        extra["kernels"] = len(kernels)
        # bytes-over-ICI accounting (per op): the collective volume the
        # op ships across the mesh + how many collectives it issues
        extra["collectives"] = total.collective_count
        extra["collective_mb"] = round(total.collective_bytes / 1e6, 2)
        if total.sort_pass_bytes:
            extra["sort_passes_bytes_gb"] = round(total.sort_pass_bytes / 1e9, 2)
        if total.sort_passes:
            # traced pass census: a sort of n rows counts k(k+1)/2 passes
            # at k = ceil(log2 n)
            extra["sort_passes"] = round(total.sort_passes, 1)
    except Exception as e:
        print(f"# roofline(recorded) failed: {e}", file=sys.stderr)


def _roofline(extra: dict, hbm: float, measured_s: float, fn, *args) -> None:
    """Attach model_s / pct_membw for a traced program to a record's extras.
    The traced (fn, args) MUST reproduce the measured path's exact
    capacities — a different cap models a different kernel.

    Collective accounting (collectives / collective_mb) is attached even
    with hbm<=0, exactly like :func:`_roofline_recorded` — the fused
    single-program rows (dist_inner_join_fused / q3_fused) previously left
    their BENCH.md colls / coll MB cells blank because only the
    bandwidth-relative numbers were gated on a real chip's hbm."""
    try:
        from benchmarks.roofline import analyze, model_seconds, pct_membw

        rep = analyze(fn, *args)
        extra["collectives"] = rep.collective_count
        extra["collective_mb"] = round(rep.collective_bytes / 1e6, 2)
        if hbm > 0:
            extra["model_s"] = round(model_seconds(rep, hbm), 4)
            extra["pct_membw"] = round(100 * pct_membw(rep, measured_s, hbm), 1)
        if rep.sort_pass_bytes:
            extra["sort_passes_bytes_gb"] = round(rep.sort_pass_bytes / 1e9, 2)
        if rep.sort_passes:
            extra["sort_passes"] = round(rep.sort_passes, 1)
    except Exception as e:  # the model must never sink the bench
        print(f"# roofline failed: {e}", file=sys.stderr)


def make_tables(ct, ctx, n, keyspace, seed=0):
    rng = np.random.default_rng(seed)
    left = ct.Table.from_pydict(
        ctx,
        {"k": rng.integers(0, keyspace, n).astype(np.int32),
         "v": rng.normal(size=n).astype(np.float32)},
    )
    right = ct.Table.from_pydict(
        ctx,
        {"k": rng.integers(0, keyspace, n).astype(np.int32),
         "w": rng.normal(size=n).astype(np.float32)},
    )
    return left, right


def run_suite(n_rows: int, reps: int, mesh_devices, scaling: bool):
    import jax

    import cylon_tpu as ct

    results = []

    # host-core normalization (VERDICT r4 weak point 5): the CPU regression
    # baseline broke when the host dropped to one physical core — a per-core
    # rate survives host resizing, so round-over-round CPU comparisons read
    # this column, not wall time
    ncores = os.cpu_count() or 1
    is_cpu = mesh_devices[0].platform == "cpu"

    from cylon_tpu.obs import metrics as _obs_metrics

    def record(name, seconds, compile_s, work_rows, world, extra=None,
               samples=None):
        # warm-rep latency quantiles through the obs.metrics histogram
        # registry (keyed like a serving fingerprint: one distribution
        # per row+world) — rows that measure through the REAL plan
        # fingerprint (q3_lazy) put their own p50/p99 in extra instead
        lat = {}
        if samples:
            key = f"bench:{name}@w{world}"
            for dt in samples:
                _obs_metrics.observe_latency(key, dt, label=name)
            qq = _obs_metrics.latency_quantiles(key)
            lat = {"p50_ms": round(qq["p50_s"] * 1e3, 2),
                   "p99_ms": round(qq["p99_s"] * 1e3, 2)}
        rate = work_rows / seconds
        row = {
            "benchmark": name,
            "rows": work_rows,
            "world": world,
            "warm_s": round(seconds, 4),
            "compile_s": round(compile_s, 2),
            "rows_per_sec": round(rate),
            **({"host_cores": ncores,
                "rows_per_sec_per_core": round(rate / ncores)}
               if is_cpu else {}),
            **lat,
            **(extra or {}),
        }
        results.append(row)
        print(json.dumps(row), flush=True)

    # bandwidth assumption for every roofline row (0 disables the model)
    hbm = float(os.environ.get(
        "BENCH_HBM_GBPS",
        0 if mesh_devices[0].platform == "cpu" else 819.0,
    ))

    # ---- config 1: local inner join, single shard --------------------------
    ctx1 = ct.CylonContext.init_distributed(ct.TPUConfig(devices=mesh_devices[:1]))
    left, right = make_tables(ct, ctx1, n_rows, keyspace=n_rows)

    def local_join():
        out = left.join(right, on="k", how="inner")
        _sync(out)

    s, c, laps = _bench(local_join, reps)
    lj_extra = {"vs_baseline": _vs_baseline(2 * n_rows, s, 1)}
    if hbm > 0:
        import jax as _jax
        import jax.numpy as jnp

        from cylon_tpu.engine import round_cap
        from cylon_tpu.ops import join as _jops

        cap = left.shard_cap
        # the measured call takes the SPECULATIVE path: spec_cap =
        # round_cap(max(cap_l, cap_r)) (table.py speculative block)
        cap_out = round_cap(max(left.shard_cap, right.shard_cap))

        def _lj(lk, lv, rk, rv, nl, nr):
            return _jops.spec_join(
                [(lk, None)], [(rk, None)],
                [(lk, None), (lv, None)], [(rk, None), (rv, None)],
                nl, nr, _jops.INNER, cap_out,
            )[1]

        sds = _jax.ShapeDtypeStruct
        _roofline(
            lj_extra, hbm, s, _lj,
            sds((cap,), jnp.int32), sds((cap,), jnp.float32),
            sds((cap,), jnp.int32), sds((cap,), jnp.float32),
            sds((), jnp.int32), sds((), jnp.int32),
        )
    record("local_inner_join", s, c, 2 * n_rows, 1, lj_extra, samples=laps)

    # ---- the distributed configs over the widest mesh ----------------------
    world = len(mesh_devices)
    ctx = ct.CylonContext.init_distributed(ct.TPUConfig(devices=mesh_devices))
    left, right = make_tables(ct, ctx, n_rows, keyspace=n_rows)

    def dist_join():
        out = left.distributed_join(right, on="k", how="inner")
        _sync(out)

    s, c, laps = _bench(dist_join, reps)
    dj_extra = {"vs_baseline": _vs_baseline(2 * n_rows, s, world)}
    _roofline_recorded(dj_extra, hbm, s, dist_join)
    record("dist_inner_join", s, c, 2 * n_rows, world, dj_extra, samples=laps)

    # config 1a: the same join under the quantized float wire tier
    # (ops/quant.py, CYLON_TPU_QUANT_TOL=1e-2): the f32 payload lanes —
    # the reason this shape DECLINES bit-lossless wire narrowing — ride
    # block-scaled int8 fields, so the coll MB cell is the win
    # (tools/quant_smoke.py holds the CI gate and the error-bound pin)
    prev_qt = os.environ.get("CYLON_TPU_QUANT_TOL")
    os.environ["CYLON_TPU_QUANT_TOL"] = "1e-2"
    try:
        s, c, laps = _bench(dist_join, reps)
        djq_extra = {"vs_baseline": _vs_baseline(2 * n_rows, s, world)}
        _roofline_recorded(djq_extra, hbm, s, dist_join)
        record(
            "dist_inner_join_quant", s, c, 2 * n_rows, world, djq_extra,
            samples=laps,
        )
    finally:
        if prev_qt is None:
            os.environ.pop("CYLON_TPU_QUANT_TOL", None)
        else:
            os.environ["CYLON_TPU_QUANT_TOL"] = prev_qt

    # config 1b: the same join at ~10% selectivity with the semi-join
    # sketch filter (ops/sketch.py): both sides prune provably partnerless
    # rows against the other side's broadcast key sketch before the
    # payload all_to_all — the coll MB cell is the win, the sketch
    # collective's own bytes included (benchmarks/semi_filter_bench.py
    # holds the CI gate and the full selectivity sweep)
    from benchmarks.semi_filter_bench import make_pair as _semi_pair
    from cylon_tpu.ops import sketch as _sk_mod
    from cylon_tpu.utils.tracing import report as _trace_report
    from cylon_tpu.utils.tracing import reset_trace as _treset

    left_s, right_s = _semi_pair(
        ct, ctx, np.random.default_rng(7), n_rows, sel=0.10
    )

    def dist_join_semi():
        out = left_s.distributed_join(right_s, on="k", how="inner")
        _sync(out)

    s, c, laps = _bench(dist_join_semi, reps)
    djs_extra = {"vs_baseline": _vs_baseline(2 * n_rows, s, world)}
    _treset()
    _roofline_recorded(djs_extra, hbm, s, dist_join_semi)
    # the semi-filter gauges of the recorded call ride the bench row so
    # regenerated BENCH tables carry them next to the coll MB they explain
    sf = _trace_report("shuffle.semi_filter.")
    g = sf.get("shuffle.semi_filter.selectivity", {})
    if g.get("count"):
        djs_extra["semi_selectivity"] = round(g["total_s"] / g["count"], 4)
    djs_extra["sketch_mb"] = round(
        _trace_report("semi_filter.").get(
            "semi_filter.sketch_bytes", {}
        ).get("rows", 0) / 1e6,
        3,
    )
    # the unfiltered coll MB of the identical join, for the narrative
    with _sk_mod.disabled():
        off_extra = {}
        _roofline_recorded(off_extra, hbm, s, dist_join_semi)
        if "collective_mb" in off_extra:
            djs_extra["coll_mb_unfiltered"] = off_extra["collective_mb"]
    record("dist_inner_join_semi", s, c, 2 * n_rows, world, djs_extra, samples=laps)

    # fused execution mode: whole shuffle->join chain as ONE XLA program
    # with a single host sync (vs one sync per op phase in eager mode) —
    # the product surface of parallel/pipeline.py. The host_sync counter
    # demonstrates the dispatch reduction.
    from cylon_tpu.utils.tracing import get_count, reset_trace

    def dist_join_fused():
        out = left.distributed_join(right, on="k", how="inner", mode="fused")
        _sync(out)

    s, c, laps = _bench(dist_join_fused, reps)
    reset_trace()
    dist_join()
    eager_syncs = get_count("host_sync")
    reset_trace()
    dist_join_fused()
    fused_syncs = get_count("host_sync")
    djf_extra = {
        "vs_baseline": _vs_baseline(2 * n_rows, s, world),
        "host_syncs": fused_syncs, "host_syncs_eager": eager_syncs,
    }
    # traced even with hbm<=0: the collective cells are platform-free
    from cylon_tpu.engine import round_cap
    from cylon_tpu.ops.join import INNER as _INNER
    from cylon_tpu.parallel import shuffle as _shmod
    from cylon_tpu.parallel.pipeline import make_distributed_join_step

    # reproduce _fused_join's EXACT first-attempt capacities
    # (table.py _fused_join: capacity_factor=2.0, respill=1, and the
    # byte-budget clamp of the chunked engine)
    cap = max(left.shard_cap, right.shard_cap)
    respill = 1
    bucket_cap = round_cap(int(2.0 * cap / max(world, 1)))
    if world > 1:
        row_bytes = max(
            _shmod.exchange_row_bytes(left._flat_cols()),
            _shmod.exchange_row_bytes(right._flat_cols()),
        )
        bucket_cap = min(
            bucket_cap,
            _shmod.budget_bucket_cap(
                row_bytes, world, ctx.shuffle_byte_budget, bucket_cap
            ),
        )
        join_cap = round_cap(2 * (1 + respill) * world * bucket_cap)
    else:
        join_cap = round_cap(left.shard_cap + right.shard_cap)
    js = make_distributed_join_step(
        ctx.mesh, ctx.axis_name, (0,), (0,), _INNER,
        bucket_cap=bucket_cap, join_cap=join_cap, respill=respill,
    )
    _roofline(
        djf_extra, hbm, s, js,
        (left._flat_cols(), left.counts_dev,
         right._flat_cols(), right.counts_dev), (),
    )
    record("dist_inner_join_fused", s, c, 2 * n_rows, world, djf_extra, samples=laps)

    # config 2: join + groupby aggregate (TPC-H Q3-ish)
    def q3():
        out = left.distributed_join(right, on="k", how="inner")
        g = out.distributed_groupby("k_x", {"v": "sum"})
        _sync(g)

    s, c, laps = _bench(q3, reps)
    q3_extra = {"vs_baseline": _vs_baseline(2 * n_rows, s, world)}
    _roofline_recorded(q3_extra, hbm, s, q3)
    record("dist_join_groupby_q3", s, c, 2 * n_rows, world, q3_extra, samples=laps)

    # config 2a': the same chain with order propagation — the join emits
    # grouped-key order (emit_order='key', same kernel cost) and the
    # groupby's factorize lexsort elides into a run-detect; the sort GB
    # column is the measured win (benchmarks/ordering_bench.py gates it)
    def q3_ordered():
        out = left.distributed_join(right, on="k", how="inner",
                                    emit_order="key")
        g = out.distributed_groupby("k_x", {"v": "sum"})
        _sync(g)

    s, c, laps = _bench(q3_ordered, reps)
    q3o_extra = {"vs_baseline": _vs_baseline(2 * n_rows, s, world)}
    _roofline_recorded(q3o_extra, hbm, s, q3_ordered)
    record("dist_join_groupby_q3_ordered", s, c, 2 * n_rows, world, q3o_extra, samples=laps)

    # config 2a'': the SERVING-substrate row (ISSUE 8): the same q3
    # through the lazy plan layer over the cached executor. Its p50/p99
    # come from the REAL plan-fingerprint histogram that every
    # LazyFrame.dispatch() feeds (end time rides the deferred count
    # materialization) — exactly what the compile-once-serve-many
    # benchmark (ROADMAP 1) will read at scale.
    right_rk = right.rename({"k": "rk"})
    lf_q3 = (
        left.lazy()
        .join(right_rk.lazy(), left_on="k", right_on="rk")
        .groupby("k", {"v": "sum"})
    )

    def q3_lazy():
        lf_q3.collect()

    # compile OUTSIDE the histogram window (its observation is reset
    # away) so hist_count == the warm reps and p50/p99 are warm-query
    # latency only, matching the _bench docstring's contract
    t0 = time.perf_counter()
    q3_lazy()
    c = time.perf_counter() - t0
    _obs_metrics.reset_latency()
    laps = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        q3_lazy()
        laps.append(time.perf_counter() - t0)
    s = min(laps)
    rep = _obs_metrics.latency_report()
    fkey, ent = max(rep.items(), key=lambda kv: kv[1]["count"])
    ql_extra = {
        "vs_baseline": _vs_baseline(2 * n_rows, s, world),
        "fingerprint": fkey,
        "hist_count": ent["count"],
        "p50_ms": round(ent["p50_s"] * 1e3, 2),
        "p99_ms": round(ent["p99_s"] * 1e3, 2),
    }
    record("dist_join_groupby_q3_lazy", s, c, 2 * n_rows, world, ql_extra)

    # config 2b: the same chain fully fused (join + groupby + psum in one
    # program, parallel/pipeline.make_join_groupby_step — what the multichip
    # dryrun runs)
    from cylon_tpu.ops.join import INNER
    from cylon_tpu.parallel.pipeline import make_join_groupby_step

    cap = left.shard_cap
    step = make_join_groupby_step(
        ctx.mesh, ctx.axis_name, l_key_idx=(0,), r_key_idx=(0,),
        agg_col_idx=1, how=INNER,
        bucket_cap=max(64, 4 * cap // max(world, 1)),
        join_cap=4 * cap, group_cap=2 * cap,
    )
    lflat = left._flat_cols()
    rflat = right._flat_cols()

    def q3_fused():
        out = step((lflat, left.counts_dev, rflat, right.counts_dev), ())
        jax.block_until_ready(out)
        _ = np.asarray(out[3])  # the single fetch

    s, c, laps = _bench(q3_fused, reps)
    q3f_extra = {
        "vs_baseline": _vs_baseline(2 * n_rows, s, world),
        "host_syncs": 1,
    }
    # roofline (VERDICT round-2 item 2): same `step`, same args as measured
    _roofline(
        q3f_extra, hbm, s, step,
        (lflat, left.counts_dev, rflat, right.counts_dev), (),
    )
    record("dist_join_groupby_q3_fused", s, c, 2 * n_rows, world, q3f_extra, samples=laps)

    # config 3: distributed sort (sample sort)
    def dsort():
        out = left.distributed_sort("k")
        _sync(out)

    s, c, laps = _bench(dsort, reps)
    ds_extra = {"vs_baseline": _vs_baseline(n_rows, s, world)}
    _roofline_recorded(ds_extra, hbm, s, dsort)
    record("dist_sort", s, c, n_rows, world, ds_extra, samples=laps)

    # config 3b: the 3-key narrow-lane local sort (ISSUE 5 lane packing):
    # the packed row vs the kill-switch row is the measured sort-word
    # fusion win in the sort GB column (keys span ~12/~16/~20 bits ->
    # pad + 3 value lanes fuse into ONE uint64 sort word;
    # benchmarks/lane_pack_bench.py holds the CI gate)
    from benchmarks.lane_pack_bench import make_sort_table
    from cylon_tpu.ops import stats as _lp_gate

    mt = make_sort_table(ct, ctx, np.random.default_rng(9), n_rows)

    def msort():
        out = mt.sort(["a", "b", "c"])
        _sync(out)

    # the packed/nopack pair measures the LANE-FUSION win in isolation
    s, c, laps = _bench(msort, reps)
    mp_extra = {}
    _roofline_recorded(mp_extra, hbm, s, msort)
    record("multikey_sort_packed", s, c, n_rows, world, mp_extra,
           samples=laps)
    with _lp_gate.disabled():
        s, c, laps = _bench(msort, reps)
        mn_extra = {}
        _roofline_recorded(mn_extra, hbm, s, msort)
        record("multikey_sort_nopack", s, c, n_rows, world, mn_extra,
               samples=laps)

    # config 4: set ops (shuffle on all columns + sorted dedup) — identical
    # schemas required, so pair ``left`` with a second (k, v) table
    left2, _ = make_tables(ct, ctx, n_rows, keyspace=n_rows, seed=1)
    for name, f in (
        ("dist_union", lambda: left.distributed_union(left2)),
        ("dist_subtract", lambda: left.distributed_subtract(left2)),
        ("dist_intersect", lambda: left.distributed_intersect(left2)),
    ):
        def setop(f=f):
            out = f()
            _sync(out)

        s, c, laps = _bench(setop, reps)
        so_extra = {"vs_baseline": _vs_baseline(2 * n_rows, s, world)}
        _roofline_recorded(so_extra, hbm, s, setop)
        record(name, s, c, 2 * n_rows, world, so_extra, samples=laps)

    # config 5: out-of-core join — both inputs stream through bounded device
    # memory (Grace-style partitioned dag join, parallel/ooc.py; the analog
    # of the reference's byte-chunked streaming shuffle + DisJoinOP)
    from cylon_tpu.parallel.ooc import OutOfCoreJoin

    rng5 = np.random.default_rng(2)
    ooc_n = n_rows
    lk = rng5.integers(0, ooc_n, ooc_n).astype(np.int32)
    lv = rng5.normal(size=ooc_n).astype(np.float32)
    rk = rng5.integers(0, ooc_n, ooc_n).astype(np.int32)
    rv = rng5.normal(size=ooc_n).astype(np.float32)
    chunk_rows = max(ooc_n // 16, 1)

    def chunks(k, v, vname):
        for i in range(0, ooc_n, chunk_rows):
            yield {"k": k[i : i + chunk_rows], vname: v[i : i + chunk_rows]}

    runs = []  # (wall_s, cost_split) per call: split must match the best rep

    def ooc():
        t0 = time.perf_counter()
        job = OutOfCoreJoin(ctx, on="k", how="inner", num_buckets=16)
        sink = job.execute(chunks(lk, lv, "v"), chunks(rk, rv, "w"))
        runs.append((time.perf_counter() - t0, job.cost_split))
        return sink.rows

    s, c, laps = _bench(ooc, max(1, reps - 1))
    # gate_exempt: first-call time here is a full host-bound streaming run
    # (16 spills + 16 joins), not XLA compile tax — the compile gate would
    # misfire on runtime. cost_split: per-phase walls of the BEST rep (the
    # run warm_s describes) — the transfer phases (spill_fetch/drain_fetch)
    # are what a slow host link inflates; their share says how much.
    # runs[0] is the cold/compile call _bench always makes first; the best
    # warm rep's split is the one warm_s describes
    best_split = min(runs[1:], key=lambda t: t[0])[1]
    record("ooc_join_16chunks", s, c, 2 * ooc_n, world,
           {"chunk_rows": chunk_rows, "gate_exempt": True, **best_split},
           samples=laps)

    # ---- scaling sweep: strong scaling of the distributed join -------------
    if scaling and world > 1:
        sizes = [w for w in (1, 2, 4, 8) if w <= world]
        for w in sizes:
            ctxw = ct.CylonContext.init_distributed(
                ct.TPUConfig(devices=mesh_devices[:w])
            )
            lw, rw = make_tables(ct, ctxw, n_rows, keyspace=n_rows)

            def djw():
                out = lw.distributed_join(rw, on="k", how="inner")
                jax.block_until_ready([col.data for col in out._columns.values()])

            s, c, laps = _bench(djw, reps)
            sc_extra = {"vs_baseline": _vs_baseline(2 * n_rows, s, w)}
            _roofline_recorded(sc_extra, hbm, s, djw)
            record("dist_join_strong_scaling", s, c, 2 * n_rows, w, sc_extra, samples=laps)
            # weak scaling: n_rows per shard
            lww, rww = make_tables(ct, ctxw, n_rows * w // max(sizes), keyspace=n_rows)

            def djww():
                out = lww.distributed_join(rww, on="k", how="inner")
                jax.block_until_ready([col.data for col in out._columns.values()])

            s, c, laps = _bench(djww, reps)
            wc_extra = {"vs_baseline": _vs_baseline(2 * len(lww), s, w)}
            _roofline_recorded(wc_extra, hbm, s, djww)
            record("dist_join_weak_scaling", s, c, 2 * len(lww), w, wc_extra, samples=laps)

    return results


def to_markdown(results, header: str) -> str:
    lines = [header, "",
             "| benchmark | world | rows | warm s | p50 ms | p99 ms | compile s | rows/s | rows/s/core | vs_baseline | %membw | colls | coll MB | coll B/row | sort GB | sort passes |",
             "|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in results:
        # collective volume per world size: the quantity that predicts real
        # ICI scaling (VERDICT r3 weak point 6 — virtual-CPU-mesh wall time
        # does not)
        cmb = r.get("collective_mb", "")
        cbr = (
            round(1e6 * r["collective_mb"] / max(r["rows"], 1), 1)
            if isinstance(cmb, (int, float))
            else ""
        )
        rpc = r.get("rows_per_sec_per_core", "")
        rpc = f"{rpc:,}" if isinstance(rpc, int) else ""
        lines.append(
            f"| {r['benchmark']} | {r['world']} | {r['rows']:,} | {r['warm_s']} "
            # warm-rep latency quantiles from the obs.metrics histograms
            # (the q3_lazy row reads the real plan-fingerprint histogram)
            f"| {r.get('p50_ms', '')} | {r.get('p99_ms', '')} "
            f"| {r['compile_s']} | {r['rows_per_sec']:,} | {rpc} "
            f"| {r.get('vs_baseline', '')} "
            f"| {r.get('pct_membw', '')} | {r.get('collectives', '')} "
            f"| {cmb} | {cbr} "
            # traced sort-pass GB (the TPU wall-time pricing quantity —
            # BENCH.md sliced-join sweep; ordering rows show the elision)
            # + the traced pass census (L(L+1)/2 a sort)
            f"| {r.get('sort_passes_bytes_gb', '')} "
            f"| {r.get('sort_passes', '')} |"
        )
    return "\n".join(lines) + "\n"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=int(os.environ.get("BENCH_ROWS", 1_000_000)))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--cpu", action="store_true", help="force host-CPU backend")
    ap.add_argument("--mesh", type=int, default=8, help="max mesh size (CPU)")
    ap.add_argument("--scaling", action="store_true", help="mesh-size sweep")
    ap.add_argument("--out", type=str, default=None, help="write markdown table")
    ap.add_argument(
        "--compile-gate", type=float,
        default=float(os.environ.get("BENCH_COMPILE_GATE", 30.0)),
        help="fail (exit 1) if any benchmark's compile_s exceeds this many "
             "seconds; <=0 disables. The TPU-tax regression gate "
             "(VERDICT round 2: q3 fused was 165 s).",
    )
    args = ap.parse_args()

    import __graft_entry__ as ge

    if args.cpu:
        devices = ge._force_cpu_mesh(args.mesh)
    else:
        import bench as _b
        import jax

        _b.require_tpu()  # --cpu is the only way onto the CPU
        devices = jax.devices()

    import jax

    d0 = devices[0]
    print(f"# platform={d0.platform} device={getattr(d0, 'device_kind', '?')} "
          f"mesh={len(devices)}", file=sys.stderr)
    results = run_suite(args.rows, args.reps, devices, args.scaling)
    if args.out:
        hdr = (f"# BENCH — cylon_tpu op suite (platform={d0.platform}, "
               f"mesh={len(devices)}, rows={args.rows:,})")
        # preserve the hand-written trailing narrative across regeneration
        # (the table is generated; the narrative is not). The narrative
        # starts at the first recognized marker — the r4 collective-volume
        # section or the classic "Notes" paragraph.
        notes = ""
        if os.path.exists(args.out):
            with open(args.out) as f:
                prev = f.read()
            starts = [
                i for i in (
                    prev.find("\n**Collective-volume"),
                    prev.find("\nNotes"),
                ) if i >= 0
            ]
            if starts:
                notes = prev[min(starts):]
        with open(args.out, "w") as f:
            f.write(to_markdown(results, hdr) + notes)

    if args.compile_gate > 0:
        slow = [
            r for r in results
            if r["compile_s"] > args.compile_gate and not r.get("gate_exempt")
        ]
        if slow:
            for r in slow:
                print(
                    f"COMPILE GATE FAIL: {r['benchmark']} compiled in "
                    f"{r['compile_s']}s (> {args.compile_gate}s)",
                    file=sys.stderr,
                )
            sys.exit(1)
        print(
            f"# compile gate ok: all {len(results)} benchmarks compiled "
            f"under {args.compile_gate}s",
            file=sys.stderr,
        )


if __name__ == "__main__":
    main()
