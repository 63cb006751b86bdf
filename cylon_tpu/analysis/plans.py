"""The representative-plan registry for the jaxpr layer.

Each entry builds a small but shape-faithful instance of one dispatch
path on the dryrun mesh (8 virtual CPU devices), runs it warm under the
kernel recorder + host-sync monitor, and checks the measured collective
census and fetch sites against the contract table. ``python -m
tools.graft_lint --jaxpr`` runs every entry; ``tests/test_analysis.py``
runs them in-process on the shared test mesh.

Paths covered (the ISSUE-6 registry):

- ``shuffle_single``   — one-table hash shuffle at K = 1 and K > 1;
- ``shuffle_wire_packed`` — narrow-int table whose wire plan engages;
- ``shuffle_quant``    — f32-payload shuffle under the lossy wire tier
  (ISSUE 13): same collective/sync contract, quant gate engaged;
- ``dist_join``        — eager distributed inner join, semi filter off;
- ``dist_join_semi``   — selective pair, sketch all_gather engaged;
- ``dist_join_replicate`` — a LEFT join against a side 1/250 the size:
  the replicate route, one all_gather a lane, no all_to_all;
- ``fused_join_step``  — the fully fused join program (jaxpr census);
- ``q3_fused_step``    — the fused join->groupby-SUM (q3) program.

The ISSUE-17 topology entries:

- ``shuffle_two_hop``  — eager shuffle under a declared 4x2 topology:
  2K grouped all_to_alls, flat sync discipline, and the kill switch
  restores ``shuffle_single``'s census exactly;
- ``fused_join_step_topo`` / ``q3_fused_step_topo`` — the fused
  programs with a two-hop exchange (jaxpr census: doubled all_to_all,
  identical psums).

And the ISSUE-7 sync-freedom entries:

- ``eager_sync_free``  — filter/groupby/unique dispatch with ZERO
  monitored fetches (deferred count lanes);
- ``q3_dispatch``      — a fused q3 plan ``dispatch()`` on a 1-device
  mesh: zero syncs at dispatch, exactly ONE at result materialization,
  attributed to ``_materialize_counts``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

from .contracts import CONTRACTS
from .jaxpr_pass import Census, census_fn, census_recorded
from .hostsync import sync_monitor


@dataclass
class PlanResult:
    name: str
    k: int
    census: Census
    sync_sites: List[str]
    violations: List[str]


def dryrun_context(world: int = 8):
    """A CPU mesh context. The caller (tools/graft_lint) must have set
    ``--xla_force_host_platform_device_count`` BEFORE jax initialized;
    in-process test suites already run on the 8-device harness."""
    import jax

    import cylon_tpu as ct

    devices = jax.devices()
    if len(devices) < world:
        raise RuntimeError(
            f"dryrun mesh needs {world} devices, found {len(devices)}: set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax "
            "initializes (tools/graft_lint does this automatically)"
        )
    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=devices[:world])
    )


def _measure(op: Callable, contract, k: int) -> PlanResult:
    """Warm ``op`` outside the monitor, then census + sync-monitor one
    warm execution and check the contract."""
    op()
    op()
    with sync_monitor() as events:
        census, _nprog = census_recorded(op, warm=False)
    violations = contract.check(census, k=k, sync_events=events)
    return PlanResult(
        name=contract.name,
        k=k,
        census=census,
        sync_sites=[e.site for e in events],
        violations=violations,
    )


# ----------------------------------------------------------------------
# plan builders
# ----------------------------------------------------------------------
def _shuffle_table(ctx, rng, n=4000):
    import cylon_tpu as ct

    return ct.Table.from_pydict(
        ctx,
        {
            "k": rng.integers(0, 100, n).astype(np.int32),
            "v": rng.normal(size=n).astype(np.float32),
        },
    )


def run_shuffle_single(ctx, rng) -> List[PlanResult]:
    from ..utils.tracing import report, reset_trace

    t = _shuffle_table(ctx, rng)
    out = []
    contract = CONTRACTS["shuffle_single"]
    for budget in (1 << 40, 8 * 16 * 12):  # K = 1 and K > 1
        def op():
            return t.shuffle(["k"], byte_budget=budget)

        reset_trace()
        op()
        k = int(report("shuffle.")["shuffle.rounds"]["rows"])
        out.append(_measure(op, contract, k))
    return out


def run_shuffle_wire_packed(ctx, rng) -> List[PlanResult]:
    from ..utils.tracing import get_count, report, reset_trace

    import cylon_tpu as ct

    n = 4096
    t = ct.Table.from_pydict(
        ctx,
        {
            # narrow measured ranges: the wire plan's packed words beat
            # the plain int32/int64 lanes and the gate engages
            "k": rng.integers(0, 1 << 12, n).astype(np.int64),
            "a": rng.integers(0, 1 << 6, n).astype(np.int64),
            "b": rng.integers(0, 2, n).astype(bool),
        },
    )
    contract = CONTRACTS["shuffle_wire_packed"]

    def op():
        return t.shuffle(["k"])

    reset_trace()
    op()
    k = int(report("shuffle.")["shuffle.rounds"]["rows"])
    res = _measure(op, contract, k)
    if not get_count("lane_pack.wire.applied"):
        res.violations.append(
            "shuffle_wire_packed: the wire-narrowing gate never engaged — "
            "the plan is not exercising the packed-wire path"
        )
    return [res]


def run_shuffle_quant(ctx, rng) -> List[PlanResult]:
    """The quantized wire tier (ISSUE 13): an f32-payload shuffle under
    CYLON_TPU_QUANT_TOL=1e-2 keeps the K-collective / 2-sync contract —
    the lossy codec changes lane widths and header rows, nothing else —
    and the gate must actually engage."""
    import os

    from ..utils.tracing import get_count, report, reset_trace

    import cylon_tpu as ct

    n = 4096
    t = ct.Table.from_pydict(
        ctx,
        {
            "k": rng.integers(0, 1 << 10, n).astype(np.int32),
            "v": rng.normal(size=n).astype(np.float32),
            "u": rng.normal(size=n).astype(np.float32),
        },
    )
    contract = CONTRACTS["shuffle_quant"]

    def op():
        return t.shuffle(["k"])

    prev = os.environ.get("CYLON_TPU_QUANT_TOL")
    os.environ["CYLON_TPU_QUANT_TOL"] = "1e-2"
    try:
        reset_trace()
        op()
        k = int(report("shuffle.")["shuffle.rounds"]["rows"])
        res = _measure(op, contract, k)
        if not get_count("shuffle.quant.applied"):
            res.violations.append(
                "shuffle_quant: the lossy wire tier never engaged — the "
                "plan is not exercising the quantized path"
            )
    finally:
        if prev is None:
            os.environ.pop("CYLON_TPU_QUANT_TOL", None)
        else:
            os.environ["CYLON_TPU_QUANT_TOL"] = prev
    return [res]


def _join_pair(ctx, rng, n=2000):
    import cylon_tpu as ct

    lt = ct.Table.from_pydict(
        ctx,
        {
            "k": rng.integers(0, 200, n).astype(np.int32),
            "v": rng.normal(size=n).astype(np.float32),
        },
    )
    rt = ct.Table.from_pydict(
        ctx,
        {
            "k": rng.integers(0, 200, 3 * n // 4).astype(np.int32),
            "w": rng.normal(size=3 * n // 4).astype(np.float32),
        },
    )
    return lt, rt


def _selective_pair(ctx, rng, n=4000):
    """~10%-overlap keyspaces with payload columns wide enough to repay
    the sketch collective (mirrors tests/test_semi_filter.py)."""
    import cylon_tpu as ct

    K = 6 * n
    cols_l = {"k": rng.integers(0, K, n).astype(np.int32)}
    cols_r = {
        "k": rng.integers(int(0.9 * K), int(1.9 * K), n).astype(np.int32)
    }
    for i in range(3):
        cols_l[f"v{i}"] = rng.normal(size=n).astype(np.float32)
        cols_r[f"w{i}"] = rng.normal(size=n).astype(np.float32)
    return (
        ct.Table.from_pydict(ctx, cols_l),
        ct.Table.from_pydict(ctx, cols_r),
    )


def run_dist_join(ctx, rng) -> List[PlanResult]:
    from ..ops import sketch as _sk

    lt, rt = _join_pair(ctx, rng)
    contract = CONTRACTS["dist_join"]

    def op():
        return lt.distributed_join(rt, on="k", how="inner")

    with _sk.disabled():
        return [_measure(op, contract, 1)]


def run_dist_join_semi(ctx, rng) -> List[PlanResult]:
    from ..utils.tracing import get_count

    lt, rt = _selective_pair(ctx, rng)
    contract = CONTRACTS["dist_join_semi"]

    def op():
        return lt.distributed_join(rt, on="k", how="inner")

    res = _measure(op, contract, 1)
    if not get_count("shuffle.semi_filter.applied"):
        res.violations.append(
            "dist_join_semi: the semi filter never engaged — the plan is "
            "not exercising the sketch path"
        )
    return [res]


def run_dist_join_replicate(ctx, rng) -> List[PlanResult]:
    import cylon_tpu as ct
    from ..utils.tracing import get_count

    n = 8000
    lt = ct.Table.from_pydict(
        ctx,
        {
            "k": rng.integers(0, 48, n).astype(np.int32),
            "v": rng.normal(size=n).astype(np.float32),
        },
    )
    rt = ct.Table.from_pydict(
        ctx,
        {
            "k": rng.permutation(64)[:32].astype(np.int32),
            "w": rng.normal(size=32).astype(np.float32),
        },
    )
    contract = CONTRACTS["dist_join_replicate"]

    def op():
        return lt.distributed_join(rt, on="k", how="left")

    before = get_count("join.route.shuffle")
    res = _measure(op, contract, 2)  # the small side's two lanes
    if get_count("join.route.shuffle") != before:
        res.violations.append(
            "dist_join_replicate: the pair took the shuffle route — the "
            "plan is not exercising the replicate route"
        )
    return [res]


def _fused_step_census(ctx, make_step, respill: int, contract) -> PlanResult:
    import jax
    import jax.numpy as jnp

    world, cap = ctx.world_size, 64
    sds = jax.ShapeDtypeStruct
    cols = [
        (sds((world * cap,), jnp.int32), None),
        (sds((world * cap,), jnp.float32), None),
    ]
    counts = sds((world,), jnp.int32)
    step = make_step(respill)
    census = census_fn(step, (cols, counts, cols, counts), ())
    violations = contract.check(census, k=respill)
    return PlanResult(
        name=contract.name, k=respill, census=census,
        sync_sites=[], violations=violations,
    )


def run_fused_join_step(ctx, _rng) -> List[PlanResult]:
    from ..ops import join as _j
    from ..parallel.pipeline import make_distributed_join_step

    contract = CONTRACTS["fused_join_step"]

    def make(respill):
        return make_distributed_join_step(
            ctx.mesh, ctx.axis_name, l_key_idx=(0,), r_key_idx=(0,),
            how=_j.INNER, bucket_cap=32, join_cap=512, respill=respill,
        )

    return [
        _fused_step_census(ctx, make, respill, contract)
        for respill in (0, 1, 2)
    ]


def run_q3_fused_step(ctx, _rng) -> List[PlanResult]:
    from ..parallel.pipeline import make_join_groupby_step

    contract = CONTRACTS["q3_fused_step"]

    from ..ops import join as _j

    def make(respill):
        return make_join_groupby_step(
            ctx.mesh, ctx.axis_name, l_key_idx=(0,), r_key_idx=(0,),
            agg_col_idx=1, how=_j.INNER, bucket_cap=32, join_cap=512,
            group_cap=512, respill=respill,
        )

    return [
        _fused_step_census(ctx, make, respill, contract)
        for respill in (0, 1)
    ]


def _topo_context(world: int = 8):
    """A dryrun context with a declared 4x2 topology (PR 17)."""
    import jax

    import cylon_tpu as ct

    return ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:world], mesh_shape="4x2")
    )


def run_shuffle_two_hop(ctx, rng) -> List[PlanResult]:
    """The two-hop eager shuffle (PR 17): under a declared 4x2 topology
    every round's exchange is TWO grouped all_to_alls (inner combine +
    outer ship) with the flat shuffle's exact 2-site sync discipline;
    flipping the CYLON_TPU_NO_TOPO kill switch on the SAME context
    restores shuffle_single's census — the 1-D collective-count-identity
    acceptance pin."""
    from ..parallel import topo as _topo
    from ..utils.tracing import get_count, report, reset_trace

    ctx2 = _topo_context()
    t = _shuffle_table(ctx2, rng)
    contract = CONTRACTS["shuffle_two_hop"]

    def op():
        return t.shuffle(["k"])

    reset_trace()
    op()
    k = int(report("shuffle.")["shuffle.rounds"]["rows"])
    res = _measure(op, contract, k)
    if not get_count("shuffle.coll_bytes.inter"):
        res.violations.append(
            "shuffle_two_hop: the per-axis byte counters never moved — "
            "the plan is not exercising the two-hop path"
        )
    out = [res]
    with _topo.disabled():
        flat = _measure(op, CONTRACTS["shuffle_single"], k)
        flat.name = "shuffle_two_hop_killswitch"
        out.append(flat)
    return out


def run_fused_join_step_topo(ctx, _rng) -> List[PlanResult]:
    from ..ops import join as _j
    from ..parallel.pipeline import make_distributed_join_step
    from ..parallel.topo import Topology

    contract = CONTRACTS["fused_join_step_topo"]

    def make(respill):
        return make_distributed_join_step(
            ctx.mesh, ctx.axis_name, l_key_idx=(0,), r_key_idx=(0,),
            how=_j.INNER, bucket_cap=32, join_cap=512, respill=respill,
            topo=Topology(4, 2),
        )

    return [
        _fused_step_census(ctx, make, respill, contract)
        for respill in (0, 1)
    ]


def run_q3_fused_step_topo(ctx, _rng) -> List[PlanResult]:
    from ..ops import join as _j
    from ..parallel.pipeline import make_join_groupby_step
    from ..parallel.topo import Topology

    contract = CONTRACTS["q3_fused_step_topo"]

    def make(respill):
        return make_join_groupby_step(
            ctx.mesh, ctx.axis_name, l_key_idx=(0,), r_key_idx=(0,),
            agg_col_idx=1, how=_j.INNER, bucket_cap=32, join_cap=512,
            group_cap=512, respill=respill, topo=Topology(4, 2),
        )

    return [
        _fused_step_census(ctx, make, respill, contract)
        for respill in (0, 1)
    ]


def run_eager_sync_free(ctx, rng) -> List[PlanResult]:
    """The dispatch-async eager ops (ISSUE 7): filter, groupby and unique
    dispatched WITHOUT materializing the results must perform ZERO
    monitored fetches — their count lanes stay deferred on the device.
    The runtime twin of the L3 0-site sync budgets."""
    t = _shuffle_table(ctx, rng)
    contract = CONTRACTS["eager_sync_free"]

    def op():
        a = t.filter(t.column("k").data < 50)
        b = t.groupby("k", {"v": "sum"})
        c = t.unique(["k"])
        return a, b, c

    return [_measure(op, contract, 1)]


def run_q3_dispatch(ctx, rng) -> List[PlanResult]:
    """The ``collect_async`` precursor pin (ISSUE 7 acceptance): a fused
    q3 plan ``dispatch()``es with zero host syncs on a 1-device mesh (the
    serving shape — many concurrent single-replica queries); its ONE sync
    happens at result materialization, attributed to
    ``_materialize_counts``. Static twin: the ``q3-dispatch-budget`` rule
    in :mod:`.syncfree`."""
    import jax

    import cylon_tpu as ct

    ctx1 = ct.CylonContext.init_distributed(
        ct.TPUConfig(devices=jax.devices()[:1])
    )
    n = 2000
    ta = ct.Table.from_pydict(
        ctx1,
        {
            "k": rng.integers(0, 50, n).astype(np.int32),
            "v": rng.normal(size=n).astype(np.float32),
        },
    )
    tb = ct.Table.from_pydict(
        ctx1,
        {
            "rk": rng.integers(0, 50, n).astype(np.int32),
            "w": rng.normal(size=n).astype(np.float32),
        },
    )
    lf = (
        ta.lazy()
        .join(tb.lazy(), left_on="k", right_on="rk")
        .filter(ct.col("w") > 0.0)
        .groupby("k", {"v": "sum"})
    )
    contract = CONTRACTS["q3_dispatch"]

    def op():
        return lf.dispatch()._materialize()

    res = _measure(op, contract, 1)
    if "join_sum_by_key_pushdown" not in lf.explain():
        res.violations.append(
            "q3_dispatch: the plan did not lower to the fused "
            "join_sum_by_key_pushdown — the pin is not exercising the q3 "
            "fused path"
        )
    # the dispatch itself, before any result access, must be sync-free
    with sync_monitor() as dev_events:
        lf.dispatch()
    if dev_events:
        res.violations.append(
            f"q3_dispatch: dispatch() performed {len(dev_events)} host "
            "sync(s) before result materialization: "
            + ", ".join(f"{e.site} ({e.file}:{e.line})" for e in dev_events)
        )
    return [res]


PLAN_RUNNERS = [
    run_shuffle_single,
    run_shuffle_wire_packed,
    run_shuffle_quant,
    run_dist_join,
    run_dist_join_semi,
    run_dist_join_replicate,
    run_fused_join_step,
    run_q3_fused_step,
    run_shuffle_two_hop,
    run_fused_join_step_topo,
    run_q3_fused_step_topo,
    run_eager_sync_free,
    run_q3_dispatch,
]


def run_all(ctx=None, seed: int = 7) -> List[PlanResult]:
    """Run every registered plan; ``ctx=None`` builds the dryrun mesh."""
    if ctx is None:
        ctx = dryrun_context()
    results: List[PlanResult] = []
    for runner in PLAN_RUNNERS:
        rng = np.random.default_rng(seed)
        results.extend(runner(ctx, rng))
    return results
