"""graft-lint: the static invariant analyzer (ISSUE 6).

Covers both layers:

- AST pass: the four seeded known-bad fixtures (tests/lint_fixtures) are
  flagged with the right rule at the right site; the known-good twins
  and the LIVE TREE are clean; the exemption registry holds zero blanket
  entries.
- jaxpr pass: collective census mechanics (scan scaling, host-callback
  detection), the extra-collective and mid-loop-sync seeded violations,
  the fused join / q3 step contracts (pure trace, no execution), and —
  slow-marked, CI runs it via ``python -m tools.graft_lint`` — the full
  representative-plan registry.

The hand-written collective pins in test_shuffle_chunked.py /
test_semi_filter.py re-export their numbers from
``cylon_tpu.analysis.contracts``; this file pins the contract table's
own shape so those constants cannot drift silently.
"""
import os

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu.analysis import contracts
from cylon_tpu.analysis.ast_pass import (
    check_no_blanket_exemptions,
    run_ast_pass,
)
from cylon_tpu.analysis.jaxpr_pass import Census, census_fn
from cylon_tpu.analysis.hostsync import sync_monitor

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "lint_fixtures")
TREE = os.path.join(os.path.dirname(HERE), "cylon_tpu")


def _fixture_findings(name):
    return run_ast_pass(FIXTURES, files=[os.path.join(FIXTURES, name)])


# ----------------------------------------------------------------------
# AST pass: seeded fixtures
# ----------------------------------------------------------------------
def test_bad_gate_not_in_key_flagged():
    fs = _fixture_findings("bad_gate_not_in_key.py")
    assert len(fs) == 1, fs
    f = fs[0]
    assert f.rule == "gate-not-in-key"
    assert f.name == "CYLON_TPU_REPEAT_IMPL"
    assert "bad_gate_not_in_key" in f.func


def test_bad_baked_constant_flagged():
    fs = _fixture_findings("bad_baked_constant.py")
    assert len(fs) == 1, fs
    f = fs[0]
    assert f.rule == "baked-constant"
    assert f.name == "threshold"
    assert "kern" in f.func


def test_good_twins_clean():
    """The same shapes with the invariant held: taint into the key, the
    scalar as a key component, the declarative site comment."""
    assert _fixture_findings("good_cases.py") == []


def test_live_tree_clean():
    """The acceptance gate: zero findings over cylon_tpu/ itself."""
    fs = run_ast_pass(TREE, package="cylon_tpu")
    assert fs == [], "\n".join(str(f) for f in fs)


def test_no_blanket_exemptions():
    """Every registry exemption names a concrete gate and an audited
    reason; `# lint:` comments are site-scoped by construction."""
    assert check_no_blanket_exemptions() == []
    from cylon_tpu.analysis.registry import EXEMPT

    for (scope, var), reason in EXEMPT.items():
        assert var.startswith("CYLON_TPU_"), (scope, var)
        assert len(reason) >= 20, (scope, var)


def test_relative_import_resolution_in_package_init():
    """Regression: a package __init__'s dotted name IS its package, so
    `from .utils import envgate` in cylon_tpu/__init__.py must resolve
    to cylon_tpu.utils.envgate (dropping one fewer level than a plain
    module would) — getting this wrong silently loses analyzer edges."""
    from cylon_tpu.analysis.ast_pass import _resolve_relative

    assert (
        _resolve_relative("cylon_tpu", 1, "utils", is_pkg=True)
        == "cylon_tpu.utils"
    )
    assert (
        _resolve_relative("cylon_tpu.table", 1, "utils", is_pkg=False)
        == "cylon_tpu.utils"
    )
    assert (
        _resolve_relative("cylon_tpu.ops.join", 2, "utils.envgate")
        == "cylon_tpu.utils.envgate"
    )


def test_cyclic_helpers_keep_transitive_reads(tmp_path):
    """Regression: mutually recursive helpers must not memoize a partial
    read-set computed while the cycle was open — the gate read through
    the cycle must still reach the key-builder check."""
    src = tmp_path / "cyc.py"
    src.write_text(
        "import os\n"
        "from cylon_tpu.engine import get_kernel\n\n"
        "def f(n):\n"
        "    if n > 0:\n"
        "        return g(n - 1)\n"
        "    return os.environ.get('CYLON_TPU_REPEAT_IMPL', 'scatter')\n\n"
        "def g(n):\n"
        "    return f(n)\n\n"
        "def builder_fn(ctx, cols):\n"
        "    key = ('cyc', len(cols))\n\n"
        "    def build():\n"
        "        def kern(dp, rep):\n"
        "            return g(0)\n\n"
        "        return kern\n\n"
        "    return get_kernel(ctx, key, build)(cols, ())\n"
    )
    fs = run_ast_pass(str(tmp_path), files=[str(src)])
    assert any(
        f.rule == "gate-not-in-key" and f.name == "CYLON_TPU_REPEAT_IMPL"
        for f in fs
    ), fs


def test_unregistered_env_read_flagged(tmp_path):
    src = tmp_path / "rogue.py"
    src.write_text(
        "import os\n\n"
        "def rogue():\n"
        "    return os.environ.get('CYLON_TPU_BRAND_NEW_KNOB', '0')\n"
    )
    fs = run_ast_pass(str(tmp_path), files=[str(src)])
    assert [f.rule for f in fs] == ["unregistered-env-read"]
    assert fs[0].name == "CYLON_TPU_BRAND_NEW_KNOB"


# ----------------------------------------------------------------------
# jaxpr pass: census mechanics + seeded violations
# ----------------------------------------------------------------------
def _mesh4(devices):
    from jax.sharding import Mesh

    return Mesh(np.array(devices[:4]), ("dp",))


def _shard_fn(devices, body):
    import jax
    from jax.sharding import PartitionSpec as P

    from cylon_tpu.compat import shard_map

    return jax.jit(
        shard_map(
            body,
            mesh=_mesh4(devices),
            in_specs=(P("dp"),),
            out_specs=P("dp"),
        )
    )


def test_extra_collective_fixture_flagged(devices):
    """Seeded known-bad: a step that issues 3 all_to_alls against a
    2-collective contract."""
    import jax
    import jax.numpy as jnp

    def body(x):
        for _ in range(3):
            x = jax.lax.all_to_all(
                x.reshape(4, -1), "dp", 0, 0, tiled=False
            ).reshape(-1)
        return x

    cen = census_fn(
        _shard_fn(devices, body), jax.ShapeDtypeStruct((32,), jnp.int32)
    )
    assert cen.counts == {"all_to_all": 3}
    c = contracts.CollectiveContract(
        name="fixture_extra_coll", description="", collectives=2, all_to_all=2
    )
    viol = c.check(cen)
    assert len(viol) == 2 and "all_to_all = 3" in viol[1], viol


def test_census_scales_scan_rounds(devices):
    """A K-round fused loop in ONE program counts K collectives (the scan
    body is scaled by its trip count, like the roofline walker)."""
    import jax
    import jax.numpy as jnp

    def body(x):
        def round_(carry, _):
            y = jax.lax.all_to_all(
                carry.reshape(4, -1), "dp", 0, 0, tiled=False
            ).reshape(-1)
            return y, ()

        out, _ = jax.lax.scan(round_, x, None, length=5)
        return out

    cen = census_fn(
        _shard_fn(devices, body), jax.ShapeDtypeStruct((32,), jnp.int32)
    )
    assert cen.counts == {"all_to_all": 5}


def test_host_callback_detected():
    """In-program host transfers (callback primitives) violate every
    contract — no shipped kernel may round-trip to the host."""
    import jax
    import jax.numpy as jnp

    def body(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )

    cen = census_fn(jax.jit(body), jax.ShapeDtypeStruct((8,), jnp.float32))
    assert cen.host_callbacks
    viol = contracts.CONTRACTS["shuffle_single"].check(cen, k=0)
    assert any("host-callback" in v for v in viol)


def test_midloop_sync_fixture_flagged(devices):
    """Seeded known-bad: a dispatch loop that fetches EVERY round. The
    monitor attributes each fetch; the contract flags both the
    non-whitelisted site and the K-scaling sync count."""
    import jax.numpy as jnp

    from cylon_tpu import table as _t

    def bad_round_loop(bufs):
        out = []
        for b in bufs:  # one host sync per round — the anti-pattern
            out.append(_t._fetch(b, "shuffle.round_counts"))
        return out

    with sync_monitor() as events:
        bad_round_loop([jnp.zeros((4,)) for _ in range(4)])
    cen = Census(counts={"all_to_all": 4})
    viol = contracts.CONTRACTS["shuffle_single"].check(
        cen, k=4, sync_events=events
    )
    assert any("host syncs" in v for v in viol), viol
    assert any("outside the whitelisted sites" in v for v in viol), viol
    assert all(e.site == "bad_round_loop" for e in events)


# ----------------------------------------------------------------------
# contract table: the numbers the pin tests re-export
# ----------------------------------------------------------------------
def test_contract_constants_pinned():
    assert contracts.DIST_JOIN_PAYLOAD_COLLECTIVES == 2
    assert contracts.DIST_JOIN_SKETCH_COLLECTIVES == 1
    assert contracts.shuffle_collectives(7) == 7
    assert contracts.fused_join_collectives(2) == 8
    assert contracts.fused_q3_collectives(1) == 7
    assert contracts.SHUFFLE_HOST_SYNCS_PER_TABLE == 2
    assert "_shuffle_many" in contracts.SHUFFLE_SYNC_SITES


def test_fused_step_contracts_trace_only(ctx8):
    """The fused join + q3 step contracts hold by pure jaxpr census (no
    execution — this also pins the q3 path's collective count, the
    acceptance criterion)."""
    from cylon_tpu.analysis import plans

    for res in plans.run_fused_join_step(ctx8, None):
        assert res.violations == [], res.violations
    for res in plans.run_q3_fused_step(ctx8, None):
        assert res.violations == [], res.violations


def test_shuffle_contract_runtime(ctx8, rng):
    """One runtime plan in tier-1: the K-round shuffle's census + sync
    whitelist (K = 1 and K > 1; the deferred fetch stays ONE fetch)."""
    from cylon_tpu.analysis import plans

    for res in plans.run_shuffle_single(ctx8, rng):
        assert res.violations == [], (res.k, res.violations)
        # count-phase fetch in _shuffle_many; the ONE deferred round
        # fetch in _shuffle_many_rounds (phase 2, split out by the
        # ISSUE-14 failure-domain wrapper)
        assert res.sync_sites == ["_shuffle_many", "_shuffle_many_rounds"]


def test_replicate_join_contract_runtime(ctx8, rng):
    """The replicate route of a distributed join: one all_gather a lane of
    the small side and one of its counts, NO all_to_all, and the local
    join's one fetch; the shuffle-route contracts stand as they were."""
    from cylon_tpu.analysis import plans

    assert contracts.replicate_join_collectives(2) == 3
    assert contracts.CONTRACTS["dist_join"].all_to_all == 2
    assert contracts.CONTRACTS["dist_join"].all_gather == 0
    [res] = plans.run_dist_join_replicate(ctx8, rng)
    assert res.violations == [], res.violations
    assert res.census.counts.get("all_to_all", 0) == 0
    assert res.sync_sites == ["join"]


@pytest.mark.slow
def test_full_plan_registry(ctx8, rng):
    """Every representative plan vs the contract table (CI runs this via
    `python -m tools.graft_lint`; slow-marked for tier-1)."""
    from cylon_tpu.analysis import plans

    results = plans.run_all(ctx=ctx8)
    bad = [v for r in results for v in r.violations]
    assert bad == [], bad


# ----------------------------------------------------------------------
# Layer 3: effect inference + sync-freedom certification (ISSUE 7)
# ----------------------------------------------------------------------
def _effect_findings(name, budgets=None, signatures=None):
    from cylon_tpu.analysis.syncfree import run_effect_pass

    return run_effect_pass(
        FIXTURES,
        files=[os.path.join(FIXTURES, name)],
        budgets={} if budgets is None else budgets,
        signatures=signatures,
    )


def test_bad_hidden_fetch_flagged():
    """Seeded known-bad: a fetch hidden behind TWO call hops must fail
    the entry's 0-site sync budget AND drift its pinned signature, with
    the full call path in both messages."""
    fs, reports = _effect_findings(
        "bad_hidden_fetch.py",
        budgets={"collect_stats": contracts.SyncBudget(0)},
        signatures={"collect_stats": "DISPATCH_SAFE"},
    )
    assert sorted(f.rule for f in fs) == ["effect-drift", "sync-budget"], fs
    for f in fs:
        assert "collect_stats -> _tally -> _sum_counts" in f.message, f
    assert reports["collect_stats"].signature == "SYNC"
    [site] = reports["collect_stats"].sync_sites
    assert site.kind == "fetch" and site.line == 21


def test_bad_shared_write_flagged():
    """Seeded known-bad: an unguarded module-dict write reachable from a
    public entry is a finding (the concurrent-serving data race)."""
    fs, _ = _effect_findings("bad_shared_write.py")
    assert [f.rule for f in fs] == ["unguarded-shared-write"], fs
    assert fs[0].name == "_RESULT_CACHE[...]"
    assert fs[0].func.endswith("remember")


def test_effect_good_twins_clean():
    """The same shapes with the invariant held: lock-dominated write,
    GIL-atomic setdefault publish, `# lint: guarded=` / `# lint:
    sync=host` declarations, and a genuinely dispatch-safe chain."""
    fs, reports = _effect_findings("good_effect_cases.py")
    assert fs == [], fs
    assert reports["dispatch_chain"].signature == "DISPATCH_SAFE"
    assert reports["remember_locked"].signature == "DISPATCH_SAFE"


def test_live_tree_effect_clean():
    """The L3 acceptance gate: zero effect findings over cylon_tpu/ —
    every public entry matches its pinned signature, every sync budget
    holds exactly, no unguarded shared writes anywhere."""
    from cylon_tpu.analysis.syncfree import run_effect_pass

    fs, reports = run_effect_pass(TREE, package="cylon_tpu")
    assert fs == [], "\n".join(str(f) for f in fs)
    # every certified entry is pinned; no MUTATES_SHARED flag anywhere
    assert set(reports) == set(contracts.EFFECT_SIGNATURES)
    assert all("MUTATES_SHARED" not in r.signature for r in reports.values())


def test_l3_contract_constants_pinned():
    """The sync-budget numbers the runtime pins re-export."""
    assert contracts.EAGER_OP_HOST_SYNCS == 0
    assert contracts.Q3_DISPATCH_HOST_SYNCS == 1
    assert contracts.Q3_DISPATCH_SYNC_SITES == ("_materialize_counts",)
    for op in contracts.Q3_DISPATCH_OPS:
        assert contracts.SYNC_SITE_BUDGETS[op].sites == 0, op
    assert (
        contracts.SYNC_SITE_BUDGETS["table._shuffle_many"].sites
        == contracts.SHUFFLE_HOST_SYNCS_PER_TABLE
    )
    assert contracts.SYNC_SITE_BUDGETS["Table._materialize_counts"].amortized
    # the flagship signatures: the q3 components are dispatch-async
    assert contracts.EFFECT_SIGNATURES["Table.project"] == "DISPATCH_SAFE"
    assert "SYNC" not in contracts.EFFECT_SIGNATURES["Table.filter"]
    assert "SYNC" not in contracts.EFFECT_SIGNATURES["Table.groupby"]
    assert contracts.CONTRACTS["q3_dispatch"].sync_sites == (
        "_materialize_counts",
    )


def test_eager_sync_free_runtime(ctx8, rng):
    """Runtime twin of the 0-site budgets: filter/groupby/unique
    dispatch with ZERO monitored fetches."""
    from cylon_tpu.analysis import plans

    for res in plans.run_eager_sync_free(ctx8, rng):
        assert res.violations == [], res.violations
        assert res.sync_sites == []


def test_q3_dispatch_runtime(ctx8, rng):
    """THE ISSUE-7 acceptance pin at runtime: a fused q3 plan
    dispatch()es with zero host syncs and materializes with exactly one,
    attributed to _materialize_counts."""
    from cylon_tpu.analysis import plans

    for res in plans.run_q3_dispatch(ctx8, rng):
        assert res.violations == [], res.violations
        assert res.sync_sites == ["_materialize_counts"]


def test_graft_lint_json_effects(capsys):
    """--json emits one machine-readable object (the CI artifact)."""
    import json as _json

    from tools import graft_lint

    rc = graft_lint.main(["--effects-only", "--json"])
    out = capsys.readouterr().out
    doc = _json.loads(out)
    assert rc == 0 and doc["exit_status"] == 0
    eff = doc["layers"]["effects"]
    assert eff["findings"] == []
    assert len(eff["signatures"]) == len(contracts.EFFECT_SIGNATURES)
    assert (
        eff["signatures"]["Table.project"]["signature"] == "DISPATCH_SAFE"
    )


def test_no_effect_lint_kill_switch(capsys, monkeypatch):
    """CYLON_TPU_NO_EFFECT_LINT=1 skips Layer 3 (declared in envgate —
    an incident escape hatch, surfaced loudly in the output)."""
    import json as _json

    from tools import graft_lint

    monkeypatch.setenv("CYLON_TPU_NO_EFFECT_LINT", "1")
    rc = graft_lint.main(["--effects-only", "--json"])
    doc = _json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["layers"]["effects"] == {"skipped": True}
