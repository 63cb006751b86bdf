"""graft-lint: the static invariant analyzer (ISSUES 6-7).

Usage::

    python -m tools.graft_lint                # all three layers
    python -m tools.graft_lint --ast-only     # L1 source analysis (fast)
    python -m tools.graft_lint --effects-only # L3 effect/sync-freedom pass
    python -m tools.graft_lint --jaxpr-only   # L2 contract checks only
    python -m tools.graft_lint --json         # machine-readable findings
    python -m tools.graft_lint --list-gates   # dump the knob registry

Layer 1 (AST) finds env-gate reads missing from kernel cache keys,
trace-time reads of host-only knobs, closure-captured baked constants,
and unregistered ``CYLON_TPU_*`` reads — see
``cylon_tpu/analysis/ast_pass.py`` and docs/ARCHITECTURE.md "Static
invariants".

Layer 2 (jaxpr) traces the representative-plan registry
(``cylon_tpu/analysis/plans.py``) on a dryrun 8-device CPU mesh and
checks the collective/host-sync contract table
(``cylon_tpu/analysis/contracts.py``).

Layer 3 (effects) runs the interprocedural effect-inference pass
(``cylon_tpu/analysis/effects.py`` + ``syncfree.py``) over the Layer-1
call graph: every public ``Table``/``DataFrame``/``LazyFrame`` entry
point must match its pinned effect signature (``DISPATCH_SAFE`` <
``MATERIALIZE`` < ``SYNC``), every budget-owning function must reach
exactly its pinned number of host-sync sites, and no public entry may
reach an unguarded write of cross-query shared state.
``CYLON_TPU_NO_EFFECT_LINT=1`` skips this layer (declared in
``utils/envgate.py``; incident escape hatch only).

``--json`` emits one JSON object on stdout — per-layer findings with
rule id, ``file:line``, owning function and sync-site call paths, plus
the computed effect signature of every certified entry point.
``--json-out FILE`` writes the same object to FILE while keeping the
human-readable output, so the CI lint job gates and produces the
``graft-lint-findings`` artifact in a single analyzer run
(.github/workflows/ci.yml).

Exit status: 0 clean, 1 findings/violations, 2 usage or environment
error. CI runs all three layers on every PR.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_MESH_FLAG = "--xla_force_host_platform_device_count=8"


def _ensure_dryrun_mesh() -> None:
    """Idempotently request the 8-virtual-device CPU mesh; the platform
    pin keeps the dry run off an attached accelerator. Only takes
    effect if jax has not initialized its backend yet — plans.run_all
    raises a clean environment error otherwise."""
    cur = os.environ.get("XLA_FLAGS", "")
    if _MESH_FLAG not in cur:
        os.environ["XLA_FLAGS"] = (cur + " " + _MESH_FLAG).strip()
    os.environ.setdefault("CYLON_TPU_PLATFORM", "cpu")


def _jaxpr_layer_selected(argv) -> bool:
    """True when the given args will run the L2 jaxpr layer: either it is
    requested explicitly or no layer-selection flag narrows it away."""
    only = ("--ast-only", "--effects-only", "--jaxpr-only")
    return "--jaxpr-only" in argv or not any(f in argv for f in only)


# the dryrun mesh needs the virtual devices BEFORE jax initializes, so
# decide from sys.argv at import time; main() re-asserts from its own
# argv (best-effort — only effective while jax is still uninitialized)
if _jaxpr_layer_selected(sys.argv):
    _ensure_dryrun_mesh()


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _finding_dict(f) -> dict:
    return {
        "rule": f.rule,
        "file": f.file,
        "line": f.line,
        "func": f.func,
        "name": f.name,
        "message": f.message,
    }


def run_ast_layer(verbose: bool, emit):
    from cylon_tpu.analysis.ast_pass import (
        check_no_blanket_exemptions,
        run_ast_pass,
    )

    root = os.path.join(_repo_root(), "cylon_tpu")
    findings = run_ast_pass(root, package="cylon_tpu")
    problems = check_no_blanket_exemptions()
    for f in findings:
        emit(str(f))
    for p in problems:
        emit(f"[exemption-audit] {p}")
    n = len(findings) + len(problems)
    emit(f"graft-lint AST layer: {n} finding(s)")
    payload = {
        "findings": [_finding_dict(f) for f in findings],
        "exemption_audit": list(problems),
    }
    return (1 if n else 0), payload


def run_effect_layer(verbose: bool, emit):
    from cylon_tpu.analysis.syncfree import run_effect_pass
    from cylon_tpu.utils.envgate import NO_EFFECT_LINT

    if NO_EFFECT_LINT.truthy():
        emit(
            "graft-lint effect layer: SKIPPED (CYLON_TPU_NO_EFFECT_LINT "
            "is set — incident escape hatch, do not merge on this)"
        )
        return 0, {"skipped": True}

    root = os.path.join(_repo_root(), "cylon_tpu")
    findings, reports = run_effect_pass(root, package="cylon_tpu")
    for f in findings:
        emit(str(f))
    sigs = {}
    for name, rep in sorted(reports.items()):
        sigs[name] = {
            "signature": rep.signature,
            "sync_sites": [
                {
                    "kind": s.kind,
                    "file": s.file,
                    "line": s.line,
                    "path": [p for p in path],
                }
                for s, path in zip(rep.sync_sites, rep.sync_paths)
            ],
            "delegations": rep.delegations,
        }
        if verbose:
            emit(f"  {name:40s} {rep.signature}")
    emit(
        f"graft-lint effect layer: {len(reports)} entry point(s) "
        f"certified, {len(findings)} finding(s)"
    )
    payload = {
        "findings": [_finding_dict(f) for f in findings],
        "signatures": sigs,
    }
    return (1 if findings else 0), payload


def run_jaxpr_layer(verbose: bool, emit):
    from cylon_tpu.analysis import plans

    try:
        results = plans.run_all()
    except RuntimeError as e:
        emit(f"graft-lint jaxpr layer: environment error: {e}")
        return 2, {"error": str(e)}
    bad = 0
    payload = []
    for r in results:
        status = "ok" if not r.violations else "FAIL"
        line = (
            f"  [{status}] {r.name} (K={r.k}): collectives={r.census.counts}"
        )
        if r.sync_sites:
            line += f" syncs={r.sync_sites}"
        if verbose or r.violations:
            emit(line)
        for v in r.violations:
            bad += 1
            emit(f"    VIOLATION: {v}")
        payload.append(
            {
                "plan": r.name,
                "k": r.k,
                "collectives": dict(r.census.counts),
                "sync_sites": list(r.sync_sites),
                "violations": list(r.violations),
            }
        )
    emit(
        f"graft-lint jaxpr layer: {len(results)} plan(s) checked, "
        f"{bad} violation(s)"
    )
    return (1 if bad else 0), {"plans": payload}


def run_list_gates() -> int:
    from cylon_tpu.utils.envgate import REGISTRY

    for var in sorted(REGISTRY):
        k = REGISTRY[var]
        print(f"{var:32s} kind={k.kind:13s} default={k.default!r}")
        if k.keyed_via:
            print(f"{'':32s} keyed via: {k.keyed_via}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="graft_lint", description=__doc__)
    ap.add_argument("--ast-only", action="store_true")
    ap.add_argument("--effects-only", action="store_true")
    ap.add_argument("--jaxpr-only", action="store_true")
    ap.add_argument("--list-gates", action="store_true")
    ap.add_argument(
        "--json",
        action="store_true",
        help="one JSON object on stdout (per-layer findings + effect "
        "signatures); human output suppressed",
    )
    ap.add_argument(
        "--json-out",
        metavar="FILE",
        help="also write the JSON findings object to FILE (human output "
        "unaffected) — lets CI gate and produce the artifact in ONE run",
    )
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args(argv)
    if _jaxpr_layer_selected(sys.argv if argv is None else argv):
        _ensure_dryrun_mesh()  # idempotent; covers explicit-argv callers
    if args.list_gates:
        return run_list_gates()

    lines: list = []
    emit = lines.append if args.json else print

    only = [args.ast_only, args.effects_only, args.jaxpr_only]
    run_all = not any(only)
    rc = 0
    doc: dict = {"tool": "graft_lint", "layers": {}}
    if run_all or args.ast_only:
        code, payload = run_ast_layer(args.verbose, emit)
        rc = max(rc, code)
        doc["layers"]["ast"] = payload
    if run_all or args.effects_only:
        code, payload = run_effect_layer(args.verbose, emit)
        rc = max(rc, code)
        doc["layers"]["effects"] = payload
    if run_all or args.jaxpr_only:
        code, payload = run_jaxpr_layer(args.verbose, emit)
        rc = max(rc, code)
        doc["layers"]["jaxpr"] = payload
    doc["exit_status"] = rc
    if args.json:
        json.dump(doc, sys.stdout, indent=1)
        sys.stdout.write("\n")
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(doc, fh, indent=1)
            fh.write("\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
