"""graftcheck: the repo's static-analysis gate (lint + compiled audits).

Usage:
    python -m tools.graftcheck
        [--lint-only | --hlo-only | --shardflow | --reshard | --memory
         | --ledger]
        [--paths P ...] [--modes M ...] [--tp N] [--programs S ...]
        [--hbm-tol F] [--metrics-dir DIR] [--json]

Three passes:

- **pass 1** (``analysis/lint.py``): AST lint of the project's own
  sources for jit-safety, device-invariant and sharding-flow bug
  classes (the ``analysis/shardflow.py`` AST rules ride this pass);
- **pass 2** (``analysis/hlo_audit.py``): the compiled artifacts of the
  REAL programs — the train step under every ``--grad-sync`` mode plus
  the zero1 weight-update-sharding leg, all three serving programs for
  both KV-pool layouts at tp=1 and on a simulated TP submesh — audited
  for donation aliasing, host callbacks, and the DCN crossing census vs
  the analytic byte models;
- **pass 3** (``analysis/shardflow.py`` + ``analysis/reshard_audit.py``):
  train-state sharding coverage (``--shardflow``), the resharding census
  (``--reshard``: full collective inventory == the expected-inventory
  model; an unexpected all-gather is GSPMD quietly replicating a sharded
  tensor), and the HBM peak-memory audit (``--memory``:
  ``memory_analysis()`` pinned to the analytic model in ``obs/cost.py``).

A fourth, artifact-free leg rides the gate: the **goodput-ledger audit**
(``analysis/ledger_audit.py``, ``--ledger``) drives the real
``obs/ledger.py`` through a scripted virtual-clock fault trace — crash,
supervisor backoff, restore, rework — and pins every category's
attribution and the ``sum(categories) == wall`` identity EXACT in
integer nanoseconds, twice (determinism), plus the fleet-merge identity
with straggler-attributed idle.

All passes run by default.  ``--lint-only``/``--hlo-only`` keep their
pre-pass-3 meaning; ``--shardflow``/``--reshard``/``--memory``/
``--ledger`` select exactly the named legs (combinable).  Passes 2 and 3 share ONE
lowering per audited program (``build_audit_programs``), so enabling the
new legs does not re-lower the 20-program matrix; ``--programs`` filters
the matrix by substring so a builder can iterate on one program.

Exit status: 0 when clean, 1 when any finding fired — the CI gate.
``--metrics-dir`` additionally emits every finding (and, when the memory
leg ran, one ``graftcheck_memory`` record per program) as
schema-versioned JSONL through the obs spine, validated on the way out
so a schema drift fails THIS run, not a later reader.  ``--json`` prints
the machine report, including per-pass wall time under ``timing_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_PASSES = ("lint", "ledger", "shardflow", "hlo", "reshard", "memory")


def _setup_cpu_mesh(n: int = 8) -> None:
    """Force the simulated n-device CPU mesh BEFORE any computation
    (the config API works after ``import jax``, env vars do not)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    from pytorch_distributed_training_tpu.compat import set_cpu_device_count

    set_cpu_device_count(n)


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftcheck", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--root", default=_REPO_ROOT,
                        help="repo root the lint paths resolve against")
    parser.add_argument("--paths", nargs="*", default=None,
                        help="lint targets (files/dirs, relative to "
                             "--root); default: the project sources")
    parser.add_argument("--lint-only", action="store_true",
                        help="run only the AST lint pass")
    parser.add_argument("--hlo-only", action="store_true",
                        help="run only the compiled-artifact audit")
    parser.add_argument("--shardflow", action="store_true",
                        help="run only the sharding-coverage leg "
                             "(combinable with --reshard/--memory)")
    parser.add_argument("--reshard", action="store_true",
                        help="run only the resharding census "
                             "(combinable with --shardflow/--memory)")
    parser.add_argument("--memory", action="store_true",
                        help="run only the HBM memory audit "
                             "(combinable with --shardflow/--reshard)")
    parser.add_argument("--ledger", action="store_true",
                        help="run only the goodput-ledger audit "
                             "(scripted fault trace; combinable with "
                             "the pass-3 flags)")
    parser.add_argument("--modes", nargs="*", default=None,
                        help="train legs to audit: grad-sync modes "
                             "and/or 'zero1' (default: all six modes + "
                             "the zero1 leg)")
    parser.add_argument("--tp", type=int, default=2,
                        help="TP submesh size for the serving audit")
    parser.add_argument("--programs", nargs="*", default=None,
                        help="substring filter on audited program names "
                             "(e.g. 'serve/contig' or 'train/step-flat') "
                             "— passes 2/3 lower only the matches")
    parser.add_argument("--hbm-tol", type=float, default=None,
                        help="relative tolerance for the HBM peak-total "
                             "pin (default: analysis default)")
    parser.add_argument("--metrics-dir", default=None,
                        help="emit findings (and memory records) as "
                             "JSONL through the obs emitter")
    parser.add_argument("--json", action="store_true",
                        help="print a machine-readable report to stdout")
    args = parser.parse_args(argv)

    only_flags = {
        "lint": args.lint_only, "hlo": args.hlo_only,
        "shardflow": args.shardflow, "reshard": args.reshard,
        "memory": args.memory, "ledger": args.ledger,
    }
    exclusive = [p for p in ("lint", "hlo") if only_flags[p]]
    pass3 = [
        p for p in ("shardflow", "reshard", "memory", "ledger")
        if only_flags[p]
    ]
    if len(exclusive) > 1 or (exclusive and pass3):
        parser.error(
            "--lint-only / --hlo-only / the pass-3 flags are mutually "
            "exclusive (pass-3 flags combine only with each other)"
        )
    if exclusive:
        selected = set(exclusive)
    elif pass3:
        selected = set(pass3)
    else:
        selected = set(ALL_PASSES)

    from pytorch_distributed_training_tpu.analysis import (
        finding_record, lint_paths, memory_record,
        validate_finding_records, validate_memory_records,
    )
    from pytorch_distributed_training_tpu.analysis.lint import (
        DEFAULT_LINT_TARGETS, iter_python_files,
    )

    findings = []
    report: dict = {}
    timing: dict[str, float] = {}
    mem_records: list[dict] = []

    if "lint" in selected:
        t0 = time.perf_counter()
        lint_findings = lint_paths(args.paths, root=args.root)
        timing["lint"] = round(time.perf_counter() - t0, 3)
        findings += lint_findings
        report["lint"] = {
            "files_checked": len(iter_python_files(
                args.paths or DEFAULT_LINT_TARGETS, args.root,
            )),
            "findings": len(lint_findings),
        }

    if "ledger" in selected:
        from pytorch_distributed_training_tpu.analysis.ledger_audit import (
            run_ledger_audit,
        )

        t0 = time.perf_counter()
        f, r = run_ledger_audit()
        timing["ledger"] = round(time.perf_counter() - t0, 3)
        findings += f
        report["ledger"] = r

    if selected & {"shardflow", "hlo", "reshard", "memory"}:
        _setup_cpu_mesh()

    if "shardflow" in selected:
        from pytorch_distributed_training_tpu.analysis.shardflow import (
            run_shardflow_audit,
        )

        t0 = time.perf_counter()
        f, r = run_shardflow_audit(tp=args.tp)
        timing["shardflow"] = round(time.perf_counter() - t0, 3)
        findings += f
        report["shardflow"] = r

    programs = None
    if selected & {"hlo", "reshard", "memory"}:
        from pytorch_distributed_training_tpu.analysis.hlo_audit import (
            GRAD_SYNC_MODES, build_audit_programs,
        )

        if args.modes is None:
            modes, zero1 = GRAD_SYNC_MODES, True
        else:
            # "zero1" rides --modes as a pseudo-mode so the flag bounds
            # the WHOLE train matrix: --modes flat audits flat alone.
            zero1 = "zero1" in args.modes
            modes = [m for m in args.modes if m != "zero1"]
        t0 = time.perf_counter()
        programs = build_audit_programs(
            modes=modes, tp=args.tp, zero1=zero1,
            programs=args.programs,
        )
        timing["lower"] = round(time.perf_counter() - t0, 3)
        if args.programs and not programs:
            parser.error(
                f"--programs {' '.join(args.programs)} matched no "
                "audited program (names look like 'train/step-flat' or "
                "'serve/contig/decode')"
            )
        report["programs"] = {
            name: round(p.lower_s, 3) for name, p in programs.items()
        }

    if "hlo" in selected:
        from pytorch_distributed_training_tpu.analysis.hlo_audit import (
            run_hlo_audit,
        )

        t0 = time.perf_counter()
        hlo_findings, hlo_report = run_hlo_audit(programs=programs)
        timing["hlo"] = round(time.perf_counter() - t0, 3)
        findings += hlo_findings
        report["hlo"] = hlo_report

    if "reshard" in selected:
        from pytorch_distributed_training_tpu.analysis.reshard_audit import (
            run_reshard_audit,
        )

        t0 = time.perf_counter()
        f, r = run_reshard_audit(programs)
        timing["reshard"] = round(time.perf_counter() - t0, 3)
        findings += f
        report["reshard"] = r

    if "memory" in selected:
        from pytorch_distributed_training_tpu.analysis.reshard_audit import (
            DEFAULT_HBM_TOL, run_memory_audit,
        )

        t0 = time.perf_counter()
        f, r = run_memory_audit(
            programs,
            tol=args.hbm_tol if args.hbm_tol is not None
            else DEFAULT_HBM_TOL,
        )
        timing["memory"] = round(time.perf_counter() - t0, 3)
        findings += f
        report["memory"] = r
        mem_records = [
            memory_record(
                name, entry["measured"], entry["model"],
                measured_total=entry.get("measured_total"),
                total_rel_err=entry.get("total_rel_err"),
            )
            for name, entry in r.items()
            if entry.get("measured") is not None
        ]

    report["timing_s"] = timing

    records = [finding_record(f) for f in findings]
    validate_finding_records(records)  # schema gate on the EMITTING side
    validate_memory_records(mem_records)

    if args.metrics_dir:
        from pytorch_distributed_training_tpu.obs import MetricsEmitter

        with MetricsEmitter(
            args.metrics_dir, rank=0, world=1,
            meta={"tool": "graftcheck"},
        ) as em:
            for rec in records + mem_records:
                em.emit("record", rec)
            em.summary(
                graftcheck_findings=len(records),
                graftcheck_clean=not records,
                graftcheck_memory_programs=len(mem_records),
            )

    if args.json:
        print(json.dumps({
            "findings": records, "report": report,
        }, indent=2, default=str))
    else:
        for f in findings:
            print(f.format())
        by_pass: dict[str, int] = {}
        for f in findings:
            by_pass[f.analysis_pass] = by_pass.get(f.analysis_pass, 0) + 1
        breakdown = ", ".join(
            f"{p}={by_pass.get(p, 0)}" for p in ALL_PASSES if p in selected
        )
        print(
            f"graftcheck: {len(findings)} finding(s)"
            + (f" ({breakdown})" if len(selected) > 1 else "")
            + (" — clean" if not findings else "")
        )
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
