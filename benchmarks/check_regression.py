"""CI benchmark regression gate.

Compares freshly-emitted ``BENCH_*.json`` from the smoke run against the
committed baselines under ``results/`` and **fails** when a headline metric
regresses beyond the tolerance::

    PYTHONPATH=src python -m benchmarks.run --smoke --seed 0 --json-dir fresh
    PYTHONPATH=src python -m benchmarks.check_regression --fresh fresh

Only *ratio* metrics gate (speedups, amortization factors): absolute
``us_per_call`` numbers are machine-dependent and meaningless across
runners, but a speedup is a same-machine A/B and survives slow hardware.
The default tolerance (30%) absorbs shared-runner noise; the smoke run's
``--seed 0`` makes the workload itself identical to the baseline run.

Re-baselining (intentional, e.g. after a perf-characteristics change)::

    PYTHONPATH=src python -m benchmarks.run --smoke --seed 0 --json-dir results
    git add results/BENCH_*.json   # commit with a note on what moved & why

``--self-test`` verifies the gate end to end without a benchmark run: it
checks the committed baselines pass against themselves, then injects a
synthetic regression (one headline degraded to 2x the tolerance) and
asserts the gate trips.  CI runs it after the real comparison, so "the gate
demonstrably fails on an injected regression" is re-proven on every build.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

# (bench, row name, derived-field[, tolerance override]) — all
# higher-is-better ratios.  A row listed here must exist in the fresh smoke
# output: a vanished benchmark is itself a regression the gate must notice.
# The serve speedups get a wide tolerance: their sequential denominator is a
# 64-dispatch host loop whose wall clock swings ~2x under shared-runner
# load, and their real failure mode is collapse to ~1x (batching broken) —
# which a 0.85 tolerance still catches; the absolute >= 3x acceptance bar
# is asserted machine-independently inside bench_serve itself.
HEADLINES: List[Tuple] = [
    ("maintenance", "fig19_batched_delete_100_edges", "batched_vs_looped"),
    # deferred-vs-exact whole-workload ratio: bench_maintenance_scaling
    # asserts >= 1.0 machine-independently; the gate tracks the margin
    ("maintenance", "fig19_deferred_workload", "deferred_workload_ratio"),
    ("wildcard", "wildcard_1hop_compact", "speedup_vs_arena"),
    ("plan_cache", "plan_cache_overhead_warm", "cold_over_warm"),
    ("plan_cache", "plan_cache_query_warm_e2e", "e2e_speedup"),
    ("predicate", "predicate_pushdown_src", "speedup"),
    ("predicate", "predicate_view_answered", "speedup"),
    ("serve", "serve_point_group", "speedup_vs_sequential", 0.85),
    ("serve", "serve_identical_group", "speedup_vs_sequential", 0.85),
    # mixed replay: both numerator and denominator are multi-second wall
    # clocks over hundreds of dispatches — the widest load band; collapse
    # to ~1x (scheduler batching broken) still trips a 0.6 tolerance
    ("serve", "serve_mixed_workload", "speedup_vs_sequential", 0.6),
    # online selection: both sides are multi-second same-machine wall
    # clocks; bench_online additionally asserts the absolute bars
    # (build_fused_speedup >= 3x, auto table5 ratio > 1.0) on every run
    ("online", "online_build_fused", "build_fused_speedup", 0.5),
    ("online", "online_table5_auto_snb", "W_ori/(MV+W_opt)", 0.5),
    # view-fed GNN epoch loop: maintained-view sampling vs per-epoch
    # re-extraction.  bench_gnn asserts the absolute bars on every run
    # (view_vs_reextract >= 3x, vec_vs_loop >= 2x); the gate tracks margin
    ("gnn", "gnn_sampled_epoch", "view_vs_reextract", 0.5),
    # deep-lane only (workloads is not a smoke bench): gated when the
    # fresh run includes it, skipped when BENCH_workloads.json is absent
    ("workloads", "table5_snb_workload", "W_ori/(MV+W_opt)", 0.5),
    ("workloads", "table3_fused_view_creation_snb_ROOT_POST", "speedup",
     0.5),
]


def _parse_derived(derived: str) -> Dict[str, str]:
    out = {}
    for part in derived.split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            out[k] = v
    return out


def load_metrics(json_dir: str) -> Dict[Tuple[str, str, str], float]:
    """Extract every headline metric present under ``json_dir``."""
    out: Dict[Tuple[str, str, str], float] = {}
    for bench, row_name, field in (h[:3] for h in HEADLINES):
        path = os.path.join(json_dir, f"BENCH_{bench}.json")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            doc = json.load(f)
        for row in doc.get("rows", []):
            if row.get("name") != row_name:
                continue
            val = _parse_derived(row.get("derived", "")).get(field)
            if val is not None:
                out[(bench, row_name, field)] = float(val)
    return out


def compare(fresh: Dict, baseline: Dict, tolerance: float,
            fresh_benches: Optional[set] = None
            ) -> Tuple[List[str], List[str]]:
    """Returns (failures, report_lines).  ``fresh_benches`` is the set of
    bench names present in the fresh run; headlines for a bench that was
    not run at all (e.g. deep-lane ``workloads`` during a smoke run) are
    skipped rather than failed — a missing *row* within a bench that did
    run still fails."""
    failures: List[str] = []
    lines: List[str] = []
    for entry in HEADLINES:
        key = entry[:3]
        tol = entry[3] if len(entry) > 3 else tolerance
        bench, row_name, field = key
        base = baseline.get(key)
        new = fresh.get(key)
        label = f"{row_name}.{field}"
        if base is None:
            lines.append(f"  SKIP {label}: no committed baseline "
                         f"(new benchmark? re-baseline to start gating)")
            continue
        if fresh_benches is not None and bench not in fresh_benches:
            lines.append(f"  SKIP {label}: bench '{bench}' not part of "
                         f"this run")
            continue
        if new is None:
            failures.append(f"{label}: metric missing from fresh run "
                            f"(baseline {base:.2f})")
            lines.append(f"  FAIL {label}: missing (baseline {base:.2f})")
            continue
        floor = base * (1.0 - tol)
        ok = new >= floor
        lines.append(f"  {'ok  ' if ok else 'FAIL'} {label}: "
                     f"{new:.2f} vs baseline {base:.2f} "
                     f"(floor {floor:.2f})")
        if not ok:
            failures.append(
                f"{label}: {new:.2f} regressed below {floor:.2f} "
                f"(baseline {base:.2f}, tolerance {tol:.0%})")
    return failures, lines


def self_test(baseline: Dict, tolerance: float) -> int:
    """Prove the gate passes on identity and trips on a planted regression."""
    if not baseline:
        print("self-test: no baselines found — nothing to prove", flush=True)
        return 1
    failures, _ = compare(copy.copy(baseline), baseline, tolerance)
    if failures:
        print("self-test FAILED: baseline does not pass against itself:")
        for f in failures:
            print(f"  {f}")
        return 1
    injected = copy.copy(baseline)
    victim = sorted(injected)[0]
    victim_tol = next((e[3] for e in HEADLINES
                       if e[:3] == victim and len(e) > 3), tolerance)
    injected[victim] = baseline[victim] * max(1.0 - 2.0 * victim_tol, 0.0)
    failures, _ = compare(injected, baseline, tolerance)
    if not failures:
        print(f"self-test FAILED: gate did not trip on injected regression "
              f"of {victim}")
        return 1
    print(f"self-test ok: identity passes; injected regression of "
          f"{victim[1]}.{victim[2]} "
          f"({baseline[victim]:.2f} -> {injected[victim]:.2f}) trips the "
          f"gate as required:")
    for f in failures:
        print(f"  {f}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fresh", type=str, default="fresh",
                    help="directory with freshly-emitted BENCH_*.json")
    ap.add_argument("--baseline", type=str, default="results",
                    help="directory with committed baseline BENCH_*.json")
    ap.add_argument("--tolerance", type=float, default=0.30,
                    help="allowed fractional regression (runner noise)")
    ap.add_argument("--self-test", action="store_true",
                    help="verify the gate trips on an injected regression")
    args = ap.parse_args(argv)

    baseline = load_metrics(args.baseline)
    if args.self_test:
        return self_test(baseline, args.tolerance)

    fresh = load_metrics(args.fresh)
    fresh_benches = {h[0] for h in HEADLINES
                     if os.path.exists(os.path.join(
                         args.fresh, f"BENCH_{h[0]}.json"))}
    failures, lines = compare(fresh, baseline, args.tolerance,
                              fresh_benches=fresh_benches)
    print(f"benchmark regression gate: {args.fresh} vs {args.baseline} "
          f"(tolerance {args.tolerance:.0%})")
    for line in lines:
        print(line)
    if failures:
        print(f"\nGATE FAILED — {len(failures)} regressed headline "
              f"metric(s):")
        for f in failures:
            print(f"  {f}")
        return 1
    print("\ngate passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
