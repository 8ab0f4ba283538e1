"""Benchmark harness — one entry per paper table/figure (+ ours).

Prints ``name,us_per_call,derived`` CSV lines. Each module also asserts
the paper's qualitative claims mechanically (a failed claim fails the
harness). Every run's per-benchmark wall-clock summary is appended to
``BENCH_history.jsonl`` (``benchmarks.history``) so the trajectory
survives across runs; pass ``--check-regression`` to fail any benchmark
whose timings got >25% slower than its previous same-backend entry
(the first run of a benchmark seeds its baseline).

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--check-regression]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks.history import check_regression, record

BENCHES = [
    ("merge_loss", "paper Fig. 6/7 — loss before/after cooperative update"),
    ("rocauc_grid", "paper Figs. 8-17 — ROC-AUC vs BP-NN baselines"),
    ("latency", "paper Table 4 — train/predict/merge latencies"),
    ("convergence", "paper Fig. 18 — merge vs sequential training"),
    ("mesh_merge", "ours — psum cooperative update on a device mesh"),
    ("fleet_scale", "ours — fleet simulator: devices × topology grid"),
    ("serve_runtime", "ours — resident runtime soak: drift detection + gated merges"),
    ("paper_eval", "paper §5 — scenario grid vs BP-NN / FedAvg at matched rounds"),
    ("fleet_ingest", "ours — fused tick ingest vs vmap+scan baseline"),
    ("kernel_bench", "ours — Pallas kernel micro-bench (interpret)"),
    ("ablation_hidden", "ours — detector width ablation (accuracy vs payload)"),
    ("robust_fleet", "ours — Byzantine-robust merges + fault-injection chaos soak"),
    ("serve_ingress", "ours — async serving front-end chaos-under-load soak"),
    ("fleet_cohort", "ours — cohort-paged arena runtime at 10⁵–10⁶ devices"),
    ("roofline_report", "ours — dry-run roofline artifact summary"),
]


def _line_metrics(lines: list[str]) -> dict[str, float]:
    """us_per_call per CSV line, keyed ``<line name>_us`` — the
    wall-clock summary the history trajectory tracks."""
    metrics: dict[str, float] = {}
    for line in lines:
        parts = line.split(",")
        if len(parts) < 2 or line.startswith("#"):
            continue
        try:
            us = float(parts[1])
        except ValueError:
            continue
        if us == us:  # NaN entries (accounting-only rows) don't gate
            metrics[f"{parts[0]}_us"] = us
    return metrics


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--history", default="BENCH_history.jsonl")
    ap.add_argument(
        "--check-regression", action="store_true",
        help="fail a benchmark whose wall-clock regressed >25%% vs its "
             "previous history entry",
    )
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    names = [name for name, _ in BENCHES]
    if args.only and args.only not in names:
        # a typo'd --only used to filter everything out and exit 0 —
        # a "green" run that measured nothing
        ap.error(
            f"--only {args.only!r}: unknown benchmark "
            f"(choose from: {', '.join(names)})"
        )

    print("name,us_per_call,derived")
    failures = []
    for mod_name, desc in BENCHES:
        if args.only and args.only != mod_name:
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
            lines = list(mod.main())
            for line in lines:
                print(line, flush=True)
            metrics = _line_metrics(lines)
            # seconds key: informational, not regression-gated (only
            # *_us keys gate; harness wall time includes compile noise)
            metrics["harness_wall_seconds"] = time.time() - t0
            # "run." namespace keeps harness summaries separate from a
            # module's own richer history entries (e.g. fleet_ingest)
            prev = record(f"run.{mod_name}", metrics, path=args.history)
            if args.check_regression:
                regressions = check_regression(prev, metrics)
                if regressions:
                    raise AssertionError(
                        f"{mod_name} wall-clock regression: " + "; ".join(regressions)
                    )
            print(f"# {mod_name} ok in {time.time()-t0:.1f}s — {desc}", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(mod_name)
            print(f"# {mod_name} FAILED — {desc}", flush=True)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)


if __name__ == "__main__":
    main()
