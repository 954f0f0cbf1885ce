"""Benchmark harness: one module per experimental axis of the paper.

Prints ``name,us_per_call,derived`` CSV (one row per measurement) and
checks the paper's qualitative claims hold quantitatively:

  * ClassAd matchmaking scales (columnar/kernel >= 10x interpreter @10k ads),
  * LDIF->ClassAd conversion is cheap (§6),
  * history-based selection beats blind/static selection (§3.2),
  * the adaptive predictor has bounded regret vs the per-trace best (§7),
  * the information plane's TTL caching pays (§3.1),
  * the data plane survives failover/straggler injection,
  * striped+hedged TransferPlan execution holds <=1.5x fault-free wall
    time under a mid-transfer kill + 4x degrade, where the legacy
    single-source read fails outright.

Usage: PYTHONPATH=src python -m benchmarks.run [--only <prefix>] [--json [PATH]]

``--json`` additionally writes the rows + claim checks to
``BENCH_matchmaking.json`` (or PATH) so the perf trajectory accumulates
run over run instead of living only in CI logs.
"""

import argparse
import gc
import json
import sys
import time
import traceback


def main() -> None:
    # Allocation-heavy benches otherwise measure CPython's collector more
    # than the code under test: once jax is imported, its XLA gc callback
    # runs on EVERY collection (~170µs each), and the default 700-alloc
    # gen0 threshold fires one per ~17 converted LDIF entries. Rarer
    # collections, identical semantics.
    gc.set_threshold(100_000, 50, 50)
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run only benches whose module name contains this")
    ap.add_argument(
        "--json",
        nargs="?",
        const="BENCH_matchmaking.json",
        default=None,
        metavar="PATH",
        help="write rows + checks as JSON (default: BENCH_matchmaking.json)",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        metavar="PATH",
        help="write the bench results as an obs metrics registry snapshot "
             "(JSON + Prometheus exposition)",
    )
    ap.add_argument(
        "--trace-out",
        default=None,
        metavar="PATH",
        help="write a Chrome trace (one span per bench module; Perfetto)",
    )
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    from . import (
        bench_analysis,
        bench_gris,
        bench_kernels,
        bench_matchmaking,
        bench_pipeline,
        bench_predictors,
        bench_selection_quality,
        bench_transfer,
    )

    modules = {
        "matchmaking": bench_matchmaking,
        "selection_quality": bench_selection_quality,
        "predictors": bench_predictors,
        "gris": bench_gris,
        "pipeline": bench_pipeline,
        "kernels": bench_kernels,
        "transfer": bench_transfer,
        "analysis": bench_analysis,
    }

    from repro.obs import Tracer

    tracer = Tracer()
    rows = []
    failures = []
    for name, mod in modules.items():
        if args.only and args.only not in name:
            continue
        try:
            with tracer.span(f"bench.{name}"):
                rows.extend(mod.run())
        except Exception as e:  # pragma: no cover
            failures.append((name, e))
            traceback.print_exc()

    print("name,us_per_call,derived")
    derived = {}
    for name, us, d in rows:
        derived[name] = d
        print(f"{name},{us:.2f},{d:.4f}")

    # ---- claim checks (reported on stderr; nonzero exit on inversions) ----
    checks = []
    if "match_speedup_steady_vs_interp_s10000" in derived:
        checks.append(("steady-state columnar >=10x interpreter @10k ads",
                       derived["match_speedup_steady_vs_interp_s10000"] >= 10))
    if "selection_gain_predicted_vs_random" in derived:
        checks.append(("history-based selection beats random",
                       derived["selection_gain_predicted_vs_random"] >= 1.0))
    if "gris_ttl_cache_speedup" in derived:
        checks.append(("GRIS TTL caching pays", derived["gris_ttl_cache_speedup"] >= 1.0))
    for trace in ("diurnal", "noisy_stationary", "regime_shift"):
        k = f"pred_adaptive_regret_{trace}"
        if k in derived:
            checks.append((f"adaptive regret bounded ({trace})", derived[k] <= 1.5))
    if "pipeline_failovers" in derived:
        checks.append(("pipeline survives endpoint death", derived["pipeline_failovers"] >= 0))
    if "transfer_fault_inflation" in derived:
        checks.append(("striped+hedged read <=1.5x fault-free time under kill+degrade",
                       derived["transfer_fault_inflation"] <= 1.5))
    if "transfer_legacy_fails_under_kill" in derived:
        checks.append(("legacy single-source read dies where striped read survives",
                       derived["transfer_legacy_fails_under_kill"] == 1.0))
    if "transfer_striped_vs_single_speedup" in derived:
        checks.append(("striping over comparable replicas beats single-source",
                       derived["transfer_striped_vs_single_speedup"] >= 1.0))
    if "analysis_select_overhead" in derived:
        checks.append(("broker ad_check adds <5% latency to select()",
                       derived["analysis_select_overhead"] <= 1.05))
    if "analysis_check_ad" in derived:
        checks.append(("ad analyzer checks >=1k ads/sec",
                       derived["analysis_check_ad"] >= 1000))
    if "gris_ldif_entries_per_sec" in derived:
        checks.append(("LDIF->ClassAd ingest >=100k entries/sec",
                       derived["gris_ldif_entries_per_sec"] >= 100_000))

    bad = [c for c, ok in checks if not ok]
    for c, ok in checks:
        print(f"# CHECK {'PASS' if ok else 'FAIL'}: {c}", file=sys.stderr)

    if args.json:
        payload = {
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "only": args.only,
            "rows": [
                {"name": name, "us_per_call": round(us, 2), "derived": d}
                for name, us, d in rows
            ],
            "checks": [{"name": c, "pass": bool(ok)} for c, ok in checks],
            "failures": [name for name, _ in failures],
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)

    if args.metrics_out:
        from repro.obs import MetricsRegistry

        reg = MetricsRegistry(max_label_sets=1024)
        for name, us, d in rows:
            reg.gauge("bench_us_per_call", "microseconds per call",
                      bench=name).set(us)
            reg.gauge("bench_derived", "bench-specific derived figure",
                      bench=name).set(d)
        for c, ok in checks:
            reg.gauge("bench_check_pass", "1 if the paper-claim check held",
                      check=c).set(1.0 if ok else 0.0)
        reg.dump_json(args.metrics_out, extra={"only": args.only})
        print(f"# wrote {args.metrics_out}", file=sys.stderr)

    if args.trace_out:
        tracer.dump_json(args.trace_out)
        print(f"# wrote {args.trace_out}", file=sys.stderr)

    if failures or bad:
        sys.exit(1)


if __name__ == "__main__":
    main()
