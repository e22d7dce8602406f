"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  table2_run              Table II (model performance comparison)
  indep_*                 §IV.E population-independent analysis
  clustering              Fig. 2 pre-training clustering
  aggregation_*           §II.D server aggregation efficiency
  sharded_store_*         sharded-store submit throughput (-> BENCH_sharded.json)
  multiproc_store_*       threaded-K vs process-K serving mix (-> BENCH_multiproc.json)
  privatize_* / secure_*  privacy subsystem overhead (-> BENCH_privacy.json)
  scenario_*              trace-driven scenario replays (-> BENCH_scenarios.json)
  fed_round_*             Algorithm 1 protocol round timing
  dryrun_*                harness §Roofline rows (if artifacts exist)

Environment knobs: REPRO_BENCH_FAST=1 shrinks the Table-II run for CI.
"""

from __future__ import annotations

import os

from repro.launch.device import use_compile_cache


def main() -> None:
    # worker processes the storms below start fold on the host CPU backend
    # (repro.launch.device), so they never contend for the chip this
    # process holds
    use_compile_cache()
    fast = os.environ.get("REPRO_BENCH_FAST", "0") == "1"
    rows: list[tuple] = []

    # ---- Table II + §IV.E ---------------------------------------------------
    from benchmarks import table2

    t2_kwargs = (dict(seeds=(0,), n_sites=6, n_days=40, rounds=2) if fast
                 else dict(seeds=(0, 1, 2), n_sites=9, n_days=60, rounds=3))
    res = table2.run(**t2_kwargs)
    table2.print_table(res)
    rows += table2.csv_rows(res)
    for col, d in res["independent"].items():
        rows.append((f"indep_{col}", 0.0,
                     f"degradation={d['degradation_pp']:+.2f}pp"))

    # ---- clustering (Fig. 2) ------------------------------------------------
    from benchmarks import clustering_report

    crep = clustering_report.run()
    rows += clustering_report.csv_rows(crep)

    # ---- aggregation efficiency (§II.D) ------------------------------------
    from benchmarks import aggregation_throughput

    sizes = (200_000, 2_000_000) if fast else (200_000, 2_000_000, 20_000_000)
    arep = aggregation_throughput.run(sizes=sizes)
    rows += aggregation_throughput.csv_rows(arep)

    # ---- privacy overhead (DP + secure aggregation) -------------------------
    from benchmarks import privacy_overhead

    pret = privacy_overhead.run(fast=fast)
    rows += privacy_overhead.csv_rows(pret)

    # ---- sharded store submit throughput (-> BENCH_sharded.json) ------------
    from benchmarks import sharded_store

    srep = sharded_store.run(fast=fast)
    rows += sharded_store.csv_rows(srep)

    # ---- multi-process server serving mix (-> BENCH_multiproc.json) ---------
    from benchmarks import multiproc_store

    mrep = multiproc_store.run(fast=fast)
    rows += multiproc_store.csv_rows(mrep)

    # ---- trace-driven scenarios (-> BENCH_scenarios.json) -------------------
    from benchmarks import scenarios

    screp = scenarios.run(fast=fast)
    rows += scenarios.csv_rows(screp)

    # ---- protocol round timing (Algorithm 1) --------------------------------
    from benchmarks import protocol_timing

    prep = protocol_timing.run(fast=fast)
    rows += protocol_timing.csv_rows(prep)

    # ---- continual-learning ablation (§II.E) --------------------------------
    from benchmarks import continual_ablation

    crep2 = continual_ablation.run(epochs_a=4 if fast else 8,
                                   epochs_b=4 if fast else 8)
    rows += continual_ablation.csv_rows(crep2)

    # ---- roofline table (if dry-run artifacts exist) ------------------------
    from benchmarks import roofline_report

    recs = roofline_report.load()
    if recs:
        roofline_report.print_table(recs)
        rows += roofline_report.csv_rows(recs)

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
