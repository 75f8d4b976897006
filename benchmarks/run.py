"""Benchmark entrypoint — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (derived = the headline metric the
paper reports for that figure).  ``--quick`` shrinks every trace for CI
smoke runs; ``--only a,b`` restricts to a comma-separated subset of names.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small traces for CI smoke runs")
    ap.add_argument("--only", default="",
                    help="comma-separated benchmark names to run")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (common, fig03_footprint, fig07_single_core,
                            fig08_eight_core, fig09_cache_hit,
                            fig10_row_hit, fig11_energy, fig12_capacity,
                            fig13_segment_size, fig14_replacement,
                            fig15_insertion, fig16_scheduler,
                            fig17_scenarios, fig_tail_latency, overhead,
                            sweep_engine)

    if args.quick:
        common.set_quick()

    benches = [
        ("fig03_footprint", fig03_footprint,
         lambda s: s.get("oracle/visit_leq2")),
        ("fig07_single_core", fig07_single_core,
         lambda s: s.get("intensive/figcache_fast")),
        ("fig08_eight_core", fig08_eight_core,
         lambda s: s.get("avg/figcache_fast")),
        ("fig09_cache_hit", fig09_cache_hit,
         lambda s: s.get("100%/figcache_fast")),
        ("fig10_row_hit", fig10_row_hit,
         lambda s: s.get("100%/figcache_fast")),
        ("fig11_energy", fig11_energy,
         lambda s: s.get("100%/figcache_fast/dram")),
        ("fig12_capacity", fig12_capacity, lambda s: s.get("FS=2")),
        ("fig13_segment_size", fig13_segment_size, lambda s: s.get("seg=16")),
        ("fig14_replacement", fig14_replacement,
         lambda s: s.get("row_benefit")),
        ("fig15_insertion", fig15_insertion, lambda s: s.get("th=1")),
        ("fig16_scheduler", fig16_scheduler,
         lambda s: s.get("frfcfs_qd16")),
        ("fig17_scenarios", fig17_scenarios,
         lambda s: s.get("embed/figcache_fast")),
        ("fig_tail_latency", fig_tail_latency,
         lambda s: (f"p99_gain={s['p99_gain_mean']}x "
                    f"zipf={s.get('zipf_reuse/p99_gain')}")),
        ("sweep_engine", sweep_engine,
         lambda s: (f"jits {s['jits_before']}->{s['jits_after']} "
                    f"cap={s['jits_capacity']} seg={s['jits_segment']} "
                    f"hotloop={s['hotloop_speedup']}x "
                    f"wavefront={s['wavefront_speedup']}x "
                    f"tracegen={s['tracegen_speedup']}x")),
        ("overhead_table", overhead,
         lambda s: s.get("fts_kB_per_channel")),
    ]
    only = {n for n in args.only.split(",") if n}
    known = {n for n, _, _ in benches}
    unknown = only - known
    if unknown:
        ap.error(f"unknown benchmark(s) {sorted(unknown)}; "
                 f"choose from {sorted(known)}")
    print("name,us_per_call,derived")
    details = {}
    for name, mod, pick in benches:
        if only and name not in only:
            continue
        t0 = time.time()
        rows, summary = mod.run()
        us = (time.time() - t0) * 1e6
        print(f"{name},{us:.0f},{pick(summary)}", flush=True)
        details[name] = summary
    print("\n# summaries", file=sys.stderr)
    for k, v in details.items():
        print(k, v, file=sys.stderr)


if __name__ == '__main__':
    main()
