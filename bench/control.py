"""The control of ``bench/check.py``: the reference with one guarantee of the
configuration broken, put in the program's place.

    python bench/control.py --workload <cell> --seed <n> [--seed <n> ...]

The broken guarantee is the channel's data bus: the control lets 64 B
bursts overlap instead of serializing them.  For each seed it computes
the first point of a run with that seed (every mix under every
configuration) with the reference and with the control at the cell's own
size, and prints the numbers ``check`` compares, beside their limits.
Every seed has to come out as not correct.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def readings(cell, seed: int) -> dict:
    """The control's readings for point 0 of a run with this seed: every
    mix under every configuration."""
    import numpy as np
    from bench import cell as C
    from bench import check
    t = cell.traffic
    cfgs = cell.config["configs"]
    got = {"trace_mismatch": 0, "counter_mismatch": 0, "result_gap": 0.0}
    for mi, mix in enumerate(t["mixes"]):
        ref_trace = check.ref_gen.generate(mix["cores"], t["n_channels"],
                                           t["per_channel"],
                                           C.point_seed(seed, 0, mi))
        refs = check.run_reference(cell.config, t, cfgs, mix, ref_trace)
        bad = check.run_reference(cell.config, t, cfgs, mix, ref_trace,
                                  break_bus=True)
        for (chans, nums), (bad_chans, bad_nums) in zip(refs, bad):
            counters = {k: np.array([c[k] for c in bad_chans])
                        for k in check.ref_dram.COUNTERS}
            tb, cb, gap = check.compare(ref_trace, counters, bad_nums,
                                        ref_trace, chans, nums)
            got["trace_mismatch"] += tb
            got["counter_mismatch"] += cb
            got["result_gap"] = max(got["result_gap"], gap)
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import check
    from bench.cell import Cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = Cell.load(json.load(f), args.workload)
    for seed in args.seed:
        got = readings(cell, seed)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": check.verdict(got), "readings": got,
                          "limits": check.LIMITS}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
