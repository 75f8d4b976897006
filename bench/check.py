"""The comparison that decides ``correct``.

After the window has closed, one campaign point drawn from the run's seed
is recomputed in full by the plain reference under ``bench/reference/``
(every mix, every configuration, every channel) and set against what the
timed path produced for it:

* ``failed``: results of the whole window whose counters are unhealthy or
  did not retire exactly their mix's real requests (``cell.failures``);
* ``trace_mismatch``: requests of the point's synthesized traces (every
  field, every channel, every mix) that differ from the reference
  generator's;
* ``counter_mismatch``: counter elements (every leaf, every channel, every
  mix and configuration) that differ from the reference simulator's, which
  serves the reference controller's order;
* ``result_gap``: the widest relative gap of a derived number (IPC,
  latency, hit rates, execution time, energy) against the reference's.

The first three are exact comparisons, with the limit 0.  The fourth's
limit sits between the sound runs' readings (rounding of float64 sums)
and the control's (``PERF.md``).
"""
from __future__ import annotations

import random

import numpy as np

from bench.reference import dram as ref_dram
from bench.reference import gen as ref_gen
from bench.reference import post as ref_post
from bench.reference import sched as ref_sched

LIMITS = {"failed": 0, "trace_mismatch": 0, "counter_mismatch": 0,
          "result_gap": 1e-9}
FIELDS = ("t_issue", "bank", "row", "col", "is_write", "core")


def draw(seed: int, n_points: int) -> int:
    """The index of the point that the reference recomputes."""
    return random.Random(seed).randrange(n_points)


def run_reference(system: dict, traffic: dict, cfgs: list, mix: dict,
                  tr: dict, break_bus: bool = False) -> list:
    """The reference's counters per channel and derived numbers for one
    mix's trace ``tr`` under each configuration of ``cfgs``: a list of
    ``(channels, numbers)``.  Each channel's service order is computed
    once, since no configuration changes it."""
    n_banks = system["geometry"]["n_banks"]
    served = []
    for c in range(tr["t_issue"].shape[0]):
        ch = {k: tr[k][c].tolist() for k in FIELDS}
        order = ref_sched.service_order(ch["t_issue"], ch["bank"], ch["row"],
                                        ch["is_write"], traffic["controller"],
                                        n_banks)
        served.append([[ch[k][i] for i in order] for k in FIELDS])
    out = []
    for cfg in cfgs:
        m = ref_dram.Mech(cfg, system)
        chans = [ref_dram.simulate_channel(m, *s, break_bus=break_bus)
                 for s in served]
        out.append((chans, ref_post.results(chans, mix["cores"],
                                            m.has_cache)))
    return out


def compare(trace, counters, numbers, ref_trace, ref_chans, ref_numbers):
    """Mismatch counts and the widest relative gap of one (mix,
    configuration).  ``trace`` is the program's trace of the mix (host
    arrays by field, or None to leave it out), ``counters`` its counters
    for the configuration (host arrays, leading channel axis), ``numbers``
    its derived numbers."""
    trace_bad = 0
    if trace is not None:
        trace_bad = int(np.sum(np.any(np.stack(
            [np.asarray(trace[k]) != ref_trace[k] for k in ref_trace]), 0)))
    cnt_bad = 0
    for k in ref_dram.COUNTERS:
        want = np.array([c[k] for c in ref_chans])
        got = np.asarray(counters[k])
        cnt_bad += int(np.sum(got != want)) if got.shape == want.shape \
            else want.size
    gap = 0.0
    for k in ref_post.NUMBERS:
        want = np.atleast_1d(np.asarray(ref_numbers[k], np.float64))
        got = np.atleast_1d(np.asarray(numbers[k], np.float64))
        if got.shape != want.shape or not np.all(np.isfinite(got)):
            return trace_bad, cnt_bad, float("inf")
        rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
        gap = max(gap, float(np.max(np.where(got == want, 0.0, rel))))
    return trace_bad, cnt_bad, gap


def verdict(readings: dict) -> bool:
    """Every reading within its limit."""
    return all(v <= LIMITS[k] for k, v in readings.items())
