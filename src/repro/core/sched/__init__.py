"""Memory-controller scheduling subsystem (DESIGN.md §10).

Two halves on top of the bank/bus/MSHR model of ``core/dram.py``:

 * ``policies`` — per-bank request queues with pluggable disciplines
   (FCFS, FR-FCFS row-hit-first with a starvation cap, write-drain
   batching), realized as trace-preprocessing permutations computed on
   the device and keyed by ``timing.SchedConfig``, so a whole controller
   grid replays through one compiled scan.
 * ``wavefront`` — bank-parallel execution: a compile pass groups the
   (scheduled) trace into distinct-bank waves and one ``lax.scan`` step
   retires a whole wave, vmapping the serial scan's own per-request
   decision function and resolving the shared bus/MSHR state with an
   in-wave ordered prefix.  Bitwise-equal to the serial fused scan under
   FCFS (``tests/test_sched.py``).
"""
from repro.core.sched.policies import (SCHED_FCFS, SchedConfig, frfcfs_perm,
                                       schedule, write_drain_perm)
from repro.core.sched.wavefront import (form_waves, make_wave_step,
                                        run_channel_waves, run_sweep_waves,
                                        simulate_waves, wave_stats)

__all__ = [
    "SCHED_FCFS", "SchedConfig", "schedule", "frfcfs_perm",
    "write_drain_perm", "form_waves", "make_wave_step", "run_channel_waves",
    "run_sweep_waves", "simulate_waves", "wave_stats",
]
