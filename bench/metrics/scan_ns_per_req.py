"""Scan step: device nanoseconds of the simulator's scan programs per
simulated request.

Reads the executions of ``dram.run_sweep`` and ``dram.run_sweep_segment``
(modules ``jit_run_sweep`` and ``jit_sweep_resume``) and divides by the
real requests the window simulated, summed over configurations and
channels.  Per request, so it reads the same work whichever engine
retires it.
"""
PROGRAMS = ("jit_run_sweep", "jit_sweep_resume")


def read(ctx):
    t = ctx.red.module_s(lambda n: n.split("(")[0] in PROGRAMS)
    if t <= 0 or ctx.sim_reqs <= 0:
        return None
    return t * 1e9 / ctx.sim_reqs
