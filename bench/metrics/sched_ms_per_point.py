"""Host scheduling: milliseconds per point inside the program's
``repro.sched.schedule`` spans (``core/sched/policies.schedule``: the
FR-FCFS walk, or FCFS's identity order) during which no operation runs
on the device, averaged over the chips.
"""
from bench import tracing

SPAN = "repro.sched.schedule"


def read(ctx):
    return tracing.idle_ms_per_point(ctx.red, ctx.red.prog_named(SPAN),
                                     ctx.n_points)
