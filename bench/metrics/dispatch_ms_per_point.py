"""Host path, group dispatch: milliseconds per point inside the program's
``repro.sweep.dispatch`` spans (``MechParams`` stacking and the scan's
dispatch, one span per static group) during which no operation runs on
the device, averaged over the chips.
"""
from bench import tracing

SPAN = "repro.sweep.dispatch"


def read(ctx):
    return tracing.idle_ms_per_point(ctx.red, ctx.red.prog_named(SPAN),
                                     ctx.n_points)
