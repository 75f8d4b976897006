"""Host path, trace stacking: milliseconds per point inside the program's
``repro.sweep.stack`` spans (``sweep_traces``: no-op padding and channel
concatenation) during which no operation runs on the device, averaged
over the chips.
"""
from bench import tracing

SPAN = "repro.sweep.stack"


def read(ctx):
    return tracing.idle_ms_per_point(ctx.red, ctx.red.prog_named(SPAN),
                                     ctx.n_points)
