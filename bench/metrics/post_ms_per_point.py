"""Host path, post-processing: milliseconds per point inside the program's
``repro.sweep.post`` spans (a group's host copy, numpy cuts,
``_results_from_counters_batch``) during which no operation runs on the
device, averaged over the chips.
"""
from bench import tracing

SPAN = "repro.sweep.post"


def read(ctx):
    return tracing.idle_ms_per_point(ctx.red, ctx.red.prog_named(SPAN),
                                     ctx.n_points)
