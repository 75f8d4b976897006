"""Host path: milliseconds per point inside the benchmark's ``simulate``
span (``simulator.sweep_traces``: scheduling, stacking, dispatch and
post-processing) during which no operation runs on the device, averaged
over the chips.
"""
from bench import tracing

SPANS = ("bench.simulate",)


def read(ctx):
    red = ctx.red
    return tracing.idle_ms_per_point(
        red, [iv for name in SPANS for iv in red.spans_named(name)],
        ctx.n_points)
