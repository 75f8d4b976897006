"""Host path: milliseconds per point inside the benchmark's ``simulate``
span (``simulator.sweep_traces``: scheduling, stacking, dispatch and
post-processing) during which no operation runs on the device.
"""
from bench import tracing

SPANS = ("bench.simulate",)


def read(ctx):
    red = ctx.red
    if not red.devices or ctx.n_points <= 0:
        return None
    spans = tracing.union(tracing.clip(
        [iv for name in SPANS for iv in red.spans_named(name)], red.window))
    if not spans:
        return None
    idle = sum(tracing.subtract(spans, red.busy(d)) for d in red.devices)
    return idle / len(red.devices) * 1e-6 / ctx.n_points
