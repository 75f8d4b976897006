"""Trace synthesis: device milliseconds of the generator program per point.

Reads the executions of the workload generator (``core/workload/
generators.py``: ``jit(gen)`` or ``jit(vmap(gen))``, module ``jit_gen``)
on the device plane of the window's trace.
"""
PROGRAMS = ("jit_gen",)


def read(ctx):
    t = ctx.red.module_s(lambda n: n.split("(")[0] in PROGRAMS)
    if t <= 0 or ctx.n_points <= 0:
        return None
    return t * 1e3 / ctx.n_points
