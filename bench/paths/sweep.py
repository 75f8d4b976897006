"""The sweep path: a point through ``simulator.sweep_traces`` on JAX's
default device.

Every mix of the point is synthesized in one call
(``workload.generate_many``, span ``bench.synthesize``), then every
configuration is simulated on every mix (``simulator.sweep_traces``, span
``bench.simulate``) until the derived numbers are on the host and the
counters are finished.  A traffic file without a ``path`` key runs here.
"""
import time

import jax

from bench import cell as C


def point(camp: C.Campaign, seed: int, index: int) -> C.Point:
    from repro.core import simulator, workload
    t0 = time.perf_counter()
    specs = camp.specs(seed, index)
    with jax.profiler.TraceAnnotation("bench.point"):
        with jax.profiler.TraceAnnotation("bench.synthesize"):
            traces = jax.block_until_ready(workload.generate_many(specs))
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.simulate"):
            res = simulator.sweep_traces(traces, camp.cfgs,
                                         [s.apps() for s in specs])
            jax.block_until_ready([r.counters for row in res for r in row])
    return C.Point(index, traces, res, t0, t1, time.perf_counter())
