"""Compile-contract checker: declarative jit budgets for the entry points.

A ``Contract`` names one compiled entry point, the *representative grid*
that exercises it, the compile counter that observes it
(``dram.jit_trace_count`` / ``workload.gen_trace_count``), and the maximum
number of fresh compilations the grid is allowed to cost.  The declaration
also records which keys are ALLOWED to recompile (the static-arg set) —
the reviewable statement of the StaticConfig/MechParams split for that
entry.

This generalizes the one-off asserts that used to live inline in
``benchmarks/sweep_engine.py``: the benchmark now imports its grids and
budgets from here (``TIMINGS_GRID``/``CAPACITY_GRID``/``SEGMENT_GRID``,
``assert_jit_budget``), so the benchmark and the analyzer cannot drift
apart, and every future entry point (wavefront variants, whole-step Pallas
scan, sharded sweeps) declares a contract once and inherits the gate in
the CLI, in CI, and in the pytest fixture (``tests/test_analysis.py``).

Budgets are *maxima*: an observed 0 means a same-shape dispatch earlier in
the process already compiled the program, which is the guarantee in an
even stronger form.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import findings as F

# ---------------------------------------------------------------------------
# the shared grids (single source of truth; sweep_engine imports these)

# 8 configs, one static structure: threshold x benefit_bits grid
TIMINGS_GRID = [dict(insert_threshold=th, benefit_bits=bb)
                for th in (1, 2, 4, 8) for bb in (4, 5)]
# fig 12 / fig 13 knobs — distinct grid sizes so each traces separately
CAPACITY_GRID = [dict(cache_rows=cr) for cr in (2, 4, 8, 16, 32, 64)]
SEGMENT_GRID = [dict(seg_blocks=sb) for sb in (8, 16, 32, 64, 128)]


@dataclasses.dataclass(frozen=True)
class Contract:
    """One entry point's compile budget.

    ``run`` executes the representative grid and returns the number of
    fresh compilations it cost (measured by the entry's own compile log).
    ``static_args`` documents the keys that are *allowed* to trigger a
    recompile; anything else recompiling is a bug this contract catches.
    """
    name: str
    description: str
    max_jits: int
    static_args: Tuple[str, ...]
    run: Callable[[], int]


REGISTRY: Dict[str, Contract] = {}


def contract(name: str, description: str, max_jits: int,
             static_args: Tuple[str, ...]):
    def deco(fn):
        REGISTRY[name] = Contract(name, description, max_jits,
                                  static_args, fn)
        return fn
    return deco


def assert_jit_budget(name: str, observed: int) -> None:
    """The benchmark-side gate: observed fresh compilations against the
    declared budget (AssertionError text carries the contract)."""
    c = REGISTRY[name]
    assert observed <= c.max_jits, (
        f"compile contract `{name}` violated: {observed} fresh "
        f"compilation(s) > budget {c.max_jits} "
        f"(allowed recompile keys: {', '.join(c.static_args)}) — "
        f"{c.description}")


# ---------------------------------------------------------------------------
# representative inputs (small on purpose: contracts gate compile COUNTS,
# not performance, so a 256-request trace proves the same property as 1M)

@functools.lru_cache(maxsize=None)
def _toy_trace():
    from repro.core import workload
    spec = workload.preset("zipf_reuse", n_cores=2, n_channels=1,
                           per_channel=256, seed=3)
    tr = workload.generate(spec)
    return jax.tree.map(lambda a: a[0], tr)   # (C, T) -> (T,)


def _stack_params(cfgs):
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[c.params() for c in cfgs])


def _grid_jits(grid_kw) -> int:
    from repro.core import dram
    from repro.core.timing import paper_config, shared_static
    cfgs = [paper_config("figcache_fast", **kw) for kw in grid_kw]
    static = shared_static(cfgs)
    tr = _toy_trace()
    j0 = dram.jit_trace_count()
    jax.block_until_ready(dram.run_sweep(tr, static, _stack_params(cfgs)))
    return dram.jit_trace_count() - j0


# ---------------------------------------------------------------------------
# the contracts

@contract("sweep.timings",
          "insert_threshold x benefit_bits grid batches into one compiled "
          "scan (pure MechParams knobs)", 1,
          ("StaticConfig", "trace/batch shapes"))
def _c_timings() -> int:
    return _grid_jits(TIMINGS_GRID)


@contract("sweep.capacity",
          "fig 12 cache-capacity grid (cache_rows 2..64) shares one padded "
          "FTS structure: one compiled scan for the whole grid", 1,
          ("StaticConfig", "trace/batch shapes"))
def _c_capacity() -> int:
    return _grid_jits(CAPACITY_GRID)


@contract("sweep.segment",
          "fig 13 segment-size grid (seg_blocks 8..128) shares one padded "
          "FTS structure: one compiled scan for the whole grid", 1,
          ("StaticConfig", "trace/batch shapes"))
def _c_segment() -> int:
    return _grid_jits(SEGMENT_GRID)


@contract("sweep.warm-cache",
          "re-dispatching an already-compiled grid costs zero fresh "
          "compilations: traced MechParams values are NOT recompile keys",
          0, ("StaticConfig", "trace/batch shapes"))
def _c_warm() -> int:
    _grid_jits(CAPACITY_GRID)          # warm (budgeted by sweep.capacity)
    return _grid_jits(CAPACITY_GRID)   # measured: must be pure cache hits


@contract("simulator.sweep_traces",
          "W workloads x N configs of one static structure run as one "
          "compiled scan (ragged traces no-op padded, specs generated on "
          "device)", 1,
          ("StaticConfig", "sched policy", "padded trace shape"))
def _c_sweep_traces() -> int:
    from repro.core import dram, simulator, workload
    specs = [workload.preset("zipf_reuse", n_cores=2, n_channels=1,
                             per_channel=n, seed=s)
             for n, s in ((192, 1), (256, 2))]
    from repro.core.timing import paper_config
    cfgs = [paper_config("figcache_fast", insert_threshold=th)
            for th in (1, 4)]
    j0 = dram.jit_trace_count()
    simulator.sweep_traces(specs, cfgs)
    return dram.jit_trace_count() - j0


@contract("streaming.chunked-replay",
          "a chunked streamed replay reuses ONE compiled segment step for "
          "every chunk: SimState out is structurally SimState in, so all "
          "same-shape segments hit the same cache entry (DESIGN.md §13)", 1,
          ("StaticConfig", "segment shape"))
def _c_chunked_replay() -> int:
    from repro.core import dram, streaming
    from repro.core.timing import paper_config
    cfg = paper_config("figcache_fast")
    tr = _toy_trace()                      # (256,) -> 4 chunks of 64
    j0 = dram.jit_trace_count()
    jax.block_until_ready(streaming.simulate_stream(
        streaming.iter_chunks(tr, 64), cfg))
    return dram.jit_trace_count() - j0


@contract("orchestrator.shard-sweep",
          "a sharded orchestrated sweep dispatches each shard through the "
          "ONE compiled segment step its (static, sched) group owns: a "
          "whole shard — checkpoints, resume, mesh placement included — "
          "costs at most one fresh compilation (DESIGN.md §14)", 1,
          ("StaticConfig", "sched policy", "segment/batch shapes"))
def _c_shard_sweep() -> int:
    import tempfile
    from repro.core import dram, workload
    from repro.core.timing import paper_config
    from repro.launch import orchestrator
    specs = [workload.preset("zipf_reuse", n_cores=2, n_channels=2,
                             per_channel=192, seed=9)]
    cfgs = [paper_config("figcache_fast", cache_rows=cr) for cr in (16, 32)]
    plan = orchestrator.make_plan(specs, cfgs, chunk_len=64)
    j0 = dram.jit_trace_count()
    with tempfile.TemporaryDirectory() as d:
        orchestrator.Orchestrator(plan, d, backoff_s=0.0).run()
    return dram.jit_trace_count() - j0


@contract("obs.telemetry-sweep",
          "a telemetry-enabled capacity sweep streams chunked through ONE "
          "compiled telemetry step: the TelemetryWindows carry extension "
          "and the per-step frame outputs do not split the compilation "
          "cache across chunks or grid points (DESIGN.md §15)", 1,
          ("StaticConfig (incl. telemetry period)", "segment/batch shapes"))
def _c_telemetry_sweep() -> int:
    import dataclasses
    from repro.core import dram, streaming
    from repro.core.timing import paper_config, shared_static
    from repro.obs.telemetry import WindowCollector
    cfgs = [dataclasses.replace(paper_config("figcache_fast", **kw),
                                telemetry=64) for kw in CAPACITY_GRID]
    static = shared_static(cfgs)
    tr = _toy_trace()
    col = WindowCollector()
    j0 = dram.jit_trace_count()
    jax.block_until_ready(streaming.sweep_stream(
        streaming.iter_chunks(tr, 64), static, _stack_params(cfgs),
        telemetry=col))
    assert col.n_segments == 4 and len(col.series(index=(0,))["win_idx"])
    return dram.jit_trace_count() - j0


@contract("obs.tail-latency",
          "the §16 latency-distribution path — histogram planes in the "
          "telemetry carry, chunked collection, and host-side percentile/"
          "SLO extraction — costs ONE compiled telemetry step for a whole "
          "SLO-threshold grid: slo_ns is traced (MechParams), so threshold "
          "sweeps batch instead of recompiling, and percentile extraction "
          "is pure host numpy (no extra programs)", 1,
          ("StaticConfig (incl. telemetry period)", "segment/batch shapes"))
def _c_tail_latency() -> int:
    import dataclasses
    import numpy as np
    from repro.core import dram, streaming
    from repro.core.timing import paper_config, shared_static
    from repro.obs import latency
    from repro.obs.telemetry import WindowCollector
    cfgs = [dataclasses.replace(paper_config("figcache_fast"),
                                telemetry=64, slo_ns=slo)
            for slo in (50, 100, 200, 400)]
    static = shared_static(cfgs)
    tr = _toy_trace()
    col = WindowCollector()
    j0 = dram.jit_trace_count()
    jax.block_until_ready(streaming.sweep_stream(
        streaming.iter_chunks(tr, 64), static, _stack_params(cfgs),
        telemetry=col))
    for p, cfg in enumerate(cfgs):
        cum = col.cumulative(index=(p,))
        pct = latency.percentiles(cum["hist"].sum(axis=(0, 1)))
        assert np.isfinite(pct["p99"].value)
        s = col.series(index=(p,))
        assert int(s["w_slo"].sum()) == int(cum["slo"].sum())
    return dram.jit_trace_count() - j0


@contract("workload.generate_many",
          "a workload grid sharing one generator structure synthesizes as "
          "ONE vmapped compiled call", 1,
          ("family", "n_cores x n_channels x per_channel shape"))
def _c_generate_many() -> int:
    from repro.core import workload
    specs = [workload.preset("zipf_reuse", n_cores=2, n_channels=1,
                             per_channel=320, seed=s) for s in (5, 6, 7)]
    g0 = workload.gen_trace_count()
    workload.generate_many(specs)
    return workload.gen_trace_count() - g0


# ---------------------------------------------------------------------------
# the pass

def check_contract(name: str) -> List[F.Finding]:
    c = REGISTRY[name]
    try:
        observed = c.run()
    except Exception as e:    # noqa: BLE001 - a crashing grid IS a finding
        return [F.Finding(
            rule="compile-contract", entry=name,
            message=f"representative grid failed to run: "
                    f"{type(e).__name__}: {e}")]
    if observed > c.max_jits:
        return [F.Finding(
            rule="compile-contract", entry=name,
            message=f"{observed} fresh compilation(s) > budget "
                    f"{c.max_jits}; allowed recompile keys are "
                    f"{', '.join(c.static_args)} — {c.description}")]
    return []


def check_all(names: Optional[List[str]] = None) -> F.Report:
    rep = F.Report(passes=["compile-contracts"])
    for name in (names if names is not None else list(REGISTRY)):
        rep.scanned.append(name)
        rep.extend(check_contract(name))
    return rep


CHECKS = {"compile-contract":
          "entry point exceeded its declared fresh-compilation budget"}
