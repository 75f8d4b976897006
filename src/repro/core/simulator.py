"""Top-level FIGCache system simulator: six mechanisms, perf + energy metrics.

Performance model (DESIGN.md §7): the trace replaces Pin, and per-core IPC is
derived from the simulated average memory latency with an MLP-weighted
latency-to-CPI conversion:

    cycles_c = I_c * CPI_exec + N_c * L_c(cycles) / MLP_c
    I_c      = N_c * 1000 / MPKI_c

Single-core results report IPC speedup vs Base; multiprogrammed results report
weighted speedup (paper §7, [133]).  Every mechanism sees the *same* trace, so
speedups isolate the memory system exactly as in the paper.

Sweeps (DESIGN.md §3): ``sweep`` takes an arbitrary list of ``MechConfig``
points, groups them by their ``StaticConfig`` (mechanism/policy + padded FTS
allocation — capacity and segment-size no longer split groups), and
dispatches each group as ONE ``dram.run_sweep`` call — a single compiled
scan vmapped over the stacked dynamic params.  ``sweep_traces`` additionally
stacks W traces along the (independent) channel axis — unequal lengths are
no-op-padded (``dram.noop_pad``, DESIGN.md §9) — so a whole workloads x
configs cross product runs per static structure as one program.
Post-processing copies each static group's stacked counters to the host
once (one ``jax.device_get`` of every leaf) and makes every later cut —
per workload, per config — as numpy indexing, so ``RunResult.counters``
holds host ``np.ndarray`` leaves and no eager device program runs after
the scan; the IPC/energy model is vectorized over the params axis
(``_results_from_counters_batch``) so very large grids do not pay a
Python-side loop for it.  ``run_single_core`` /
``run_eight_core`` are thin wrappers that sweep one config per mechanism;
``run_single_core_batch`` / ``run_eight_core_batch`` are their stacked-trace
counterparts (figs 7/8).

Host-path spans (DESIGN.md §15): ``sweep`` and ``sweep_traces`` mark each
phase of their host path with an ``obs.trace.span`` on the profiler's
clock — ``repro.sched.schedule`` (in ``sched_policies.schedule``, once
per controller), ``repro.sweep.stack`` (no-op padding and channel
stacking, once per call), ``repro.sweep.dispatch`` (once per static
group) and ``repro.sweep.post`` (once per group and workload) — siblings,
disjoint in time, whose stats are counted from shapes and Python values.

Workloads are first-class sweep axes too (DESIGN.md §11): ``sweep_traces``
accepts ``workload.WorkloadSpec`` entries and synthesizes those traces on
device (specs sharing a generator structure batch into one vmapped compiled
call), and ``run_scenario`` evaluates the paper mechanisms on one
device-generated scenario family.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dram, streaming, traces, workload
from repro.core.energy import ENERGY
from repro.core.sched import policies as sched_policies
from repro.core.timing import (DDR4, GEOM, DRAMTimings, MechConfig,
                               paper_config, shared_static, static_group_key)
from repro.obs.trace import span

CPU_GHZ = 3.2
CPI_EXEC = 0.4          # 3-wide OoO issue
MLP_INTENSIVE = 2.2     # 8 MSHRs/core, bursty misses overlap
MLP_NON = 1.4

PAPER_MECHS = ("base", "lisa_villa", "figcache_slow", "figcache_fast",
               "figcache_ideal", "lldram")

@dataclasses.dataclass
class RunResult:
    mechanism: str
    ipc: np.ndarray              # per-core
    avg_lat_ns: np.ndarray       # per-core
    row_hit_rate: float
    cache_hit_rate: float        # hits / lookups (cache mechanisms only)
    exec_time_ns: float
    dram_energy_nj: float
    system_energy_nj: float
    energy_parts: Dict[str, float]
    counters: object


def _per_core_latency(cnt) -> Tuple[np.ndarray, np.ndarray]:
    lat = np.asarray(cnt.lat_sum_ns, dtype=np.float64)
    req = np.asarray(cnt.req_cnt, dtype=np.float64)
    if lat.ndim == 2:            # (channels, cores) -> sum over channels
        lat, req = lat.sum(0), req.sum(0)
    return np.where(req > 0, lat / np.maximum(req, 1), 0.0), req


def _results_from_counters_batch(cnts, cfgs: Sequence[MechConfig],
                                 apps: Sequence, n_channels: int
                                 ) -> List[RunResult]:
    """Turn a stacked batch of host ``dram.Counters`` into ``RunResult``s.

    Counter leaves are host ``np.ndarray``s (callers ``jax.device_get``
    them first) carrying a leading params axis ``(P, ...)`` (P ==
    len(cfgs)); the MLP-weighted IPC model, execution time and the energy
    model all evaluate vectorized over that axis, so post-processing a
    large grid is a handful of numpy array ops instead of a Python loop.
    Each result's ``counters`` is its config's numpy view ``a[i, ...]``,
    shaped like the per-config scan's output.
    """
    P = len(cfgs)
    lat = np.asarray(cnts.lat_sum_ns, dtype=np.float64)  # (P, [C,] cores)
    req = np.asarray(cnts.req_cnt, dtype=np.float64)
    if lat.ndim == 3:                # multi-channel: sum over channels
        lat, req = lat.sum(1), req.sum(1)
    avg_lat = np.where(req > 0, lat / np.maximum(req, 1), 0.0)
    n_apps = len(apps)
    mpki = np.array([a.mpki for a in apps], dtype=np.float64)
    mlp = np.array([MLP_INTENSIVE if a.name in traces.INTENSIVE else MLP_NON
                    for a in apps], dtype=np.float64)
    r, al = req[:, :n_apps], avg_lat[:, :n_apps]          # (P, n_apps)
    instr = r * 1000.0 / mpki
    cycles = instr * CPI_EXEC + r * (al * CPU_GHZ) / mlp
    with np.errstate(divide="ignore", invalid="ignore"):
        ipc = np.where(r > 0, instr / cycles, 1.0 / CPI_EXEC)
    # exec time: slowest core (ns); 0 when no core issued any request
    exec_ns = np.where(r > 0, cycles / CPU_GHZ, 0.0).max(axis=1)
    instr_tot = instr.sum(axis=1)
    tot = lambda x: np.asarray(x, dtype=np.float64).reshape(P, -1).sum(axis=1)
    n_req = tot(cnts.reads) + tot(cnts.writes)
    parts = ENERGY.system_energy_nj_batch(cnts, n_channels, n_apps,
                                          instr_tot, exec_ns, tot)
    row_hits, cache_hits = tot(cnts.row_hits), tot(cnts.cache_hits)
    out = []
    for i, cfg in enumerate(cfgs):
        div = n_req[i] if n_req[i] else 1.0
        out.append(RunResult(
            mechanism=cfg.mechanism,
            ipc=ipc[i],
            avg_lat_ns=avg_lat[i],
            row_hit_rate=row_hits[i] / div,
            cache_hit_rate=cache_hits[i] / div if cfg.has_cache else 0.0,
            exec_time_ns=float(exec_ns[i]),
            dram_energy_nj=float(parts["dram_total"][i]),
            system_energy_nj=float(parts["system_total"][i]),
            energy_parts={k: float(v[i]) for k, v in parts.items()},
            # `...` keeps a 0-d leaf an ndarray, not a numpy scalar
            counters=jax.tree.map(lambda a, i=i: a[i, ...], cnts),
        ))
    return out


def _post_stats(n_cfgs: int, copies: bool) -> dict:
    """Stats of one ``repro.sweep.post`` span.  ``device_ops``: the device
    programs it launches, none since every cut is numpy indexing on host
    arrays; ``d2h_copies``: the counter leaves it copies to the host, all
    of them in a group's first post span (``copies``) and none after."""
    return {"configs": n_cfgs, "device_ops": 0,
            "d2h_copies": len(dram.Counters._fields) if copies else 0}


def _stack_params(cfgs: Sequence[MechConfig], idxs: Sequence[int],
                  t: DRAMTimings):
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[cfgs[i].params(t) for i in idxs])


def _result_from_counters(cnt, cfg: MechConfig, apps: Sequence,
                          n_channels: int) -> RunResult:
    """One config's ``Counters`` -> ``RunResult`` (one host copy, then a
    P=1 batch, so the scalar and swept paths share one arithmetic and
    agree to the last float)."""
    one = jax.tree.map(lambda a: np.asarray(a)[None], jax.device_get(cnt))
    return _results_from_counters_batch(one, [cfg], apps, n_channels)[0]


def run_mechanism(trace: dram.Trace, cfg: MechConfig,
                  apps: Sequence[traces.AppParams]) -> RunResult:
    trace = sched_policies.schedule(trace, cfg.sched)
    cnt = dram.run_channel(trace, cfg)
    shape = np.shape(trace.t_issue)
    n_channels = shape[0] if len(shape) == 2 else 1
    return _result_from_counters(cnt, cfg, apps, n_channels)


def _dispatch_sweep(trace: dram.Trace, static, batch,
                    chunk_len: int | None) -> dram.Counters:
    """One static group's compiled dispatch: the monolithic ``run_sweep``
    or — when ``chunk_len`` is set — the segment-carried streamed replay
    (DESIGN.md §13), which is bitwise-identical and bounds the device
    trace residency at O(chunk_len) regardless of trace length."""
    if chunk_len is None:
        return dram.run_sweep(trace, static, batch)
    return streaming.sweep_stream(
        streaming.iter_chunks(trace, chunk_len), static, batch)


def sweep(trace: dram.Trace, cfgs: Sequence[MechConfig],
          apps: Sequence[traces.AppParams],
          t: DRAMTimings = DDR4,
          chunk_len: int | None = None) -> List[RunResult]:
    """Run an arbitrary config grid with one compiled scan per static
    structure (DESIGN.md §3).

    Configs are grouped by ``timing.static_group_key`` plus their
    controller (``cfg.sched``, DESIGN.md §10) and bucketed to the group's
    tightest shared structure (``timing.shared_static``); each group's
    dynamic params are stacked and dispatched as one ``dram.run_sweep``
    call over the group's *scheduled* trace, so N configs cost one
    compilation per group instead of N — controller grids replay
    reordered copies of the trace through the same compiled scan.
    Results come back in input order and are bitwise-identical to
    per-config ``run_mechanism``.  ``chunk_len`` streams each group
    through the segment-carried scan instead (same results bitwise;
    DESIGN.md §13) for traces too long to replay monolithically.
    """
    multi = np.asarray(trace.t_issue).ndim == 2
    n_channels = np.asarray(trace.t_issue).shape[0] if multi else 1
    out: List[RunResult | None] = [None] * len(cfgs)
    scheduled: Dict[object, dram.Trace] = {}   # one call per controller
    for (static, sc), idxs in _static_groups(cfgs).items():
        if sc not in scheduled:
            scheduled[sc] = sched_policies.schedule(trace, sc)
        P = len(idxs)
        with span("repro.sweep.dispatch", mechanism=static.mechanism,
                  configs=P, lanes=P * n_channels):
            cnts = _dispatch_sweep(scheduled[sc], static,
                                   _stack_params(cfgs, idxs, t), chunk_len)
        with span("repro.sweep.post", **_post_stats(P, True)):
            results = _results_from_counters_batch(
                jax.device_get(cnts), [cfgs[i] for i in idxs], apps,
                n_channels)
        for j, i in enumerate(idxs):
            out[i] = results[j]
    return out


def static_groups(cfgs: Sequence[MechConfig]) -> Dict[object, List[int]]:
    """Group a config grid for batched dispatch: configs sharing a
    ``static_group_key`` (mechanism/policy/fts_kernel) AND a controller
    (``cfg.sched``) go to ONE group and the group's shared static is the
    *tightest* bucket covering its maximum FTS geometry
    (``timing.shared_static``).  A single-config group — e.g.
    ``run_single_core``'s one point per mechanism — therefore gets the
    small 512-slot bucket instead of the 1024-slot sweep ceiling.
    Controllers split the *dispatch* (each replays a differently-ordered
    trace) but never the *compilation*: scheduled traces keep the input
    shape, so every sched group of one static structure reuses one scan."""
    keyed: Dict[object, List[int]] = {}
    for i, cfg in enumerate(cfgs):
        keyed.setdefault((static_group_key(cfg), cfg.sched), []).append(i)
    return {(shared_static([cfgs[i] for i in idxs]), sc): idxs
            for (_, sc), idxs in keyed.items()}


# the grouping is public API now: the sweep orchestrator
# (launch/orchestrator.py, DESIGN.md §14) builds its durable work shards
# from exactly these compilation units
_static_groups = static_groups


def sweep_traces(trs: Sequence, cfgs: Sequence[MechConfig],
                 apps_list=None,
                 t: DRAMTimings = DDR4,
                 chunk_len: int | None = None) -> List[List[RunResult]]:
    """Cross-workload batching: W traces x N configs in one compiled scan
    per static structure (ROADMAP: collapse figs 7/8).

    Channels are fully independent in the model (each gets its own scan
    carry), so W workloads stack along the channel axis: (T,) traces stack
    to (W, T), (C, T) traces concatenate to (W*C, T), and the existing
    ``dram.run_sweep`` channel vmap does the rest.  Traces of *unequal
    length* are right-padded to the longest with no-op requests
    (``dram.noop_pad``: issue-time ``NOOP_ISSUE``, zero-latency retire, no
    state or counter effect) — the trace-axis analogue of the padded FTS —
    so arbitrary workload mixes batch; they must still agree on the channel
    count.  Returns ``results[w][i]`` for workload ``trs[w]`` under config
    ``cfgs[i]``, bitwise-equal to per-workload ``sweep`` calls.

    Entries of ``trs`` may also be ``workload.WorkloadSpec``s (DESIGN.md
    §11): those traces are synthesized *on device* — specs sharing a
    generator structure batch into one vmapped compiled call
    (``workload.generate_many``) — so a workload-grid x config-grid cross
    product runs without any host trace building.  ``apps_list`` may be
    omitted when every entry is a spec (each spec supplies its own
    ``apps()``); with mixed entries, pass ``None`` per spec position to
    use the spec's apps.

    Padding no-ops are a *suffix* here only by convention — interior
    no-ops (e.g. the chunk-tail fillers a codec-decoded stream carries)
    are equally counter-inert in every scan body
    (``tests/test_streaming.py`` pins this), and ``chunk_len`` streams
    the stacked workloads through the segment-carried scan exactly like
    ``sweep``'s.
    """
    trs = list(trs)
    assert trs, "need at least one workload"
    spec_idx = [i for i, x in enumerate(trs)
                if isinstance(x, workload.WorkloadSpec)]
    if apps_list is None:
        assert len(spec_idx) == len(trs), \
            "apps_list may be omitted only when every entry is a WorkloadSpec"
        apps_list = [None] * len(trs)
    apps_list = [trs[i].apps() if a is None else a
                 for i, a in enumerate(apps_list)]
    if spec_idx:
        gen = workload.generate_many([trs[i] for i in spec_idx])
        for i, tr in zip(spec_idx, gen):
            trs[i] = tr
    assert len(trs) == len(apps_list), "one apps tuple per trace"
    ndims = {np.asarray(tr.t_issue).ndim for tr in trs}
    assert len(ndims) == 1, f"traces must agree on channel layout: {ndims}"
    multi = np.asarray(trs[0].t_issue).ndim == 2
    if multi:
        chans = {np.asarray(tr.t_issue).shape[0] for tr in trs}
        assert len(chans) == 1, f"traces must share a channel count: {chans}"
    n_channels = np.asarray(trs[0].t_issue).shape[0] if multi else 1
    W = len(trs)
    t_max = max(np.asarray(tr.t_issue).shape[-1] for tr in trs)
    # no-op pad, then stack: no-ops stay a suffix of every lane, so one
    # schedule call per controller on the stack equals per-workload calls
    with span("repro.sweep.stack", workloads=W, trips=t_max):
        padded = [dram.noop_pad(tr, t_max) for tr in trs]
        join = jnp.concatenate if multi else jnp.stack
        flat = jax.tree.map(
            lambda *xs: join([jnp.asarray(x) for x in xs]), *padded)
    scheduled: Dict[object, dram.Trace] = {}   # one call per controller

    out: List[List[RunResult | None]] = [[None] * len(cfgs) for _ in range(W)]
    C = n_channels
    for (static, sc), idxs in _static_groups(cfgs).items():
        if sc not in scheduled:
            scheduled[sc] = sched_policies.schedule(flat, sc)
        P = len(idxs)
        with span("repro.sweep.dispatch", mechanism=static.mechanism,
                  configs=P, lanes=P * W * C):
            cnts = _dispatch_sweep(scheduled[sc], static,
                                   _stack_params(cfgs, idxs, t),
                                   chunk_len)  # (P, W*C, ...)
        for w in range(W):
            with span("repro.sweep.post", **_post_stats(P, w == 0)):
                if w == 0:
                    # the group's one host copy: every leaf's transfer
                    # starts before any is waited on (so this waits on
                    # the scan); all later cuts are numpy views
                    cnts = jax.device_get(cnts)
                # slice workload w back out; single-channel inputs also
                # drop the stacking axis so results are shaped exactly
                # like plain `sweep`
                if multi:
                    cnt_w = jax.tree.map(
                        lambda a, w=w: a[:, w * C:(w + 1) * C], cnts)
                else:
                    cnt_w = jax.tree.map(lambda a, w=w: a[:, w], cnts)
                results = _results_from_counters_batch(
                    cnt_w, [cfgs[i] for i in idxs], apps_list[w], C)
            for j, i in enumerate(idxs):
                out[w][i] = results[j]
    return out


def weighted_speedup(res: RunResult, base: RunResult) -> float:
    return float(np.sum(res.ipc / base.ipc))


def speedup(res: RunResult, base: RunResult) -> float:
    """Per-workload average speedup (normalized weighted speedup)."""
    return weighted_speedup(res, base) / len(base.ipc)


def mech_grid(mechanisms, cfg_overrides) -> List[MechConfig]:
    return [paper_config(m, **(cfg_overrides or {})) if m != "base"
            else paper_config(m) for m in mechanisms]


@functools.lru_cache(maxsize=None)
def _single_trace(app_name: str, n_reqs: int, seed: int):
    a = traces.app_params(app_name)
    return traces.build_trace([a], 1, n_reqs, seed), (a,)


def run_single_core(app_name: str, mechanisms=PAPER_MECHS, n_reqs: int = 24576,
                    seed: int = 1, cfg_overrides: dict | None = None
                    ) -> Dict[str, RunResult]:
    tr, apps = _single_trace(app_name, n_reqs, seed)
    res = sweep(tr, mech_grid(mechanisms, cfg_overrides), apps)
    return dict(zip(mechanisms, res))


def run_eight_core(workload, mechanisms=PAPER_MECHS, per_channel: int = 12288,
                   seed: int = 2, cfg_overrides: dict | None = None
                   ) -> Dict[str, RunResult]:
    name, frac, apps = workload
    tr = traces.build_trace(apps, 4, per_channel, seed)
    res = sweep(tr, mech_grid(mechanisms, cfg_overrides), apps)
    return dict(zip(mechanisms, res))


def run_single_core_batch(app_names: Sequence[str], mechanisms=PAPER_MECHS,
                          n_reqs: int = 24576, seed: int = 1,
                          cfg_overrides: dict | None = None
                          ) -> Dict[str, Dict[str, RunResult]]:
    """All of fig 7 in one dispatch: every app's trace stacked, every
    mechanism's params batched — one compiled scan per static structure
    covers the whole apps x mechanisms cross product (``sweep_traces``)."""
    pairs = [_single_trace(a, n_reqs, seed) for a in app_names]
    res = sweep_traces([p[0] for p in pairs],
                       mech_grid(mechanisms, cfg_overrides),
                       [p[1] for p in pairs])
    return {a: dict(zip(mechanisms, r)) for a, r in zip(app_names, res)}


def run_eight_core_batch(workloads, mechanisms=PAPER_MECHS,
                         per_channel: int = 12288, seed: int = 2,
                         cfg_overrides: dict | None = None
                         ) -> List[Dict[str, RunResult]]:
    """Stacked-trace counterpart of ``run_eight_core`` for fig 8: W
    multiprogrammed workloads run as one W*C-channel batch per structure."""
    trs = [traces.build_trace(apps, 4, per_channel, seed)
           for _, _, apps in workloads]
    res = sweep_traces(trs, mech_grid(mechanisms, cfg_overrides),
                       [apps for _, _, apps in workloads])
    return [dict(zip(mechanisms, r)) for r in res]


def run_scenario(spec: "workload.WorkloadSpec", mechanisms=PAPER_MECHS,
                 cfg_overrides: dict | None = None) -> Dict[str, RunResult]:
    """Evaluate the paper mechanisms on one device-generated scenario
    (DESIGN.md §11): the workload counterpart of ``run_single_core`` /
    ``run_eight_core``, with the trace synthesized on device."""
    res = sweep(workload.generate(spec), mech_grid(mechanisms,
                                                   cfg_overrides),
                spec.apps())
    return dict(zip(mechanisms, res))


def speedup_summary(results: Dict[str, RunResult]) -> Dict[str, float]:
    base = results["base"]
    return {m: weighted_speedup(r, base) / len(base.ipc)
            for m, r in results.items()}
