"""Regression tests for the batched sweep engine (DESIGN.md §3) and the
simulator bugfixes that shipped with it: per-config vs stacked-batch bitwise
equivalence, insertion-tracker hit-path purity, the ``t_end >= done``
execution-time invariant, and zero-request robustness."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram, simulator, traces
from repro.core import fts as fts_lib
from repro.core.timing import (DDR4, GEOM, DRAMTimings, MechConfig,
                               MechParams, paper_config)

ALL_MECHS = ("base", "lisa_villa", "figcache_slow", "figcache_fast",
             "figcache_ideal", "lldram")


@functools.lru_cache(maxsize=None)
def _trace(n_reqs=2048, multi=False):
    a = traces.app_params("libquantum")
    if multi:
        apps = tuple(traces.app_params(n) for n in ("libquantum", "mcf"))
        return traces.build_trace(list(apps), 2, n_reqs, 3), apps
    tr = traces.build_trace([a], 1, n_reqs, 1)
    return jax.tree.map(lambda x: x[0], tr), (a,)


def _assert_counters_equal(ref: dram.Counters, got: dram.Counters, ctx):
    for name, x, y in zip(ref._fields, ref, got):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (ctx, name)


@pytest.mark.parametrize("mech", ALL_MECHS)
def test_run_sweep_matches_run_channel_bitwise(mech):
    """A stacked params batch must reproduce per-config runs exactly —
    varied thresholds, benefit widths and even DRAM timings in one batch."""
    tr, _ = _trace()
    slow = DRAMTimings(tRCD=16.25, tRP=15.0)   # a second timing corner
    variants = [(paper_config(mech), DDR4)]
    if mech != "base":
        variants += [
            (paper_config(mech, insert_threshold=3), DDR4),
            (paper_config(mech, benefit_bits=3), slow),
        ]
    static = variants[0][0].static
    assert all(c.static == static for c, _ in variants)
    batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[c.params(t) for c, t in variants])
    swept = dram.run_sweep(tr, static, batch)
    for i, (cfg, t) in enumerate(variants):
        ref = dram.run_channel(tr, cfg, t)
        got = jax.tree.map(lambda a, i=i: a[i], swept)
        _assert_counters_equal(ref, got, (mech, i))


def test_run_sweep_multi_channel():
    tr, _ = _trace(multi=True)
    cfgs = [paper_config("figcache_fast", insert_threshold=th)
            for th in (1, 2, 4)]
    batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[c.params() for c in cfgs])
    swept = dram.run_sweep(tr, cfgs[0].static, batch)
    assert np.asarray(swept.reads).shape[:2] == (3, 2)   # (P, C)
    for i, cfg in enumerate(cfgs):
        ref = dram.run_channels(tr, cfg)
        got = jax.tree.map(lambda a, i=i: a[i], swept)
        _assert_counters_equal(ref, got, ("multi", i))


def test_sweep_traces_matches_per_workload_sweep():
    """Cross-workload stacking (figs 7/8 path): results[w][i] must equal a
    plain per-workload ``sweep`` bit for bit — counters, IPC and energy —
    for single-channel AND multi-channel traces, across several statics."""
    cfgs = [paper_config("base"),
            paper_config("figcache_fast"),
            paper_config("figcache_fast", insert_threshold=2),
            paper_config("lisa_villa")]
    a1 = (traces.app_params("libquantum"),)
    a2 = (traces.app_params("mcf"),)
    single = [(jax.tree.map(lambda x: x[0],
                            traces.build_trace(list(a), 1, 1024, s)), a)
              for a, s in ((a1, 1), (a2, 2), (a1, 3))]
    multi_apps = tuple(traces.app_params(n) for n in ("libquantum", "mcf"))
    multi = [(traces.build_trace(list(multi_apps), 2, 1024, s), multi_apps)
             for s in (4, 5)]
    for label, group in (("single", single), ("multi", multi)):
        trs = [t for t, _ in group]
        apps_list = [a for _, a in group]
        res = simulator.sweep_traces(trs, cfgs, apps_list)
        for w, (tr, apps) in enumerate(group):
            ref = simulator.sweep(tr, cfgs, apps)
            for i in range(len(cfgs)):
                _assert_counters_equal(ref[i].counters, res[w][i].counters,
                                       (label, w, i))
                assert np.array_equal(ref[i].ipc, res[w][i].ipc)
                assert ref[i].system_energy_nj == res[w][i].system_energy_nj
                assert ref[i].exec_time_ns == res[w][i].exec_time_ns


def test_simulator_sweep_matches_run_mechanism():
    """Grouped dispatch (several static structures in one grid) must agree
    with the one-config-at-a-time path, in input order."""
    tr, apps = _trace(multi=True)
    cfgs = [paper_config("base"),
            paper_config("figcache_fast", insert_threshold=4),
            paper_config("lisa_villa"),
            paper_config("figcache_fast")]
    res = simulator.sweep(tr, cfgs, apps)
    assert [r.mechanism for r in res] == [c.mechanism for c in cfgs]
    for cfg, r in zip(cfgs, res):
        ref = simulator.run_mechanism(tr, cfg, apps)
        _assert_counters_equal(ref.counters, r.counters, cfg)
        assert np.allclose(ref.ipc, r.ipc)
        assert ref.system_energy_nj == r.system_energy_nj


@pytest.mark.parametrize("entry", ("sweep", "sweep_traces"))
@pytest.mark.parametrize("multi", (True, False), ids=("multi", "single"))
def test_results_are_host_numpy_and_match_run_mechanism(entry, multi):
    """Post-processing hands back host ``np.ndarray`` counters, each leaf
    shaped and typed like the per-config scan's output, and every result
    bitwise equal to per-config ``run_mechanism``: counters, IPC, latency,
    hit rates, execution time and energy."""
    cfgs = [paper_config("base"),
            paper_config("figcache_fast"),
            paper_config("figcache_fast", insert_threshold=2)]
    tr, apps = _trace(n_reqs=1024, multi=multi)
    if entry == "sweep":
        got = [(tr, simulator.sweep(tr, cfgs, apps))]
    else:
        tr2 = traces.build_trace(list(apps), 2 if multi else 1, 768, 9)
        if not multi:
            tr2 = jax.tree.map(lambda x: x[0], tr2)
        res = simulator.sweep_traces([tr, tr2], cfgs, [apps, apps])
        got = list(zip((tr, tr2), res))
    for w, (t, row) in enumerate(got):
        for cfg, r in zip(cfgs, row):
            ref = simulator.run_mechanism(t, cfg, apps)
            scan = (dram.run_channels if multi else dram.run_channel)(t, cfg)
            for name, x, y, z in zip(scan._fields, scan, ref.counters,
                                     r.counters):
                ctx = (w, cfg.mechanism, name)
                assert type(y) is np.ndarray and type(z) is np.ndarray, ctx
                assert z.shape == x.shape and z.dtype == x.dtype, ctx
                assert np.array_equal(x, z) and np.array_equal(y, z), ctx
            assert np.array_equal(ref.ipc, r.ipc)
            assert np.array_equal(ref.avg_lat_ns, r.avg_lat_ns)
            for k in ("row_hit_rate", "cache_hit_rate", "exec_time_ns",
                      "dram_energy_nj", "system_energy_nj", "energy_parts"):
                assert getattr(ref, k) == getattr(r, k), (w, cfg, k)


def _mini_trace(n, bank_of, row_of, col_of, core_of=lambda i: 0,
                t_issue=lambda i: 0):
    idx = range(n)
    return dram.Trace(
        t_issue=jnp.array([t_issue(i) for i in idx], jnp.int32),
        bank=jnp.array([bank_of(i) for i in idx], jnp.int32),
        row=jnp.array([row_of(i) for i in idx], jnp.int32),
        col=jnp.array([col_of(i) for i in idx], jnp.int32),
        is_write=jnp.zeros((n,), bool),
        core=jnp.array([core_of(i) for i in idx], jnp.int32),
    )


def _final_state(trace, cfg: MechConfig) -> dram.BankState:
    static = cfg.static
    step = dram.make_step(static)
    # telemetry lane is None when static.telemetry == 0 (DESIGN.md §15)
    carry0 = (dram.init_state(static), dram.init_counters(), None)
    (state, _, _), _ = jax.lax.scan(
        functools.partial(step, cfg.params()), carry0, trace)
    return state


def test_insertion_tracker_pure_on_hits():
    """Cache hits must not advance the consecutive-miss tracker: with
    threshold=2, segment A misses twice (cnt->2, inserted) and then hits many
    times — its tracked count must still read 2 afterwards."""
    cfg = paper_config("figcache_fast", insert_threshold=2)
    n_track = 256
    seg = 5 * cfg.segs_per_row        # row 5, col 0 => seg id 40
    trace = _mini_trace(10, bank_of=lambda i: 0, row_of=lambda i: 5,
                        col_of=lambda i: 0, t_issue=lambda i: i * 4096)
    state = _final_state(trace, cfg)
    fts0 = jax.tree.map(lambda a: a[0], state.fts)
    idx = seg % n_track
    assert int(fts0.miss_tags[idx]) == seg
    # 2 misses then 8 hits: a hit-mutating tracker would read 10 here
    assert int(fts0.miss_cnt[idx]) == 2
    hit, _ = fts_lib.lookup(fts0, jnp.int32(seg))
    assert bool(hit)


def test_t_end_covers_bus_serialized_bursts():
    """Execution time must cover the shared-bus drain: K simultaneous
    requests to K different banks finish their *bank* work quickly, but the
    channel bus serializes K bursts — t_end >= K * tBL."""
    K = 12
    trace = _mini_trace(K, bank_of=lambda i: i, row_of=lambda i: 100 + i,
                        col_of=lambda i: 0, core_of=lambda i: i % GEOM.n_cores)
    cnt = dram.run_channel(trace, paper_config("base"))
    assert int(cnt.t_end) >= K * DDR4.bl
    # and it still covers the bank-side busy window (reloc etc.)
    assert int(cnt.t_end) >= DDR4.rcd + DDR4.ccd


def test_run_mechanism_zero_requests():
    """All-idle cores (empty trace) must not crash ``max(times)`` and must
    report zero execution time / neutral rates."""
    empty = _mini_trace(0, bank_of=lambda i: 0, row_of=lambda i: 0,
                        col_of=lambda i: 0)
    apps = (traces.app_params("libquantum"),)
    res = simulator.run_mechanism(empty, paper_config("figcache_fast"), apps)
    assert res.exec_time_ns == 0.0
    assert res.row_hit_rate == 0.0 and res.cache_hit_rate == 0.0
    assert np.allclose(res.ipc, 1.0 / simulator.CPI_EXEC)


def test_per_core_latency_returns_tuple():
    cnt = dram.init_counters()
    out = simulator._per_core_latency(cnt)
    assert isinstance(out, tuple) and len(out) == 2
    lat, req = out
    assert isinstance(lat, np.ndarray) and isinstance(req, np.ndarray)


def test_one_compile_per_static_structure():
    """Re-dispatching new params batches through ``run_sweep`` must not
    retrace: the jit count is a function of distinct static structures (and
    trace shapes) only."""
    tr, _ = _trace()
    cfgs = [paper_config("figcache_fast", insert_threshold=th)
            for th in (1, 2)]
    static = cfgs[0].static
    batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[c.params() for c in cfgs])
    dram.run_sweep(tr, static, batch)            # warm (may trace)
    before = dram.jit_trace_count()
    other = jax.tree.map(lambda *xs: jnp.stack(xs), *[
        paper_config("figcache_fast", insert_threshold=th).params()
        for th in (4, 8)])
    dram.run_sweep(tr, static, other)            # same static: no retrace
    assert dram.jit_trace_count() == before
