"""Scheduler-subsystem regression tests (DESIGN.md §10).

Contracts:

 1. **Wavefront == serial fused scan (FCFS oracle).**  The bank-wavefront
    scan must be bitwise-equal to the serial fused scan across all six
    mechanisms x four replacement policies — on structured pressure
    traces, hypothesis-random traces, ragged no-op-padded traces, and
    multi-channel traces.  With ``lookahead > 0`` the oracle is the
    linearized wave order (same requests, per-bank FIFO preserved).
 2. **Wave formation invariants.**  Every wave's banks are distinct (pads
    take unused banks), per-bank FIFO order is preserved, at most
    ``N_MSHR`` same-core lanes per wave, and the linearization of a
    ``lookahead=0`` formation is exactly the input order.
 3. **Scheduling policies.**  ``schedule`` emits a permutation; FR-FCFS
    respects the starvation cap (replay-checked), degenerates to FCFS at
    ``starve_cap=0``, preserves per-(bank, row) FIFO order, and actually
    reorders a crafted row-conflict trace; write-drain defers writes in
    (bank, row)-sorted batches; the device service order equals the
    plain-Python oracle (``write_drain_perm`` + ``frfcfs_perm``) lane by
    lane, and one call on a stacked batch equals per-workload calls; sched-carrying configs route through
    ``simulator.sweep`` bitwise-identically to per-config runs.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dram, sched, simulator, traces, workload
from repro.core.sched import policies, wavefront
from repro.core.timing import GEOM, SchedConfig, paper_config

POLICIES = ("row_benefit", "segment_benefit", "lru", "random")
CACHED = ("lisa_villa", "figcache_slow", "figcache_fast", "figcache_ideal")


def _assert_counters_equal(ref, got, ctx):
    for name, x, y in zip(ref._fields, ref, got):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (ctx, name)


@functools.lru_cache(maxsize=None)
def _pressure_trace(n=320):
    """One-channel hammer overflowing a tiny cache: constant insert/evict
    pressure through every picker, multiple banks and cores."""
    idx = np.arange(n)
    return dram.Trace(
        t_issue=jnp.asarray(idx * 16, jnp.int32),
        bank=jnp.asarray(idx % 5, jnp.int32),
        row=jnp.asarray((idx * 7) % 97, jnp.int32),
        col=jnp.asarray((idx * 13) % 128, jnp.int32),
        is_write=jnp.asarray(idx % 5 == 0, bool),
        core=jnp.asarray(idx % 8, jnp.int32),
    )


def _mech_policy_matrix():
    out = [("base", "row_benefit"), ("lldram", "row_benefit")]
    for mech in CACHED:
        for policy in POLICIES:
            out.append((mech, policy))
    return out


# ---------------------------------------------------------------------------
# 1. wavefront == serial fused scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mech,policy", _mech_policy_matrix())
def test_wavefront_bitwise_all_mechanisms_policies(mech, policy):
    """The acceptance bar: wave scan == serial fused scan, bit for bit,
    across the whole mechanism x policy matrix (FCFS order)."""
    tr = _pressure_trace()
    cfg = paper_config(mech, cache_rows=2, policy=policy) \
        if mech in CACHED else paper_config(mech, policy=policy)
    serial = dram.run_channel(tr, cfg)
    wave = sched.run_channel_waves(tr, cfg)
    _assert_counters_equal(serial, wave, (mech, policy))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 2), st.sampled_from(POLICIES),
       st.integers(1, 8))
def test_wavefront_bitwise_random_traces(seed, policy, width):
    """Hypothesis property: random traces (same-bank streaks, same-core
    bursts, idle gaps) stay bitwise-equal at any wave width."""
    rng = np.random.default_rng(seed)
    n = 160
    tr = dram.Trace(
        t_issue=jnp.asarray(np.cumsum(rng.integers(0, 120, n)), jnp.int32),
        bank=jnp.asarray(rng.integers(0, GEOM.n_banks, n), jnp.int32),
        row=jnp.asarray(rng.integers(0, 50, n), jnp.int32),
        col=jnp.asarray(rng.integers(0, 128, n), jnp.int32),
        is_write=jnp.asarray(rng.random(n) < 0.3),
        core=jnp.asarray(rng.integers(0, GEOM.n_cores, n), jnp.int32),
    )
    cfg = paper_config("figcache_fast", cache_rows=2, policy=policy)
    serial = dram.run_channel(tr, cfg)
    wave = sched.run_channel_waves(tr, cfg, width=width)
    _assert_counters_equal(serial, wave, (seed, policy, width))


def test_wavefront_bitwise_ragged_noop_padded():
    """No-op padding (ragged ``sweep_traces`` traces) is dropped by wave
    formation and must not perturb any counter."""
    tr = _pressure_trace()
    cfg = paper_config("figcache_fast", cache_rows=2)
    padded = dram.noop_pad(tr, 512)
    _assert_counters_equal(dram.run_channel(tr, cfg),
                           sched.run_channel_waves(padded, cfg), "ragged")


def test_wavefront_bitwise_multi_channel():
    apps = tuple(traces.app_params(n) for n in ("libquantum", "mcf"))
    tr = traces.build_trace(list(apps), 2, 512, 4)
    cfg = paper_config("figcache_fast", cache_rows=4)
    _assert_counters_equal(dram.run_channel(tr, cfg),
                           sched.run_channel_waves(tr, cfg), "multi")


def test_wavefront_lookahead_matches_linearized_oracle():
    """With a bank-parallelism window the wave order is a bounded
    reordering; the serial scan on the *linearized* order is the oracle."""
    tr = _pressure_trace()
    cfg = paper_config("figcache_fast", cache_rows=2)
    wtr = wavefront.form_waves(tr, lookahead=16)
    lin = wavefront.linearize_waves(wtr)
    serial = dram.run_channel(dram.Trace(*map(jnp.asarray, lin)), cfg)
    wave = wavefront._simulate_waves_jit(wtr, cfg.static, cfg.params())
    _assert_counters_equal(serial, wave, "lookahead")


def test_wavefront_sweep_matches_run_sweep():
    """The wave scan batches over stacked params like ``dram.run_sweep``."""
    tr = _pressure_trace()
    cfgs = [paper_config("figcache_fast", cache_rows=cr) for cr in (2, 4)]
    static = cfgs[0].static
    assert all(c.static == static for c in cfgs)
    batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[c.params() for c in cfgs])
    wtr = wavefront.form_waves(tr)
    swept = wavefront.run_sweep_waves(wtr, static, batch)
    for i, cfg in enumerate(cfgs):
        ref = dram.run_channel(tr, cfg)
        got = jax.tree.map(lambda a, i=i: a[i], swept)
        _assert_counters_equal(ref, got, ("sweep", i))


# ---------------------------------------------------------------------------
# 2. wave formation invariants
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 2), st.integers(1, 16), st.integers(0, 48))
def test_wave_formation_invariants(seed, width, lookahead):
    rng = np.random.default_rng(seed)
    n = 200
    tr = dram.Trace(
        t_issue=np.cumsum(rng.integers(1, 60, n)).astype(np.int32),
        bank=rng.integers(0, GEOM.n_banks, n).astype(np.int32),
        row=rng.integers(0, 50, n).astype(np.int32),
        col=rng.integers(0, 128, n).astype(np.int32),
        is_write=rng.random(n) < 0.3,
        core=rng.integers(0, GEOM.n_cores, n).astype(np.int32),
    )
    wtr = wavefront.form_waves(tr, width=width, lookahead=lookahead)
    t = np.asarray(wtr.t_issue)
    banks = np.asarray(wtr.bank)
    cores = np.asarray(wtr.core)
    real = t < dram.NOOP_ISSUE
    assert t.shape[1] == width
    for w in range(t.shape[0]):
        # banks distinct within every wave (pads included)
        assert len(set(banks[w].tolist())) == width, banks[w]
        # at most N_MSHR same-core real lanes per wave
        c, k = np.unique(cores[w][real[w]], return_counts=True)
        assert (k <= dram.N_MSHR).all()
    # the linearization is a permutation of the input ...
    lin = wavefront.linearize_waves(wtr)
    key = lambda trc, m: sorted(
        (np.asarray(trc.bank)[m] * 10 ** 9 + np.asarray(trc.row)[m] * 1000
         + np.asarray(trc.col)[m]).tolist())
    assert key(lin, slice(None)) == key(tr, slice(None))
    # ... that preserves per-bank FIFO order (t_issue is strictly
    # increasing within the trace, so it identifies requests).  Per-core
    # order may legitimately change with lookahead > 0: an idle-bank
    # request is pulled past a blocked same-core request, exactly like any
    # out-of-order controller.
    for b in range(GEOM.n_banks):
        m_in = np.asarray(tr.bank) == b
        m_out = np.asarray(lin.bank) == b
        assert np.array_equal(np.asarray(tr.t_issue)[m_in],
                              np.asarray(lin.t_issue)[m_out]), b
    if lookahead == 0:   # order-preserving formation: identity linearization
        assert np.array_equal(np.asarray(lin.t_issue),
                              np.asarray(tr.t_issue))


# ---------------------------------------------------------------------------
# 3. scheduling policies
# ---------------------------------------------------------------------------

def _sched_trace(n=240, seed=3):
    rng = np.random.default_rng(seed)
    return dram.Trace(
        t_issue=np.cumsum(rng.integers(1, 40, n)).astype(np.int32),
        bank=rng.integers(0, GEOM.n_banks, n).astype(np.int32),
        row=rng.integers(0, 8, n).astype(np.int32),
        col=rng.integers(0, 128, n).astype(np.int32),
        is_write=rng.random(n) < 0.4,
        core=rng.integers(0, GEOM.n_cores, n).astype(np.int32),
    )


def _req_keys(tr):
    return sorted(zip(np.asarray(tr.t_issue).tolist(),
                      np.asarray(tr.bank).tolist(),
                      np.asarray(tr.row).tolist(),
                      np.asarray(tr.col).tolist()))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 2), st.integers(1, 32), st.integers(0, 8),
       st.booleans())
def test_frfcfs_is_permutation_and_respects_starve_cap(seed, qd, cap, drain):
    sc = SchedConfig("frfcfs", queue_depth=qd, starve_cap=cap,
                     write_drain=drain, drain_batch=8,
                     arrival_window_ns=10 ** 6)
    tr = _sched_trace(seed=seed % 1000)
    out = sched.schedule(tr, sc)
    assert _req_keys(out) == _req_keys(tr)           # permutation
    # replay the service order against the *drain pre-pass* order (the
    # queue FR-FCFS walks) and count bypasses of the oldest pending
    order = list(range(np.asarray(tr.t_issue).size))
    if drain:
        order = sched.write_drain_perm(
            np.asarray(tr.bank).tolist(), np.asarray(tr.row).tolist(),
            np.asarray(tr.is_write).tolist(), order, 8)
    pos = {i: k for k, i in enumerate(order)}
    # recover each served request's pre-pass position via its unique t_issue
    tmap = {}
    t_in = np.asarray(tr.t_issue).tolist()
    for i in order:
        tmap.setdefault(t_in[i], []).append(pos[i])
    pending = set(range(len(order)))
    bypass = 0
    for ti in np.asarray(out.t_issue).tolist():
        p = tmap[ti].pop(0)
        if p == min(pending):
            bypass = 0
        else:
            bypass += 1
            assert bypass <= cap, (p, bypass, cap)
        pending.remove(p)


def _lanes(seed=5, T=96):
    """Six lanes: plain, ragged no-op suffix, interior no-ops, all no-op,
    and two with few rows (many hits); arrival gaps include 0 ticks."""
    rng = np.random.default_rng(seed)
    L = 6
    t = np.cumsum(rng.integers(0, 40, (L, T)), axis=1).astype(np.int32)
    t[1, T // 3:] = dram.NOOP_ISSUE
    t[2, ::5] = dram.NOOP_ISSUE
    t[3] = dram.NOOP_ISSUE
    rows = np.where(np.arange(L)[:, None] >= 4, 2, 8)
    return dram.Trace(
        t_issue=t,
        bank=rng.integers(0, GEOM.n_banks, (L, T)).astype(np.int32),
        row=(rng.integers(0, 8, (L, T)) % rows).astype(np.int32),
        col=rng.integers(0, 128, (L, T)).astype(np.int32),
        is_write=rng.random((L, T)) < 0.4,
        core=rng.integers(0, GEOM.n_cores, (L, T)).astype(np.int32))


def _assert_oracle_order(tr, sc):
    """``schedule`` equals ``_schedule_channel``'s plain-Python order on
    every lane of a (L, T) trace, in every field."""
    got = sched.schedule(tr, sc)
    for lane in range(np.shape(tr.t_issue)[0]):
        ch = [np.asarray(x)[lane] for x in tr]
        perm = policies._schedule_channel(ch[0], ch[1], ch[2], ch[4], sc,
                                          GEOM.n_banks)
        for f, x, y in zip(dram.Trace._fields, ch, got):
            assert np.array_equal(np.asarray(y)[lane], x[perm]), \
                (sc, lane, f)


def _oracle_grid():
    for qd in (1, 4, 32):
        for cap in (0, 1, 16):
            for aw in (0, 50, 10 ** 6):
                for drain in (None, 1, 16):
                    yield SchedConfig("frfcfs", queue_depth=qd,
                                      starve_cap=cap, arrival_window_ns=aw,
                                      write_drain=drain is not None,
                                      drain_batch=drain or 16)
    for drain in (1, 16):
        yield SchedConfig("fcfs", write_drain=True, drain_batch=drain)


@pytest.mark.parametrize("sc", list(_oracle_grid()),
                         ids=lambda sc: f"{sc.policy}-q{sc.queue_depth}"
                         f"-c{sc.starve_cap}-w{sc.arrival_window_ns}"
                         f"-d{int(sc.write_drain) and sc.drain_batch}")
def test_device_order_matches_oracle(sc):
    _assert_oracle_order(_lanes(), sc)


def _edge_trace(case):
    tr = _lanes(seed=9, T=20 if case == "short" else 96)
    if case == "all_writes":
        tr = tr._replace(is_write=np.ones_like(tr.is_write))
    elif case == "all_reads":
        tr = tr._replace(is_write=np.zeros_like(tr.is_write))
    return tr


@pytest.mark.parametrize("case", ["short", "all_writes", "all_reads"])
@pytest.mark.parametrize("drain", [False, True])
def test_device_order_matches_oracle_edge_traces(case, drain):
    """T < queue_depth (20 < 32), every request a write, none a write."""
    _assert_oracle_order(_edge_trace(case),
                         SchedConfig("frfcfs", queue_depth=32,
                                     write_drain=drain))


def test_device_order_matches_oracle_bench_shape():
    """One paper8.frfcfs mix as the benchmark generates it: 4 channels x
    8192 requests under queue 32, cap 16, window 50 ns, drain 16."""
    _, _, apps = traces.eight_core_workloads()[0]
    (tr,) = workload.generate_many([workload.spec_from_apps(apps, 4, 8192,
                                                            seed=1)])
    _assert_oracle_order(tr, SchedConfig("frfcfs", queue_depth=32,
                                          starve_cap=16,
                                          arrival_window_ns=50,
                                          write_drain=True, drain_batch=16))


@pytest.mark.parametrize("multi", [False, True])
def test_schedule_stacked_equals_per_workload(multi):
    """One call on a padded stack == the stack of per-workload calls,
    padded: no-ops stay a suffix of every lane."""
    full = _lanes(seed=13)
    C = 2 if multi else 1
    parts = [jax.tree.map(lambda x: np.asarray(x)[w * C:(w + 1) * C, :n],
                          full) for w, n in enumerate((96, 70, 41))]
    if not multi:
        parts = [jax.tree.map(lambda x: x[0], p) for p in parts]
    join = np.concatenate if multi else np.stack
    sc = SchedConfig("frfcfs", queue_depth=8, write_drain=True, drain_batch=4)
    stacked = sched.schedule(jax.tree.map(lambda *xs: join(xs), *[
        dram.noop_pad(p, 96) for p in parts]), sc)
    per = jax.tree.map(lambda *xs: join(xs), *[
        dram.noop_pad(sched.schedule(p, sc), 96) for p in parts])
    for f, x, y in zip(dram.Trace._fields, per, stacked):
        assert np.array_equal(np.asarray(x), np.asarray(y)), f


def test_frfcfs_starve_cap_zero_is_fcfs():
    tr = _sched_trace()
    out = sched.schedule(tr, SchedConfig("frfcfs", starve_cap=0))
    assert np.array_equal(np.asarray(out.t_issue), np.asarray(tr.t_issue))


def test_fcfs_is_identity_object():
    tr = _sched_trace()
    assert sched.schedule(tr, SchedConfig()) is tr


def test_frfcfs_serves_row_hit_first():
    """bank0: rowA, rowB, rowA — the second rowA request must be pulled
    past rowB once rowA's row is open."""
    tr = dram.Trace(
        t_issue=np.asarray([0, 1, 2], np.int32),
        bank=np.zeros(3, np.int32),
        row=np.asarray([7, 9, 7], np.int32),
        col=np.asarray([0, 0, 16], np.int32),
        is_write=np.zeros(3, bool),
        core=np.zeros(3, np.int32),
    )
    out = sched.schedule(tr, SchedConfig("frfcfs", queue_depth=4))
    assert np.asarray(out.row).tolist() == [7, 7, 9]


def test_frfcfs_preserves_per_row_fifo():
    """Row hits may bypass older same-bank *conflicts* (that is the point
    of FR-FCFS), but requests to the same (bank, row) — one row stream —
    are always served oldest-first."""
    tr = _sched_trace(seed=11)
    out = sched.schedule(tr, SchedConfig("frfcfs", queue_depth=16))
    key_in = np.asarray(tr.bank) * 1000 + np.asarray(tr.row)
    key_out = np.asarray(out.bank) * 1000 + np.asarray(out.row)
    for k in np.unique(key_in):
        assert np.array_equal(np.asarray(tr.t_issue)[key_in == k],
                              np.asarray(out.t_issue)[key_out == k]), k


def test_write_drain_batches_writes():
    """Writes queue up and drain as (bank, row)-sorted batches while reads
    flow past."""
    n = 12
    tr = dram.Trace(
        t_issue=np.arange(n, dtype=np.int32),
        bank=np.asarray([3, 2, 0, 1, 0, 1, 2, 0, 1, 2, 0, 1], np.int32),
        row=np.arange(n, dtype=np.int32) % 4,
        col=np.zeros(n, np.int32),
        is_write=np.asarray([0, 1, 0, 1, 0, 1, 0, 1, 0, 0, 0, 0], bool),
        core=np.zeros(n, np.int32),
    )
    out = sched.schedule(tr, SchedConfig("fcfs", write_drain=True,
                                         drain_batch=4))
    wr = np.asarray(out.is_write)
    # all four writes drain as one contiguous batch after the 4th write
    # arrives (input position 7), before the remaining reads
    first = int(np.argmax(wr))
    assert wr[first:first + 4].all() and wr.sum() == 4
    db, dr = np.asarray(out.bank)[first:first + 4], \
        np.asarray(out.row)[first:first + 4]
    keys = list(zip(db.tolist(), dr.tolist()))
    assert keys == sorted(keys)


def test_sweep_with_sched_matches_run_mechanism():
    """sched-carrying configs group/dispatch through ``simulator.sweep``
    bitwise-identically to one-at-a-time ``run_mechanism`` calls."""
    a = traces.app_params("libquantum")
    tr = jax.tree.map(lambda x: x[0], traces.build_trace([a], 1, 512, 1))
    cfgs = [paper_config("figcache_fast"),
            paper_config("figcache_fast",
                         sched=SchedConfig("frfcfs", queue_depth=16)),
            paper_config("base", sched=SchedConfig("frfcfs")),
            paper_config("base",
                         sched=SchedConfig("fcfs", write_drain=True))]
    res = simulator.sweep(tr, cfgs, (a,))
    for cfg, r in zip(cfgs, res):
        ref = simulator.run_mechanism(tr, cfg, (a,))
        _assert_counters_equal(ref.counters, r.counters, cfg.sched)


def test_sweep_traces_with_sched_matches_per_workload():
    a1 = (traces.app_params("libquantum"),)
    a2 = (traces.app_params("mcf"),)
    trs = [jax.tree.map(lambda x: x[0], traces.build_trace(list(a), 1, n, s))
           for a, n, s in ((a1, 384, 1), (a2, 256, 2))]
    sc = SchedConfig("frfcfs", queue_depth=16)
    cfgs = [paper_config("base", sched=sc),
            paper_config("figcache_fast", sched=sc),
            paper_config("figcache_fast")]
    res = simulator.sweep_traces(trs, cfgs, [a1, a2])
    for w, tr in enumerate(trs):
        ref = simulator.sweep(tr, cfgs, (a1, a2)[w])
        for i in range(len(cfgs)):
            _assert_counters_equal(ref[i].counters, res[w][i].counters,
                                   ("sched-ragged", w, i))
