"""Hot-loop regression tests (DESIGN.md §9).

Three contracts introduced by the incremental hot loop:

 1. **Carried aggregates == recomputed reductions.**  ``fts.row_sum``, the
    free stack and ``n_valid`` are maintained O(1) per ``touch`` / ``insert``
    / ``invalidate``; after ANY operation sequence they must equal the
    from-scratch reductions over the base arrays, and (without invalidate)
    the O(1) decision path must reproduce the recompute decision path
    (``insert(recompute=True)``) event for event.
 2. **Fused scan == dense scan == unpadded exact scan.**  The surgical
    per-(bank, slot) step (``dram.make_step``) must be bitwise-equal to
    the pre-aggregate "dense" reference body (``dram.simulate_dense``) and
    to ``dram.run_channel_exact`` across all six mechanisms and all four
    replacement policies; the Pallas-lookup static (``fts_kernel=True``,
    pure-JAX fallback on CPU CI) must change nothing.
 3. **No-op requests are inert.**  Ragged ``sweep_traces`` pads unequal
    traces with ``dram.NOOP_ISSUE`` requests; padding must not perturb any
    counter or result.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dram, simulator, traces
from repro.core import fts as fts_lib
from repro.core.timing import paper_config

POLICIES = ("row_benefit", "segment_benefit", "lru", "random")
CACHED = ("lisa_villa", "figcache_slow", "figcache_fast", "figcache_ideal")

MAX_SLOTS, MAX_SEGS = 48, 8   # padded allocation
N_SLOTS, SPR = 16, 4          # effective geometry: 4 rows x 4 segments


# ---------------------------------------------------------------------------
# 1a. aggregates == recomputed-from-scratch after arbitrary op sequences
# ---------------------------------------------------------------------------

def _apply_ops(ops, policy, use_recompute=False):
    """Drive a padded store through (kind, value) ops; kind 0 = access
    (lookup -> touch|insert), 1 = invalidate slot ``value % max_slots``,
    2 = access with the recompute (oracle) insert path."""
    fts = fts_lib.init(MAX_SLOTS, MAX_SEGS)
    for step, (kind, val) in enumerate(ops):
        if kind == 1:
            fts = fts_lib.invalidate(fts, jnp.int32(val % MAX_SLOTS), SPR)
            continue
        hit, slot = fts_lib.lookup(fts, jnp.int32(val))
        if bool(hit):
            fts = fts_lib.touch(fts, slot, jnp.bool_(val % 3 == 0),
                                jnp.int32(step), 31, SPR)
        else:
            want, fts = fts_lib.should_insert(fts, jnp.int32(val), 1)
            fts = fts_lib.insert(fts, jnp.int32(val), jnp.bool_(False),
                                 jnp.int32(step), policy=policy,
                                 segs_per_row=SPR, n_slots=N_SLOTS,
                                 recompute=use_recompute or kind == 2).fts
    return fts


def _assert_aggregates_consistent(fts):
    valid = np.asarray(fts.valid)
    benefit = np.asarray(fts.benefit)
    # row_sum[r] == sum of active-slot benefits of row r (recompute)
    active = np.arange(MAX_SLOTS) < N_SLOTS
    want_rows = np.zeros(MAX_SLOTS, np.int64)
    np.add.at(want_rows, np.arange(MAX_SLOTS) // SPR,
              np.where(active, benefit, 0))
    assert np.array_equal(np.asarray(fts.row_sum), want_rows)
    # n_valid == popcount(valid)
    n_valid = int(fts.n_valid)
    assert n_valid == int(valid.sum())
    # the free-stack suffix is exactly the invalid slot set, each once (the
    # prefix below the pointer is stale scratch — pushes overwrite it)
    free = np.asarray(fts.free_list)
    assert sorted(free[n_valid:].tolist()) == \
        sorted(np.flatnonzero(~valid).tolist())


@settings(max_examples=25, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 9), st.integers(0, 40)),
                min_size=1, max_size=60),
       st.sampled_from(POLICIES))
def test_aggregates_match_recompute_after_arbitrary_ops(raw_ops, policy):
    # kind 9 -> invalidate (~1/10 of ops; only active slots so the padding
    # invariant is respected); kind 8 -> recompute-path insert, which must
    # keep the carried stack consistent even when refilling argmin-first
    # holes the O(1) stack would refill in LIFO order
    ops = [(1, v % N_SLOTS) if k == 9 else (2 if k == 8 else 0, v)
           for k, v in raw_ops]
    fts = _apply_ops(ops, policy)
    _assert_aggregates_consistent(fts)


@settings(max_examples=15, deadline=None)
@given(st.lists(st.integers(0, 60), min_size=1, max_size=50),
       st.sampled_from(POLICIES))
def test_carried_decisions_equal_recompute_decisions(segs, policy):
    """Without invalidate, the O(1) aggregate path and the from-scratch
    recompute path must make identical decisions AND leave identical
    state."""
    ops = [(0, s) for s in segs]
    fast = _apply_ops(ops, policy)
    slow = _apply_ops(ops, policy, use_recompute=True)
    for name, a, b in zip(fast._fields, fast, slow):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (policy, name)
    _assert_aggregates_consistent(fast)


def test_invalidate_is_o1_push_and_reinsert_reuses_hole():
    fts = fts_lib.init(8, 4)
    for s in range(8):
        fts = fts_lib.insert(fts, jnp.int32(s), jnp.bool_(False),
                             jnp.int32(s), policy="row_benefit",
                             segs_per_row=4).fts
    assert int(fts.n_valid) == 8
    fts = fts_lib.invalidate(fts, jnp.int32(5), 4)
    assert int(fts.n_valid) == 7
    assert not bool(fts.valid[5]) and int(fts.tags[5]) == -1
    hit, _ = fts_lib.lookup(fts, jnp.int32(5))
    assert not bool(hit)
    res = fts_lib.insert(fts, jnp.int32(99), jnp.bool_(False), jnp.int32(9),
                         policy="row_benefit", segs_per_row=4)
    assert int(res.slot) == 5 and not bool(res.evicted_valid)
    # double-invalidate must be a no-op (slot pushed exactly once)
    fts2 = fts_lib.invalidate(res.fts, jnp.int32(3), 4)
    fts2 = fts_lib.invalidate(fts2, jnp.int32(3), 4)
    assert int(fts2.n_valid) == 7
    assert np.asarray(fts2.free_list)[7:].tolist() == [3]


# ---------------------------------------------------------------------------
# 2. scan-level bitwise equivalence: fused == dense == unpadded exact
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pressure_trace(n=320):
    """One-bank hammer overflowing a tiny cache: constant insert/evict
    pressure through every picker, small enough to keep compiles cheap."""
    idx = np.arange(n)
    return dram.Trace(
        t_issue=jnp.asarray(idx * 16, jnp.int32),
        bank=jnp.asarray(idx % 4, jnp.int32),
        row=jnp.asarray((idx * 7) % 97, jnp.int32),
        col=jnp.asarray((idx * 13) % 128, jnp.int32),
        is_write=jnp.asarray(idx % 5 == 0, bool),
        core=jnp.asarray(idx % 8, jnp.int32),
    )


def _assert_counters_equal(ref, got, ctx):
    for name, x, y in zip(ref._fields, ref, got):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (ctx, name)


def _mech_policy_matrix():
    """All six mechanisms x all four policies; the cache-less mechanisms
    have no replacement decision, so one policy covers their cell row."""
    out = []
    for mech in ("base", "lldram"):
        out.append((mech, "row_benefit"))
    for mech in CACHED:
        for policy in POLICIES:
            out.append((mech, policy))
    return out


@pytest.mark.parametrize("mech,policy", _mech_policy_matrix())
def test_fused_step_bitwise_all_mechanisms_policies(mech, policy):
    """The acceptance bar: fused padded scan == dense padded scan ==
    unpadded ``run_channel_exact``, bit for bit, across the whole
    mechanism x policy matrix."""
    tr = _pressure_trace()
    cfg = paper_config(mech, cache_rows=2, policy=policy) \
        if mech in CACHED else paper_config(mech, policy=policy)
    fused = dram.run_channel(tr, cfg)
    dense = dram.simulate_dense(tr, cfg.static, cfg.params())
    exact = dram.run_channel_exact(tr, cfg)
    _assert_counters_equal(fused, dense, (mech, policy, "dense"))
    _assert_counters_equal(fused, exact, (mech, policy, "exact"))


@pytest.mark.parametrize("policy", ["row_benefit", "segment_benefit"])
def test_fts_kernel_static_is_bitwise_neutral(policy):
    """``fts_kernel=True`` routes lookup+victim through the fused op; on
    non-TPU backends it falls back to the bit-exact pure-JAX ref, so the
    counters must not move at all."""
    tr = _pressure_trace()
    plain = dram.run_channel(tr, paper_config(
        "figcache_fast", cache_rows=2, policy=policy))
    kern = dram.run_channel(tr, paper_config(
        "figcache_fast", cache_rows=2, policy=policy, fts_kernel=True))
    _assert_counters_equal(plain, kern, policy)


# ---------------------------------------------------------------------------
# 3. ragged-workload batching: no-op padding is inert
# ---------------------------------------------------------------------------

def test_noop_padding_is_inert():
    tr = _pressure_trace()
    cfg = paper_config("figcache_fast", cache_rows=2)
    padded = dram.noop_pad(tr, 512)
    assert padded.t_issue.shape == (512,)
    _assert_counters_equal(dram.run_channel(tr, cfg),
                           dram.run_channel(padded, cfg), "noop-pad")


def test_sweep_traces_ragged_single_channel():
    a = traces.app_params("libquantum")
    trs = [jax.tree.map(lambda x: x[0], traces.build_trace([a], 1, n, s))
           for n, s in ((768, 1), (512, 2), (250, 3))]
    cfgs = [paper_config("base"), paper_config("figcache_fast")]
    apps_list = [(a,)] * len(trs)
    res = simulator.sweep_traces(trs, cfgs, apps_list)
    for w, tr in enumerate(trs):
        ref = simulator.sweep(tr, cfgs, apps_list[w])
        for i in range(len(cfgs)):
            _assert_counters_equal(ref[i].counters, res[w][i].counters,
                                   ("ragged-1ch", w, i))
            assert np.array_equal(ref[i].ipc, res[w][i].ipc)
            assert ref[i].system_energy_nj == res[w][i].system_energy_nj


def test_sweep_traces_ragged_multi_channel():
    apps = tuple(traces.app_params(n) for n in ("libquantum", "mcf"))
    trs = [traces.build_trace(list(apps), 2, n, s)
           for n, s in ((512, 4), (300, 5))]
    cfgs = [paper_config("figcache_fast")]
    res = simulator.sweep_traces(trs, cfgs, [apps] * len(trs))
    for w, tr in enumerate(trs):
        ref = simulator.sweep(tr, cfgs, apps)
        _assert_counters_equal(ref[0].counters, res[w][0].counters,
                               ("ragged-2ch", w))
        assert np.array_equal(ref[0].ipc, res[w][0].ipc)


def test_sweep_traces_channel_count_must_agree():
    a = traces.app_params("libquantum")
    one = jax.tree.map(lambda x: x[0], traces.build_trace([a], 1, 64, 1))
    two = traces.build_trace([a, a], 2, 64, 2)
    with pytest.raises(AssertionError):
        simulator.sweep_traces([one, two], [paper_config("base")],
                               [(a,), (a, a)])


# ---------------------------------------------------------------------------
# 4. per-lane index ops (DESIGN.md §9): one-hot access to the small leaves
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.int32, jnp.bool_])
@pytest.mark.parametrize("i", [0, 15])
def test_onehot_helpers_match_indexing(dtype, i):
    """``onehot_read`` / ``onehot_write`` on a bank axis of 16 are ``x[i]``
    and ``x.at[i].set(v)``, for scalar and row leaves, at the first and
    the last bank."""
    rng = np.random.default_rng(i)
    for shape in ((16,), (16, 8)):
        x = jnp.asarray(rng.integers(-9, 9, shape)).astype(dtype)
        v = jnp.asarray(rng.integers(-9, 9, shape[1:])).astype(dtype)
        idx = jnp.int32(i)
        assert np.array_equal(fts_lib.onehot_read(x, idx), x[i])
        assert np.array_equal(fts_lib.onehot_write(x, idx, v),
                              x.at[i].set(v))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.bool_])
def test_onehot_helpers_under_vmap_with_distinct_banks(dtype):
    """The wavefront's use: ``decide`` vmapped over a wave of distinct
    banks reads with ``onehot_read``, and ``apply_write`` writes the whole
    wave with one vector-index ``onehot_write``; the serial step runs
    under the lane vmap with its state batched too."""
    rng = np.random.default_rng(5)
    banks = jnp.asarray([15, 0, 7, 3], jnp.int32)
    x = jnp.asarray(rng.integers(-9, 9, (16, 8))).astype(dtype)
    v = jnp.asarray(rng.integers(-9, 9, (4, 8))).astype(dtype)
    got = jax.vmap(fts_lib.onehot_read, in_axes=(None, 0))(x, banks)
    assert np.array_equal(got, x[banks])
    assert np.array_equal(fts_lib.onehot_write(x, banks, v),
                          x.at[banks].set(v))
    assert np.array_equal(fts_lib.onehot_write(x[:, 0], banks, v[:, 0]),
                          x[:, 0].at[banks].set(v[:, 0]))
    xs = jnp.asarray(rng.integers(-9, 9, (4, 16, 8))).astype(dtype)
    want = jnp.stack([xs[k].at[banks[k]].set(v[k]) for k in range(4)])
    assert np.array_equal(jax.vmap(fts_lib.onehot_write)(xs, banks, v), want)


# gather + scatter primitives in run_sweep's scan body: none on the bank,
# core and MSHR axes (one-hot); for a caching mechanism the slot planes'
# reads and writes at (bank, w), the tracker pair's at (bank, tr_idx), and
# the lookup's row gathers (43 before, DESIGN.md §9)
SCAN_INDEX_OPS = {"base": 0, "lldram": 0, **{m: 19 for m in CACHED}}


@pytest.mark.parametrize("mech", sorted(SCAN_INDEX_OPS))
def test_scan_body_index_ops(mech):
    from repro.analysis.jaxpr_audit import _walk
    cfg = paper_config(mech)
    batch = jax.tree.map(lambda a: a[None], cfg.params())
    tr = dram.Trace(*(jax.ShapeDtypeStruct((4, 64), dt) for dt in (
        jnp.int32, jnp.int32, jnp.int32, jnp.int32, jnp.bool_, jnp.int32)))
    closed = jax.make_jaxpr(
        lambda t, b: dram.run_sweep(t, cfg.static, b))(tr, batch)
    n = sum(1 for eqn, _path, depth in _walk(closed.jaxpr)
            if depth >= 1 and eqn.primitive.name in
            ("gather", "scatter", "scatter-add"))
    assert n <= SCAN_INDEX_OPS[mech], (mech, n)
