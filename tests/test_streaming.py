"""Chunked-streaming regression tests (DESIGN.md §13).

The headline guarantee under test: **any chunking of any trace replays
bitwise-identically to the monolithic scan** — across mechanisms,
controllers, execution variants (serial fused / wavefront), channel
counts, ragged no-op-padded tails, the codec path, resumed-from-
checkpoint runs, and device-synthesized epoch streams.  Contracts:

 1. **Chunk-size invariance.**  ``streaming.simulate_stream`` over chunk
    sizes {1, 7, 64, full} equals ``dram.run_channel`` for every
    mechanism, every controller (FCFS / FR-FCFS / write-drain / both),
    wavefront execution, multi-channel traces, and hypothesis-random
    traces with ragged tails.
 2. **Codec roundtrip.**  ``traces.encode_trace``/``decode_trace`` is the
    identity on real requests — including adversarial delta-overflow
    (gaps and scheduler-induced *negative* deltas outside int16) and
    cluster-table-boundary traces — and the decoded segment stream
    replays bitwise.
 3. **Checkpoint/resume.**  A replay interrupted mid-trace and resumed
    from its newest ``SimState`` snapshot finishes bitwise-equal to the
    uninterrupted run (with and without a controller in front).
 4. **Interior no-ops.**  Chunk-tail fillers land *inside* the scanned
    stream, so interior no-ops must be exactly as counter-inert as the
    terminal padding ``sweep_traces`` emits — pinned against golden
    counters for base + figcache_fast (fused, wavefront, and chunked).
 5. **Compile budget.**  Chunked replay compiles the segment step exactly
    once (the ``streaming.chunked-replay`` contract).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dram, sched, streaming, traces, workload
from repro.core.sched import policies
from repro.core.timing import (GEOM, SCHED_FCFS, SchedConfig, paper_config)

MECHS = ("base", "lldram", "lisa_villa", "figcache_slow", "figcache_fast",
         "figcache_ideal")
CACHED = ("lisa_villa", "figcache_slow", "figcache_fast", "figcache_ideal")
CHUNKS = (1, 7, 64, 320)          # 320 == the full pressure trace

SCHEDS = (
    SCHED_FCFS,
    SchedConfig(policy="frfcfs", queue_depth=8, starve_cap=4),
    SchedConfig(write_drain=True, drain_batch=4),
    SchedConfig(policy="frfcfs", queue_depth=8, starve_cap=4,
                write_drain=True, drain_batch=4),
)


def _assert_counters_equal(ref, got, ctx):
    for name, x, y in zip(ref._fields, ref, got):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (ctx, name)


def _cfg(mech, **kw):
    return paper_config(mech, cache_rows=2, **kw) if mech in CACHED \
        else paper_config(mech, **kw)


@functools.lru_cache(maxsize=None)
def _pressure_trace(n=320):
    """The test_sched.py hammer: tiny cache, constant insert/evict
    pressure, multiple banks and cores."""
    idx = np.arange(n)
    return dram.Trace(
        t_issue=jnp.asarray(idx * 16, jnp.int32),
        bank=jnp.asarray(idx % 5, jnp.int32),
        row=jnp.asarray((idx * 7) % 97, jnp.int32),
        col=jnp.asarray((idx * 13) % 128, jnp.int32),
        is_write=jnp.asarray(idx % 5 == 0, bool),
        core=jnp.asarray(idx % 8, jnp.int32),
    )


def _random_trace(seed, n=160):
    rng = np.random.default_rng(seed)
    return dram.Trace(
        t_issue=jnp.asarray(np.cumsum(rng.integers(0, 120, n)), jnp.int32),
        bank=jnp.asarray(rng.integers(0, GEOM.n_banks, n), jnp.int32),
        row=jnp.asarray(rng.integers(0, 50, n), jnp.int32),
        col=jnp.asarray(rng.integers(0, 128, n), jnp.int32),
        is_write=jnp.asarray(rng.random(n) < 0.3),
        core=jnp.asarray(rng.integers(0, GEOM.n_cores, n), jnp.int32),
    )


# ---------------------------------------------------------------------------
# 1. chunk-size invariance
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mech", MECHS)
def test_chunk_invariance_all_mechanisms(mech):
    """The acceptance bar: every chunking of the pressure trace equals
    the monolithic scan, bit for bit, for every mechanism."""
    tr = _pressure_trace()
    cfg = _cfg(mech)
    mono = dram.run_channel(tr, cfg)
    for L in CHUNKS:
        got = streaming.simulate_stream(streaming.iter_chunks(tr, L), cfg)
        _assert_counters_equal(mono, got, (mech, L))


@pytest.mark.parametrize("sc", SCHEDS, ids=("fcfs", "frfcfs", "drain",
                                            "frfcfs+drain"))
def test_chunk_invariance_scheduled(sc):
    """A controller in front: the carried ``StreamScheduler`` window must
    reproduce the monolithic ``schedule`` permutation across chunk
    boundaries, so streamed == schedule-then-monolithic bitwise."""
    tr = _pressure_trace()
    cfg = _cfg("figcache_fast", sched=sc)
    mono = dram.run_channel(policies.schedule(tr, sc), cfg)
    for L in (1, 7, 64, 320):
        got = streaming.simulate_stream(streaming.iter_chunks(tr, L), cfg)
        _assert_counters_equal(mono, got, (sc, L))


def test_stream_scheduler_matches_schedule():
    """feed/flush across any chunking emits exactly the monolithic
    ``schedule`` service order (requests compared field-by-field)."""
    tr = _pressure_trace()
    leaves = {f: np.asarray(getattr(tr, f)) for f in dram.Trace._fields}
    for sc in SCHEDS[1:]:
        ref = policies.schedule(tr, sc)
        for L in (1, 13, 64):
            ss = policies.StreamScheduler(sc)
            parts = [ss.feed(seg) for seg in streaming.iter_chunks(tr, L)]
            parts.append(ss.flush())
            for f in dram.Trace._fields:
                got = np.concatenate([np.asarray(getattr(p, f))
                                      for p in parts])
                assert np.array_equal(got, np.asarray(getattr(ref, f))), \
                    (sc, L, f)


def test_chunk_invariance_wavefront():
    """Wavefront execution: per-chunk wave formation + the padded wave
    segment scan equals the monolithic wave scan (and the serial scan)."""
    tr = _pressure_trace()
    cfg = _cfg("figcache_fast")
    mono = sched.run_channel_waves(tr, cfg)
    _assert_counters_equal(dram.run_channel(tr, cfg), mono, "serial==wave")
    for L in (7, 64, 320):
        got = streaming.simulate_stream(streaming.iter_chunks(tr, L), cfg,
                                        wavefront_exec=True)
        _assert_counters_equal(mono, got, ("wave", L))


def test_chunk_invariance_multi_channel():
    """(C, T) traces chunk along the request axis; each channel's carry
    threads independently.  Ragged tail (512 % 96 != 0) rides along."""
    apps = tuple(traces.app_params(n) for n in ("libquantum", "mcf"))
    tr = traces.build_trace(list(apps), 2, 512, 4)
    cfg = _cfg("figcache_fast")
    mono = dram.run_channel(tr, cfg)
    for L in (96, 512):
        got = streaming.simulate_stream(streaming.iter_chunks(tr, L), cfg)
        _assert_counters_equal(mono, got, ("multi", L))


def test_chunk_invariance_multi_channel_scheduled():
    apps = tuple(traces.app_params(n) for n in ("libquantum", "mcf"))
    tr = traces.build_trace(list(apps), 2, 384, 4)
    sc = SCHEDS[3]
    cfg = _cfg("figcache_fast", sched=sc)
    mono = dram.run_channel(policies.schedule(tr, sc), cfg)
    got = streaming.simulate_stream(streaming.iter_chunks(tr, 100), cfg)
    _assert_counters_equal(mono, got, "multi-sched")


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 2), st.sampled_from((1, 7, 33, 64, 160)),
       st.sampled_from(("base", "figcache_fast", "figcache_ideal")))
def test_chunk_invariance_random_traces(seed, L, mech):
    """Hypothesis property: random traces (bursts, idle gaps, ragged
    tails whenever L does not divide T) are chunking-invariant."""
    tr = _random_trace(seed)
    cfg = _cfg(mech)
    mono = dram.run_channel(tr, cfg)
    got = streaming.simulate_stream(streaming.iter_chunks(tr, L), cfg)
    _assert_counters_equal(mono, got, (seed, L, mech))


def test_sweep_chunk_len_routing():
    """``simulator.sweep(..., chunk_len=)`` routes through the streamed
    sweep and stays bitwise-equal to the monolithic dispatch."""
    from repro.core import simulator
    tr = _pressure_trace()
    apps = [traces.app_params("mcf")]
    cfgs = [_cfg("figcache_fast", insert_threshold=th) for th in (1, 4)]
    mono = simulator.sweep(tr, cfgs, apps)
    got = simulator.sweep(tr, cfgs, apps, chunk_len=64)
    for m, g in zip(mono, got):
        _assert_counters_equal(m.counters, g.counters, "sweep-chunked")


# ---------------------------------------------------------------------------
# 2. codec roundtrip
# ---------------------------------------------------------------------------

def _assert_trace_equal(ref, got, ctx):
    for f in dram.Trace._fields:
        assert np.array_equal(np.asarray(getattr(ref, f)),
                              np.asarray(getattr(got, f))), (ctx, f)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31 - 2), st.sampled_from((32, 64, 256)),
       st.sampled_from((4, 64, 1024)))
def test_codec_roundtrip_random(seed, chunk_len, max_clusters):
    """encode -> decode is the identity on real requests for ANY chunk
    length / cluster-table size (unrepresentable cases terminate chunks
    early rather than losing information)."""
    tr = _random_trace(seed)
    chunks = traces.encode_trace(tr, chunk_len=chunk_len,
                                 max_clusters=max_clusters)
    _assert_trace_equal(tr, traces.decode_trace(chunks),
                        (seed, chunk_len, max_clusters))


def test_codec_roundtrip_delta_overflow():
    """Gaps beyond int16 (idle periods) force early chunk termination +
    a fresh base next chunk; the roundtrip stays exact."""
    n = 100
    idx = np.arange(n)
    gaps = np.where(idx % 10 == 9, 200_000, 16)    # 9 overflowing deltas
    tr = _pressure_trace()._replace(
        t_issue=jnp.asarray(np.cumsum(gaps), jnp.int32),
        bank=jnp.asarray(idx % 5, jnp.int32),
        row=jnp.asarray(idx % 7, jnp.int32),
        col=jnp.asarray(idx % 128, jnp.int32),
        is_write=jnp.asarray(idx % 3 == 0, bool),
        core=jnp.asarray(idx % 8, jnp.int32))
    chunks = traces.encode_trace(tr, chunk_len=64)
    assert len(chunks) > 2          # the overflows actually fragmented it
    _assert_trace_equal(tr, traces.decode_trace(chunks), "delta-overflow")


def test_codec_roundtrip_negative_deltas():
    """Scheduled traces are non-monotone: FR-FCFS row-hit bypass yields
    negative deltas.  Small ones encode in int16; ones beyond -2**15
    terminate the chunk.  Both roundtrip exactly."""
    idx = np.arange(160)
    tr = _pressure_trace()._replace(          # same-bank row ping-pong:
        t_issue=jnp.asarray(idx * 4, jnp.int32),   # FR-FCFS hoists hits
        bank=jnp.zeros(160, jnp.int32),
        row=jnp.asarray(idx % 2, jnp.int32),
        col=jnp.asarray(idx % 128, jnp.int32),
        is_write=jnp.asarray(idx % 3 == 0, bool),
        core=jnp.asarray(idx % 8, jnp.int32))
    sc = SchedConfig(policy="frfcfs", queue_depth=8, starve_cap=4)
    sched_tr = policies.schedule(tr, sc)
    assert np.any(np.diff(np.asarray(sched_tr.t_issue)) < 0)
    _assert_trace_equal(sched_tr,
                        traces.decode_trace(traces.encode_trace(
                            sched_tr, chunk_len=64)), "neg-small")
    # adversarial: a jump far forward then back, outside int16 either way
    t = np.asarray(tr.t_issue).copy()
    t[50], t[51] = t[50] + 300_000, t[51]
    adv = tr._replace(t_issue=jnp.asarray(t, jnp.int32))
    _assert_trace_equal(adv,
                        traces.decode_trace(traces.encode_trace(
                            adv, chunk_len=64)), "neg-large")


def test_codec_cluster_boundary():
    """Exactly max_clusters distinct pages fills the table; one more
    terminates the chunk at the boundary.  Both roundtrip exactly."""
    for distinct in (8, 9):
        idx = np.arange(64)
        tr = _pressure_trace()._replace(
            t_issue=jnp.asarray(idx * 16, jnp.int32),
            bank=jnp.asarray(idx % 2, jnp.int32),
            row=jnp.asarray((idx // 2) % (distinct // 2 + distinct % 2),
                            jnp.int32),
            col=jnp.asarray(idx % 4, jnp.int32),
            is_write=jnp.asarray(idx % 2 == 0, bool),
            core=jnp.asarray(idx % 8, jnp.int32))
        chunks = traces.encode_trace(tr, chunk_len=64, max_clusters=8)
        n_pages = len(np.unique(np.asarray(tr.bank) * (1 << 16)
                                + np.asarray(tr.row)))
        if n_pages > 8:
            assert len(chunks) > 1
        _assert_trace_equal(tr, traces.decode_trace(chunks),
                            ("clusters", distinct))


def test_codec_segments_replay_bitwise():
    """The full pipeline: encode -> decoded_segments -> simulate_stream
    equals the monolithic replay, single- and multi-channel."""
    tr = _pressure_trace()
    cfg = _cfg("figcache_fast")
    enc = traces.encode_trace(tr, chunk_len=64)
    _assert_counters_equal(
        dram.run_channel(tr, cfg),
        streaming.simulate_stream(streaming.decoded_segments(enc), cfg),
        "codec-replay")
    apps = tuple(traces.app_params(n) for n in ("libquantum", "mcf"))
    mtr = traces.build_trace(list(apps), 2, 384, 4)
    enc2 = [traces.encode_trace(
        jax.tree.map(lambda a, c=c: np.asarray(a)[c], mtr), chunk_len=64)
        for c in range(2)]
    _assert_counters_equal(
        dram.run_channel(mtr, cfg),
        streaming.simulate_stream(streaming.decoded_segments(enc2), cfg),
        "codec-replay-multi")


# ---------------------------------------------------------------------------
# 3. checkpoint / resume
# ---------------------------------------------------------------------------

def test_checkpoint_resume_bitwise(tmp_path):
    """Interrupt a chunked replay mid-trace, restore the newest SimState
    snapshot, finish: bitwise the uninterrupted run."""
    tr = _pressure_trace()
    cfg = _cfg("figcache_fast")
    mono = dram.run_channel(tr, cfg)
    full = streaming.simulate_stream(
        streaming.iter_chunks(tr, 64), cfg,
        checkpoint_dir=str(tmp_path), checkpoint_every=2)
    _assert_counters_equal(mono, full, "with-snapshots")
    # the "interrupted" run IS the snapshot state on disk (chunk 4 of 5);
    # resume must replay only the suffix and still agree
    got = streaming.resume_stream(streaming.iter_chunks(tr, 64), cfg,
                                  str(tmp_path))
    _assert_counters_equal(mono, got, "resumed")


def test_checkpoint_resume_scheduled(tmp_path):
    """Resume composes with a controller in front: the skipped prefix is
    counted in *emitted* segments, after the scheduling wrap."""
    tr = _pressure_trace()
    cfg = _cfg("figcache_fast", sched=SCHEDS[1])
    mono = dram.run_channel(policies.schedule(tr, SCHEDS[1]), cfg)
    streaming.simulate_stream(streaming.iter_chunks(tr, 32), cfg,
                              checkpoint_dir=str(tmp_path),
                              checkpoint_every=3)
    got = streaming.resume_stream(streaming.iter_chunks(tr, 32), cfg,
                                  str(tmp_path))
    _assert_counters_equal(mono, got, "resumed-scheduled")


# ---------------------------------------------------------------------------
# 4. interior no-ops (chunk-tail fillers)
# ---------------------------------------------------------------------------

# golden sums for _interior_noop_trace(): pinned so any change to the
# sentinel guards that would silently re-count interior padding fails
# loudly rather than shifting results (fused == wavefront == chunked).
_GOLDEN = {
    "base": dict(acts_slow=120, acts_fast=0, reads=90, writes=30,
                 reloc_blocks=0, wb_blocks=0, row_hits=0, cache_hits=0,
                 insertions=0, lat_sum_ns=29935, req_cnt=120, t_end=6630),
    "figcache_fast": dict(acts_slow=120, acts_fast=0, reads=90, writes=30,
                          reloc_blocks=1920, wb_blocks=160, row_hits=0,
                          cache_hits=0, insertions=120, lat_sum_ns=50400,
                          req_cnt=120, t_end=10050),
}


def _interior_noop_trace():
    """Three 40-request runs separated by 8-deep INTERIOR no-op runs —
    the shape a chunk-tail filler stream presents to the scan."""
    parts, k = [], 0
    for blk in range(3):
        idx = np.arange(40) + blk * 40
        parts.append(dict(
            t_issue=idx * 24, bank=idx % 5, row=(idx * 11) % 97,
            col=(idx * 3) % 128, is_write=idx % 4 == 0, core=idx % 8))
        if blk < 2:
            parts.append(dict(
                t_issue=np.full(8, dram.NOOP_ISSUE),
                bank=np.zeros(8, int), row=np.zeros(8, int),
                col=np.zeros(8, int), is_write=np.zeros(8, bool),
                core=np.zeros(8, int)))
    cat = {f: np.concatenate([p[f] for p in parts]) for f in parts[0]}
    return dram.Trace(
        t_issue=jnp.asarray(cat["t_issue"], jnp.int32),
        bank=jnp.asarray(cat["bank"], jnp.int32),
        row=jnp.asarray(cat["row"], jnp.int32),
        col=jnp.asarray(cat["col"], jnp.int32),
        is_write=jnp.asarray(cat["is_write"], bool),
        core=jnp.asarray(cat["core"], jnp.int32))


@pytest.mark.parametrize("mech", ("base", "figcache_fast"))
def test_interior_noops_golden(mech):
    """Interior no-ops are exactly as inert as terminal padding: fused,
    wavefront, and chunked replays agree with each other AND with the
    pinned golden counters (catches silent re-counting regressions)."""
    tr = _interior_noop_trace()
    cfg = _cfg(mech)
    fused = dram.run_channel(tr, cfg)
    _assert_counters_equal(fused, sched.run_channel_waves(tr, cfg),
                           (mech, "wave"))
    _assert_counters_equal(
        fused, streaming.simulate_stream(streaming.iter_chunks(tr, 17),
                                         cfg), (mech, "chunked"))
    got = {f: int(np.asarray(getattr(fused, f)).sum())
           for f in fused._fields}
    assert got == _GOLDEN[mech], (mech, got)


def test_interior_noops_equal_stripped():
    """Stripping the interior no-ops entirely gives the same counters:
    padding position (interior vs terminal vs absent) never matters."""
    tr = _interior_noop_trace()
    keep = np.asarray(tr.t_issue) < dram.NOOP_ISSUE
    stripped = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)[keep]), tr)
    cfg = _cfg("figcache_fast")
    _assert_counters_equal(dram.run_channel(stripped, cfg),
                           dram.run_channel(tr, cfg), "stripped")


# ---------------------------------------------------------------------------
# 5. compile budget + generated streams
# ---------------------------------------------------------------------------

def test_chunked_replay_compile_budget():
    """The sanitizer contract: a chunked replay compiles the segment step
    exactly once — all same-shape segments hit one cache entry."""
    from repro.analysis import contracts
    findings = contracts.check_contract("streaming.chunked-replay")
    assert not findings, [f.message for f in findings]


def test_generate_stream_replays_bitwise():
    """Epoch-streamed synthesis: the concatenation of generate_stream's
    segments (epoch-tail no-ops landing INTERIOR) replays monolithically
    to the same counters as the streamed replay."""
    spec = workload.preset("stream", n_cores=2, n_channels=2,
                           per_channel=160, seed=9)
    segs = list(workload.generate_stream(spec, 2))
    assert len(segs) == 2
    # arrival clocks stay continuous across the epoch boundary
    a, b = (np.asarray(s.t_issue) for s in segs)
    assert b[b < dram.NOOP_ISSUE].min() > a[a < dram.NOOP_ISSUE].max()
    cat = jax.tree.map(
        lambda x, y: jnp.concatenate(
            [jnp.asarray(x), jnp.asarray(y)], axis=-1), *segs)
    cfg = _cfg("figcache_fast")
    _assert_counters_equal(dram.run_channel(cat, cfg),
                           streaming.simulate_stream(iter(segs), cfg),
                           "generate-stream")
