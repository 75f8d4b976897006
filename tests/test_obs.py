"""Flight-recorder observability tests (DESIGN.md §15, ISSUE 9).

Five contract families:

 1. **Bitwise invisibility + golden pin.**  With ``telemetry=0`` the
    counters of every mechanism x controller combo equal the golden
    fingerprints pinned below (generated from the pre-telemetry seed —
    the telemetry plumbing may not perturb a single bit of the disabled
    path), and the telemetry-ENABLED run of the same combo produces
    bitwise-identical final ``Counters``: windows observe the scan, they
    never steer it.
 2. **Conservation.**  The sum of per-window deltas equals the final
    ``Counters`` exactly (ints, not approximately) — nothing is dropped
    at window/segment boundaries, including the trailing partial window.
 3. **Chunk invariance.**  The window series from chunked replays
    (chunk in {1, 7, 64k}) is byte-identical to the monolithic scan's,
    for the single-config, multi-channel, and batched-sweep paths, at
    the default period and the period=1 stress point.
 4. **Span-log determinism.**  Under a seeded fault plan (kill+resume,
    transient x3, straggler re-issue) the orchestrator's JSONL span log
    is byte-identical across two independent runs, and the per-attempt
    fault records land durably in the manifest's shard diagnostics.
 5. **Chrome export.**  The Perfetto/chrome://tracing export of a real
    span log validates against the trace-event schema (required keys,
    known phases, balanced B/E nesting with synthetic closes flagged).
 6. **Tail latency (DESIGN.md §16, ISSUE 10).**  The in-scan latency
    histogram's mass reconciles exactly with ``Counters`` per
    (mechanism x controller), the window time-sums stay inside the
    bucket-implied bracket even under ``LAT_SUM_CAP`` saturation,
    percentile extraction is pinned against an exact-sort oracle within
    the declared bucket resolution, SLO violations are counted exactly,
    zero-request windows degrade to explicit NaN/0, counter events
    round-trip through the Chrome exporter, and the ``bench_diff``
    trajectory gate fails on an injected regression.
"""
import dataclasses
import importlib.util
import json
import pathlib
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dram, streaming, traces
from repro.core.timing import (SCHED_FCFS, SchedConfig, paper_config,
                               shared_static)
from repro.launch import orchestrator as orch_mod
from repro.obs import latency
from repro.obs.telemetry import WindowCollector, series_csv, window_table
from repro.obs.trace import (Tracer, chrome_from_jsonl, counter_events,
                             read_jsonl)
from repro.runtime.faults import FaultEvent, FaultPlan, InjectedKill

MECHS = ("base", "lldram", "lisa_villa", "figcache_slow", "figcache_fast",
         "figcache_ideal")
CACHED = ("lisa_villa", "figcache_slow", "figcache_fast", "figcache_ideal")
SCHEDS = {
    "fcfs": SCHED_FCFS,
    "frfcfs": SchedConfig(policy="frfcfs", queue_depth=8, starve_cap=4),
    "drain": SchedConfig(write_drain=True, drain_batch=4),
    "frfcfs+drain": SchedConfig(policy="frfcfs", queue_depth=8,
                                starve_cap=4, write_drain=True,
                                drain_batch=4),
}
PERIOD = 32


def _cfg(mech, **kw):
    return paper_config(mech, cache_rows=2, **kw) if mech in CACHED \
        else paper_config(mech, **kw)


def _reuse_trace(n=320):
    """Reuse-heavy pressure trace: small row space so the cached
    mechanisms produce nonzero row/cache-hit lanes worth pinning."""
    idx = np.arange(n)
    return dram.Trace(
        t_issue=jnp.asarray(idx * 16, jnp.int32),
        bank=jnp.asarray(idx % 3, jnp.int32),
        row=jnp.asarray((idx * 7) % 13, jnp.int32),
        col=jnp.asarray((idx * 13) % 128, jnp.int32),
        is_write=jnp.asarray(idx % 5 == 0, bool),
        core=jnp.asarray(idx % 8, jnp.int32),
    )


def _stream(tr, cfg, chunk=160, collector=None):
    return streaming.simulate_stream(streaming.iter_chunks(tr, chunk), cfg,
                                     telemetry=collector)


def _assert_counters_equal(ref, got, ctx):
    for name, x, y in zip(ref._fields, ref, got):
        assert np.array_equal(np.asarray(x), np.asarray(y)), (ctx, name)


# ---------------------------------------------------------------------------
# 1. bitwise invisibility, pinned against the pre-telemetry seed
# ---------------------------------------------------------------------------

# (acts_slow, acts_fast, reads, writes, reloc_blocks, wb_blocks, row_hits,
#  cache_hits, insertions, sum(lat_sum_ns), sum(req_cnt), t_end) of the
# telemetry-DISABLED chunked replay of _reuse_trace(), per combo —
# generated from the seed revision this PR grew from.
GOLDEN = {
    ('base', 'fcfs'): (320, 0, 256, 64, 0, 0, 0, 0, 0, 203846, 320, 28920),
    ('base', 'frfcfs'): (320, 0, 256, 64, 0, 0, 0, 0, 0, 203846, 320, 28920),
    ('base', 'drain'): (320, 0, 256, 64, 0, 0, 0, 0, 0, 204769, 320, 28968),
    ('base', 'frfcfs+drain'): (320, 0, 256, 64, 0, 0, 0, 0, 0, 204769, 320,
                               28968),
    ('lldram', 'fcfs'): (0, 320, 256, 64, 0, 0, 0, 0, 0, 132798, 320, 19118),
    ('lldram', 'frfcfs'): (0, 320, 256, 64, 0, 0, 0, 0, 0, 132798, 320,
                           19118),
    ('lldram', 'drain'): (0, 320, 256, 64, 0, 0, 0, 0, 0, 133624, 320,
                          19188),
    ('lldram', 'frfcfs+drain'): (0, 320, 256, 64, 0, 0, 0, 0, 0, 133624,
                                 320, 19188),
    ('lisa_villa', 'fcfs'): (296, 24, 256, 64, 37888, 7552, 0, 24, 296,
                             257761, 320, 36264),
    ('lisa_villa', 'frfcfs'): (296, 24, 256, 64, 37888, 7552, 0, 24, 296,
                               257761, 320, 36264),
    ('lisa_villa', 'drain'): (297, 23, 256, 64, 38016, 7552, 0, 23, 297,
                              257802, 320, 36262),
    ('lisa_villa', 'frfcfs+drain'): (297, 23, 256, 64, 38016, 7552, 0, 23,
                                     297, 257802, 320, 36262),
    ('figcache_slow', 'fcfs'): (295, 0, 256, 64, 4320, 752, 25, 50, 270,
                                299156, 320, 42932),
    ('figcache_slow', 'frfcfs'): (295, 0, 256, 64, 4320, 752, 25, 50, 270,
                                  299156, 320, 42932),
    ('figcache_slow', 'drain'): (291, 0, 256, 64, 4272, 768, 29, 53, 267,
                                 296726, 320, 42712),
    ('figcache_slow', 'frfcfs+drain'): (291, 0, 256, 64, 4272, 768, 29, 53,
                                        267, 296726, 320, 42712),
    ('figcache_fast', 'fcfs'): (270, 25, 256, 64, 4320, 752, 25, 50, 270,
                                291785, 320, 42012),
    ('figcache_fast', 'frfcfs'): (270, 25, 256, 64, 4320, 752, 25, 50, 270,
                                  291785, 320, 42012),
    ('figcache_fast', 'drain'): (267, 24, 256, 64, 4272, 768, 29, 53, 267,
                                 290152, 320, 41884),
    ('figcache_fast', 'frfcfs+drain'): (267, 24, 256, 64, 4272, 768, 29,
                                        53, 267, 290152, 320, 41884),
    ('figcache_ideal', 'fcfs'): (270, 25, 256, 64, 4320, 752, 25, 50, 270,
                                 185359, 320, 26656),
    ('figcache_ideal', 'frfcfs'): (270, 25, 256, 64, 4320, 752, 25, 50,
                                   270, 185359, 320, 26656),
    ('figcache_ideal', 'drain'): (267, 24, 256, 64, 4272, 768, 29, 53, 267,
                                  184511, 320, 26528),
    ('figcache_ideal', 'frfcfs+drain'): (267, 24, 256, 64, 4272, 768, 29,
                                         53, 267, 184511, 320, 26528),
}


def _fingerprint(cnt):
    return (int(cnt.acts_slow), int(cnt.acts_fast), int(cnt.reads),
            int(cnt.writes), int(cnt.reloc_blocks), int(cnt.wb_blocks),
            int(cnt.row_hits), int(cnt.cache_hits), int(cnt.insertions),
            int(np.asarray(cnt.lat_sum_ns).sum()),
            int(np.asarray(cnt.req_cnt).sum()), int(cnt.t_end))


@pytest.mark.parametrize("sid", list(SCHEDS), ids=list(SCHEDS))
@pytest.mark.parametrize("mech", MECHS)
def test_telemetry_invisible_and_counters_identical(mech, sid):
    """Disabled == seed golden; enabled == disabled, bitwise."""
    tr = _reuse_trace()
    off = _stream(tr, _cfg(mech, sched=SCHEDS[sid]))
    assert _fingerprint(off) == GOLDEN[(mech, sid)], (mech, sid)
    col = WindowCollector()
    on = _stream(tr, dataclasses.replace(_cfg(mech, sched=SCHEDS[sid]),
                                         telemetry=PERIOD), collector=col)
    _assert_counters_equal(off, on, (mech, sid))
    assert col.n_segments == 2
    assert len(col.series()["win_idx"]) > 0


# ---------------------------------------------------------------------------
# 2. conservation: window deltas sum to the final counters exactly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mech", ("base", "figcache_fast"))
def test_window_sums_match_counters(mech):
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg(mech), telemetry=PERIOD)
    col = WindowCollector()
    cnt = _stream(tr, cfg, chunk=64, collector=col)
    s = col.series()
    assert np.array_equal(s["win_idx"], np.arange(len(s["win_idx"])))
    assert int(s["w_reqs"].sum()) == int(cnt.reads) + int(cnt.writes)
    assert int(s["w_reads"].sum()) == int(cnt.reads)
    assert int(s["w_writes"].sum()) == int(cnt.writes)
    assert int(s["w_row_hits"].sum()) == int(cnt.row_hits)
    assert int(s["w_cache_hits"].sum()) == int(cnt.cache_hits)
    assert int(s["w_ins"].sum()) == int(cnt.insertions)
    assert int(s["w_reloc_blocks"].sum()) == int(cnt.reloc_blocks)
    assert int(s["w_lat_ns"].sum()) == int(np.asarray(cnt.lat_sum_ns).sum())
    assert int(s["w_bank_issues"].sum()) == int(s["w_reqs"].sum())


def test_windows_index_real_requests_not_noops():
    """No-op chunk fillers are telemetry-inert: a ragged chunking (tail
    padded with no-ops inside the stream) yields the same series as the
    exact chunking."""
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=PERIOD)
    exact, ragged = WindowCollector(), WindowCollector()
    _stream(tr, cfg, chunk=160, collector=exact)     # 320 = 2 x 160
    _stream(tr, cfg, chunk=96, collector=ragged)     # 320 = 3 x 96 + 32
    a, b = exact.series(), ragged.series()
    for k in a:
        assert np.array_equal(a[k], b[k]), k


# ---------------------------------------------------------------------------
# 3. chunk invariance of the window series
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("period", (PERIOD, 1), ids=("period32", "period1"))
def test_series_chunk_invariance(period):
    """chunk in {1, 7, 64k} == monolithic, byte for byte — including
    period=1 (every request closes a window: the ring-buffer spare-row
    edge case)."""
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=period)
    mono = WindowCollector()
    _stream(tr, cfg, chunk=1 << 16, collector=mono)
    assert mono.n_segments == 1
    ref = mono.series()
    # the §16 histogram rows and derived tail series ride the same pin
    assert "w_hist" in ref and "p50_ns" in ref and "p99_ns" in ref
    assert len(ref["win_idx"]) == -(-320 // period)
    for L in (1, 7):
        col = WindowCollector()
        _stream(tr, cfg, chunk=L, collector=col)
        got = col.series()
        for k in ref:
            assert np.array_equal(ref[k], got[k]), (period, L, k)


def test_series_chunk_invariance_multi_channel():
    apps = tuple(traces.app_params(n) for n in ("libquantum", "mcf"))
    tr = traces.build_trace(list(apps), 2, 384, 4)
    cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=PERIOD)
    mono, col = WindowCollector(), WindowCollector()
    _stream(tr, cfg, chunk=384, collector=mono)
    _stream(tr, cfg, chunk=100, collector=col)
    for c in range(2):
        a, b = mono.series(index=(c,)), col.series(index=(c,))
        for k in a:
            assert np.array_equal(a[k], b[k]), (c, k)


def test_series_chunk_invariance_sweep():
    """The batched path: every grid point's series survives chunking."""
    tr = _reuse_trace()
    cfgs = [dataclasses.replace(paper_config("figcache_fast", cache_rows=cr),
                                telemetry=PERIOD) for cr in (2, 64)]
    static = shared_static(cfgs)
    import jax
    pb = jax.tree.map(lambda *xs: jnp.stack(xs),
                      *[c.params() for c in cfgs])
    mono, col = WindowCollector(), WindowCollector()
    streaming.sweep_stream(streaming.iter_chunks(tr, 320), static, pb,
                           telemetry=mono)
    streaming.sweep_stream(streaming.iter_chunks(tr, 64), static, pb,
                           telemetry=col)
    for p in range(len(cfgs)):
        a, b = mono.series(index=(p,)), col.series(index=(p,))
        for k in a:
            assert np.array_equal(a[k], b[k]), (p, k)
    # capacity ordering sanity: more cache rows, no fewer total hits
    hits = [int(mono.series(index=(p,))["w_cache_hits"].sum())
            for p in range(len(cfgs))]
    assert hits[1] >= hits[0]


# ---------------------------------------------------------------------------
# telemetry API guardrails
# ---------------------------------------------------------------------------

def test_telemetry_guardrails():
    tr = _reuse_trace()
    cfg_tel = dataclasses.replace(_cfg("figcache_fast"), telemetry=PERIOD)
    cfg_off = _cfg("figcache_fast")
    # a collector without an enabled config is a silent no-op trap
    with pytest.raises(ValueError, match="telemetry"):
        _stream(tr, cfg_off, collector=WindowCollector())
    # wavefront execution has no telemetry path (yet)
    with pytest.raises(ValueError, match="wavefront"):
        streaming.simulate_stream(streaming.iter_chunks(tr, 160), cfg_tel,
                                  telemetry=WindowCollector(),
                                  wavefront_exec=True)
    # the dense reference body rejects telemetry instead of lying
    with pytest.raises(ValueError, match="dense"):
        dram.simulate_dense(tr, cfg_tel.static, cfg_tel.params())
    # a disabled static yields no frames, not empty ones
    _, frames = dram.resume(tr, cfg_off.static, cfg_off.params(),
                            dram.sim_init(cfg_off.static))
    assert frames is None


@pytest.mark.parametrize("tel", [0, PERIOD], ids=["off", "on"])
@pytest.mark.parametrize("batched", [False, True],
                         ids=["run_segment", "run_sweep_segment"])
def test_segment_entry_points_frames_and_one_chunk(batched, tel):
    """The segment entry points return ``(SimState, frames)``: frames are
    ``None`` exactly when telemetry is off, and a one-chunk replay's state
    equals the monolithic ``simulate`` / ``run_sweep`` bitwise."""
    tr = _reuse_trace()
    if batched:
        cfgs = [dataclasses.replace(paper_config("figcache_fast",
                                                 cache_rows=cr),
                                    telemetry=tel) for cr in (2, 64)]
        static = shared_static(cfgs)
        pb = jax.tree.map(lambda *xs: jnp.stack(xs),
                          *[c.params() for c in cfgs])
        state, frames = dram.run_sweep_segment(
            tr, static, pb, dram.sim_init(static, batch=len(cfgs)))
        ref = dram.run_sweep(tr, static, pb)
    else:
        cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=tel)
        state, frames = dram.run_segment(tr, cfg.static, cfg.params(),
                                         dram.sim_init(cfg.static))
        ref = dram.simulate(tr, cfg.static, cfg.params())
    if tel:
        assert frames is not None and state.tel is not None
        valid = np.asarray(frames.valid)
        assert valid.shape[:-1] == ((2,) if batched else ())
        assert valid.any(axis=-1).all()
    else:
        assert frames is None and state.tel is None
    _assert_counters_equal(ref, dram.finalize(state), (batched, tel))


def test_window_table_and_csv_render():
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=PERIOD)
    col = WindowCollector()
    _stream(tr, cfg, collector=col)
    s = col.series()
    tbl = window_table(s, max_rows=4)
    assert "hit%" in tbl and len(tbl.splitlines()) <= 6
    csv = series_csv(s)
    assert csv.splitlines()[0].startswith("win_idx")
    assert len(csv.splitlines()) == len(s["win_idx"]) + 1


# ---------------------------------------------------------------------------
# 4. span-log determinism under the fault matrix
# ---------------------------------------------------------------------------

def _traced_faulted_run(run_dir: pathlib.Path):
    """kill+resume, transient x3 (exp backoff), straggler re-issue — one
    orchestrated sweep, spans appended to one JSONL log."""
    run_dir.mkdir(parents=True, exist_ok=True)
    plan = orch_mod.ci_grid(chunk_len=128)
    fp = FaultPlan([
        FaultEvent(kind="transient", shard=0, times=3),
        FaultEvent(kind="kill", shard=1, segment=1, mode="raise"),
        FaultEvent(kind="slow", shard=4, segment=0, factor=8.0),
    ])
    log = run_dir / "span.jsonl"
    tracer = Tracer(str(log), clock=fp.clock.now)
    o = orch_mod.Orchestrator(plan, str(run_dir), fault_plan=fp,
                              backoff_s=0.05, max_retries=3, tracer=tracer)
    with pytest.raises(InjectedKill):
        o.run()
    o2 = orch_mod.Orchestrator(plan, str(run_dir), fault_plan=fp,
                               backoff_s=0.05, max_retries=3, tracer=tracer)
    assert o2.run() == {"done": len(plan.shards)}
    tracer.close()
    return o2, fp, log, plan


def test_span_log_byte_identical_and_manifest_events(tmp_path):
    o, fp, log, plan = _traced_faulted_run(tmp_path / "a")
    _, _, log2, _ = _traced_faulted_run(tmp_path / "b")
    assert log.read_bytes() == log2.read_bytes()
    assert len(log.read_bytes()) > 0

    # the exponential backoff ran on the logical clock, never wall time
    assert fp.clock.slept[:3] == [0.05, 0.1, 0.2]

    events = read_jsonl(str(log))
    names = {e["name"] for e in events}
    assert {"run", "shard", "checkpoint.save", "checkpoint.restore",
            "transient_retry", "straggler_reissue"} <= names
    # logical timestamps are monotone in emission order
    ts = [e["ts"] for e in events]
    assert all(a <= b for a, b in zip(ts, ts[1:]))
    # per-attempt shard spans carry worker + attempt + outcome
    shard_b = [e for e in events if e["name"] == "shard" and e["ph"] == "B"]
    assert all({"key", "worker", "attempt"} <= set(e["args"])
               for e in shard_b)
    retried = plan.shards[0].key
    assert sum(e["args"].get("key") == retried for e in shard_b) == 4

    # durable manifest diagnostics: the same attempts, without the tracer
    rec = o.manifest["shards"][retried]["events"]
    assert [r["kind"] for r in rec] == ["transient_retry"] * 3
    assert [r["attempt"] for r in rec] == [1, 2, 3]
    assert [r["backoff_s"] for r in rec] == [0.05, 0.1, 0.2]
    slow = o.manifest["shards"][plan.shards[4].key]["events"]
    assert any(r["kind"] == "straggler_reissue" and r["worker"] !=
               r["new_worker"] for r in slow)


def test_kill_leaves_open_span_resume_restores(tmp_path):
    """The killed run's log ends inside an open span (the death site);
    the resumed run records the checkpoint restore for the killed shard."""
    plan = orch_mod.ci_grid(chunk_len=128)
    fp = FaultPlan([FaultEvent(kind="kill", shard=1, segment=1,
                               mode="raise")])
    log = tmp_path / "span.jsonl"
    tracer = Tracer(str(log), clock=fp.clock.now)
    o = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                              backoff_s=0.0, tracer=tracer)
    with pytest.raises(InjectedKill):
        o.run()
    depth = sum(1 if e["ph"] == "B" else -1 if e["ph"] == "E" else 0
                for e in read_jsonl(str(log)))
    assert depth > 0                       # died inside >= 1 open span
    o2 = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                               backoff_s=0.0, tracer=tracer)
    assert o2.run() == {"done": len(plan.shards)}
    tracer.close()
    restores = [e for e in read_jsonl(str(log))
                if e["name"] == "checkpoint.restore"]
    assert any(e["args"]["shard"] == plan.shards[1].key for e in restores)


# ---------------------------------------------------------------------------
# 5. chrome trace-event export
# ---------------------------------------------------------------------------

def test_chrome_export_schema(tmp_path):
    _, _, log, _ = _traced_faulted_run(tmp_path / "run")
    dst = tmp_path / "span.chrome.json"
    n = chrome_from_jsonl(str(log), str(dst))
    doc = json.loads(dst.read_text())
    evs = doc["traceEvents"]
    assert n == len(evs) and n > 0
    assert doc["displayTimeUnit"] == "ms"
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        assert e["ph"] in ("B", "E", "i")
        if e["ph"] == "i":
            assert e["s"] == "t"
    # B/E strictly balanced: the exporter synthesizes closes for spans
    # the process died inside, and flags them
    depth = 0
    for e in evs:
        depth += 1 if e["ph"] == "B" else -1 if e["ph"] == "E" else 0
        assert depth >= 0
    assert depth == 0
    # the killed run died inside run+shard spans: the exporter must have
    # synthesized (and flagged) their closes
    assert sum(bool(e.get("args", {}).get("synthetic_close"))
               for e in evs if e["ph"] == "E") >= 1


def test_compile_contract_registered():
    """The telemetry sweep owns a declared jit budget (satellite: the
    sanitizer knows about the new entry points)."""
    from repro.analysis import contracts
    assert "obs.telemetry-sweep" in contracts.REGISTRY
    assert contracts.check_contract("obs.telemetry-sweep") == []


# ---------------------------------------------------------------------------
# 6. §16 latency histograms, percentiles, SLO accounting (ISSUE 10)
# ---------------------------------------------------------------------------

SLO_NS = 40  # sits inside _reuse_trace's latency range: violations nonzero


@pytest.mark.parametrize("sid", list(SCHEDS), ids=list(SCHEDS))
@pytest.mark.parametrize("mech", MECHS)
def test_hist_mass_reconciles_with_counters(mech, sid):
    """Histogram mass == Counters totals, exactly, per combo: the read
    plane is ``Counters.reads``, the write plane ``writes``, the per-core
    mass ``req_cnt`` — and every window row's mass is its request count."""
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg(mech, sched=SCHEDS[sid]),
                              telemetry=PERIOD, slo_ns=SLO_NS)
    col = WindowCollector()
    cnt = _stream(tr, cfg, collector=col)
    cum = col.cumulative()
    assert int(cum["hist"][0].sum()) == int(cnt.reads), (mech, sid)
    assert int(cum["hist"][1].sum()) == int(cnt.writes), (mech, sid)
    assert np.array_equal(cum["hist"].sum(axis=(0, 2)),
                          np.asarray(cnt.req_cnt, np.int64)), (mech, sid)
    s = col.series()
    assert np.array_equal(s["w_hist"].sum(axis=1), s["w_reqs"]), (mech, sid)
    # the exact SLO count is conserved window-by-window, like every lane
    assert int(s["w_slo"].sum()) == int(cum["slo"].sum()), (mech, sid)


def test_lat_sum_inside_hist_bracket():
    """Bucket-implied bounds bracket the exact window time-sum: with
    ``lower = sum(h * lo)`` and ``upper = sum(h * hi)``,
    ``min(CAP, lower) <= w_lat_ns <= min(CAP, upper)`` per window."""
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=PERIOD)
    col = WindowCollector()
    _stream(tr, cfg, collector=col)
    s = col.series()
    lo, hi = latency.bucket_bounds(dram.HIST_BUCKETS)
    lower = (s["w_hist"] * lo).sum(axis=1)
    upper = (s["w_hist"] * hi).sum(axis=1)
    assert np.all(np.minimum(lower, dram.LAT_SUM_CAP) <= s["w_lat_ns"])
    assert np.all(s["w_lat_ns"] <= np.minimum(upper, dram.LAT_SUM_CAP))


def test_lat_sum_saturation_keeps_hist_mass_exact():
    """Drive ``_telemetry_step`` directly into ``LAT_SUM_CAP`` saturation
    (unreachable from a real trace: the MSHR closed loop bounds per-request
    latency far below what 20 x 2^26 ns needs).  The time-sum lane clamps
    at the cap; the histogram, request count, and SLO lanes stay exact, so
    the bracket identity above still holds with the ``min(CAP, .)``."""
    tel = dram.init_telemetry()
    cur = dram._tel_pack(tel.win)
    scan = dram._TelScan(
        cur=cur, hist=tel.hist, slo=tel.slo,
        buf_scalars=jnp.zeros((4,) + cur.scalars.shape, jnp.int32),
        buf_banks=jnp.zeros((4,) + cur.bank_issues.shape, jnp.int32),
        buf_hist=jnp.zeros((4,) + cur.hist_win.shape, jnp.int32),
        n=jnp.int32(0))
    t, f, z = jnp.bool_(True), jnp.bool_(False), jnp.int32(0)
    big = jnp.int32(1 << 26)          # bucket 27 (the clip bucket)
    steps = 20                        # 20 * 2^26 > CAP = 2^30 - 1
    for i in range(steps):
        scan = dram._telemetry_step(
            scan, 1 << 20, real=t, bank=z, core=z, is_write=f, row_hit=f,
            hit=f, n_ins=z, moved=z, lat_ns=big, bus_wait=z, mshr_wait=z,
            slo_ns=jnp.int32(SLO_NS), step_id=jnp.int32(i))
    win = dram._tel_unpack(scan.cur)
    assert int(win.w_lat_ns) == dram.LAT_SUM_CAP        # saturated
    assert int(win.w_reqs) == steps                     # counts exact
    assert int(win.w_hist.sum()) == steps               # mass exact
    assert int(win.w_hist[dram.HIST_BUCKETS - 1]) == steps
    assert int(win.w_slo) == steps                      # 2^26 > SLO_NS
    assert int(scan.slo[0]) == steps
    lo, hi = latency.bucket_bounds(dram.HIST_BUCKETS)
    lower = int((np.asarray(win.w_hist) * lo).sum())
    upper = int((np.asarray(win.w_hist) * hi).sum())
    assert min(lower, dram.LAT_SUM_CAP) <= int(win.w_lat_ns) \
        <= min(upper, dram.LAT_SUM_CAP)


def test_bucket_scheme_host_device_agree():
    """``obs.latency.bucket_index`` is a bit-exact host mirror of the
    in-scan ``dram.hist_bucket``, and the published bounds partition."""
    vals = np.array([0, 1, 2, 3, 4, 7, 8, 127, 128, (1 << 27) - 1,
                     1 << 27, np.iinfo(np.int32).max], np.int32)
    dev = np.asarray(jax.vmap(dram.hist_bucket)(jnp.asarray(vals)))
    assert np.array_equal(dev, latency.bucket_index(vals))
    lo, hi = latency.bucket_bounds(dram.HIST_BUCKETS)
    assert lo[0] == hi[0] == 0                   # bucket 0 is exactly 0
    for b in range(1, dram.HIST_BUCKETS):
        assert int(latency.bucket_index(np.int64(lo[b]))) == b
        if b < dram.HIST_BUCKETS - 1:            # last bucket is the clip
            assert int(latency.bucket_index(np.int64(hi[b]))) == b
            assert lo[b + 1] == hi[b] + 1        # gap-free partition


def test_percentiles_vs_exact_sort_oracle():
    """period=1 makes every window one request, so ``w_lat_ns`` IS the
    exact per-request latency series: sort it and pin each extracted
    percentile inside its declared bucket bracket around the true
    nearest-rank value — and pin the SLO count against the same oracle."""
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=1,
                              slo_ns=SLO_NS)
    col = WindowCollector()
    _stream(tr, cfg, collector=col)
    s = col.series()
    lats = np.sort(s["w_lat_ns"])
    n = len(lats)
    cum = col.cumulative()
    hist = cum["hist"].sum(axis=(0, 1))
    assert int(hist.sum()) == n == 320
    for q in latency.QS:
        p = latency.percentile(hist, q)
        k = min(max(int(np.ceil(q * n)), 1), n)  # 1-based nearest rank
        oracle = int(lats[k - 1])
        assert p.lo <= oracle <= p.hi, (q, oracle, p)
        assert p.lo <= p.value <= p.hi, (q, p)
        assert abs(p.value - oracle) <= p.hi - p.lo  # declared resolution
    assert int(cum["slo"].sum()) == int((s["w_lat_ns"] > SLO_NS).sum())
    assert (s["w_lat_ns"] > SLO_NS).sum() > 0    # the oracle is non-trivial


def test_zero_request_window_guard():
    """A hand-crafted all-zero window row (impossible from the scan —
    closed windows always hold ``period`` requests, but hosts can feed
    synthetic frames) degrades explicitly: count rates 0.0, latency
    series NaN, no RuntimeWarning, and the table still renders."""
    zeros = lambda *sh: np.zeros(sh, np.int32)
    win = dram.TelemetryWindows(
        **{f: zeros(1) for f in dram._TEL_SCALARS},
        w_bank_issues=zeros(1, dram.GEOM.n_banks),
        w_hist=zeros(1, dram.HIST_BUCKETS))
    col = WindowCollector()
    col.add(dram.TelemetryFrame(valid=np.array([True]), win=win))
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        s = col.series()
    assert s["hit_rate"][0] == 0.0 and s["slo_rate"][0] == 0.0
    assert np.isnan(s["avg_lat_ns"][0])
    assert np.isnan(s["p50_ns"][0]) and np.isnan(s["p99_ns"][0])
    assert "nan" in window_table(s).lower()


def test_all_noop_segment_is_telemetry_inert():
    """An entire no-op segment spliced into the stream leaves the window
    series byte-identical (the zero-request-window guard's scan-side
    half: no-ops never open, advance, or close a window)."""
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=PERIOD,
                              slo_ns=SLO_NS)
    ref, got = WindowCollector(), WindowCollector()
    _stream(tr, cfg, chunk=160, collector=ref)
    segs = list(streaming.iter_chunks(tr, 160))
    segs.insert(1, streaming._noop_segment((160,)))
    streaming.simulate_stream(iter(segs), cfg, telemetry=got)
    a, b = ref.series(), got.series()
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(a[k], b[k], equal_nan=True), k
    assert np.array_equal(ref.cumulative()["hist"], got.cumulative()["hist"])


def test_chrome_counter_roundtrip(tmp_path):
    """Telemetry counter events survive the JSONL -> Chrome round trip
    bit-exactly, interleaved with spans, with NaN samples dropped."""
    tr = _reuse_trace()
    cfg = dataclasses.replace(_cfg("figcache_fast"), telemetry=PERIOD,
                              slo_ns=SLO_NS)
    col = WindowCollector()
    _stream(tr, cfg, collector=col)
    s = col.series()
    log = tmp_path / "tel.jsonl"
    tracer = Tracer(str(log))
    with tracer.span("replay"):
        n = counter_events(tracer, s, PERIOD)
    tracer.close()
    assert n > 0
    dst = tmp_path / "tel.chrome.json"
    chrome_from_jsonl(str(log), str(dst))
    evs = json.loads(dst.read_text())["traceEvents"]
    cs = [e for e in evs if e["ph"] == "C"]
    assert len(cs) == n
    assert {e["name"] for e in cs} >= {"telemetry/hit_rate",
                                       "telemetry/latency_ns",
                                       "telemetry/slo"}
    assert all(v == v for e in cs for v in e["args"].values())  # no NaN
    first = next(e for e in cs if e["name"] == "telemetry/hit_rate")
    assert first["args"]["hit_rate"] == float(s["hit_rate"][0])
    assert first["ts"] == float(s["win_idx"][0]) * PERIOD
    # spans still bracket correctly around the counter block
    assert evs[0]["ph"] == "B" and evs[-1]["ph"] == "E"


def _bench_diff_mod():
    p = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" \
        / "bench_diff.py"
    spec = importlib.util.spec_from_file_location("bench_diff_under_test", p)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_diff_flags_injected_regression(tmp_path):
    """The trajectory gate passes inside the band and fails past it —
    demonstrated on an injected regression (satellite: bench_diff)."""
    bd = _bench_diff_mod()
    base, fresh = tmp_path / "base", tmp_path / "fresh"
    base.mkdir(), fresh.mkdir()
    doc = {"hotloop_speedup": 6.5, "jits_capacity": 1}
    (base / "BENCH_hotloop.json").write_text(json.dumps(doc))
    # identical -> ok; a 20% dip sits inside the 50% band -> still ok
    for wobble in (1.0, 0.8):
        (fresh / "BENCH_hotloop.json").write_text(json.dumps(
            dict(doc, hotloop_speedup=doc["hotloop_speedup"] * wobble)))
        rows, fails = bd.diff(str(base), str(fresh))
        assert fails == [], wobble
        assert any(r["verdict"] == "ok" for r in rows)
    # past the band + a jit-count bump -> both flagged, CLI exits 1
    (fresh / "BENCH_hotloop.json").write_text(json.dumps(
        dict(doc, hotloop_speedup=1.0, jits_capacity=2)))
    rows, fails = bd.diff(str(base), str(fresh))
    assert len(fails) == 2
    assert {r["metric"] for r in rows if r["verdict"] == "FAIL"} == \
        {"hotloop_speedup", "jits_capacity"}
    assert bd.main(["--baseline", str(base), "--fresh", str(fresh)]) == 1
    # absent files are skipped with a note, never a failure
    assert all(r["verdict"].startswith("skip")
               for r in rows if r["file"] != "BENCH_hotloop.json")


def test_tail_latency_contract_registered():
    """The §16 tail-latency pipeline owns a declared jit budget
    (satellite: the sanitizer knows the extended entry points)."""
    from repro.analysis import contracts
    assert "obs.tail-latency" in contracts.REGISTRY
    assert contracts.check_contract("obs.tail-latency") == []
