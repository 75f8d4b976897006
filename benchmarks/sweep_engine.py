"""Sweep-engine microbenchmark: jit counts, us-per-config and hot-loop
steps/sec, before vs after.

"Before" reproduces the seed's dispatch: every ``MechConfig`` point gets its
own freshly-jitted scan (params baked into the compilation), so a grid of N
configs costs N compilations.  "After" is the sweep engine: the same grid
shares one static structure, so ``dram.run_sweep`` compiles ONE scan and
vmaps it over the stacked ``MechParams`` batch (DESIGN.md §3).

Three grids are measured and ASSERTED to batch into a single compilation:

 * timings grid — insert_threshold x benefit_bits (pure ``MechParams``
   knobs since PR 1);
 * capacity grid — ``cache_rows`` (fig 12's knob), which changes the FTS
   slot count;
 * segment grid — ``seg_blocks`` (fig 13's knob), which changes
   ``segs_per_row``.

The last two only batch because the FTS is shape-polymorphic: arrays are
padded to the grid's shared bucket (``timing.shared_static``) and the
effective ``n_slots`` / ``segs_per_row`` ride traced in ``MechParams``.
Each batched run is also cross-checked bitwise against per-config
*unpadded* runs (``dram.run_channel_exact``: FTS allocated at exactly
n_slots), so the 1-compilation behavior is not bought with a semantics
change.

The HOT-LOOP section (DESIGN.md §9) measures per-step cost on the default
fig-12 capacity grid: the dense reference body (``dram.simulate_dense``)
re-derives every FTS decision from scratch each step (the pre-aggregate
loop), the fused scan body (``dram.run_sweep``) updates carried
aggregates with per-(bank, slot) scalar writes.  Both are
bitwise-identical (``tests/test_hotloop.py``);
steps/sec and the speedup land in ``BENCH_hotloop.json`` so the perf
trajectory is recorded per PR (CI uploads it to the job summary).

The WAVEFRONT section (DESIGN.md §10) measures the bank-wavefront scan
(``core/sched/wavefront.py``) against the serial fused scan on the same
fig-12 grid — single-stream regime asserted >= 2x, batched regime
recorded — into ``BENCH_wavefront.json`` (also published by CI).

The TRACEGEN section (DESIGN.md §11) measures the device workload engine
(``core/workload/``) against the numpy oracle generator on the 1M-request
8-core acceptance workload — asserted >= 10x reqs/sec (2x ``--quick``
tripwire) — into ``BENCH_tracegen.json`` (also published by CI).

The STREAMING section (DESIGN.md §13) measures the chunked segment-carried
replay against the monolithic sweep on the fig-12 capacity grid — asserted
>= 0.9x steps/sec at chunk >= 64k (looser ``--quick`` tripwire at toy
chunk sizes, where per-segment dispatch overhead dominates) — plus, in
full mode, the capability the monolithic path cannot offer at all: a
>4M-request epoch-synthesized stream (beyond the audit's declared
``TRACE_LEN_BOUND`` = 1M monolithic budget) replayed to completion with
O(chunk) device trace residency.  Codec compression on the measured trace
rides along.  Written to ``BENCH_streaming.json`` (also published by CI).

The SHARDED SWEEP section (DESIGN.md §14) measures the fault-tolerant
orchestrator (``launch/orchestrator.py``) against the monolithic
``simulator.sweep_traces`` on the fig-12 x fig-13 cross grid: the
orchestration tax (manifest + per-segment checkpoints + mesh placement)
is recorded as a steps/sec ratio, the orchestrated counters are asserted
bitwise equal to the monolithic oracle, a kill-and-resume pass records
the resume overhead (also bitwise-checked), and the whole orchestrated
run is held to the ``orchestrator.shard-sweep`` compile contract (at most
ONE fresh compilation).  Written to ``BENCH_shardsweep.json`` (also
published by CI).

Compilations are counted via ``dram.JIT_TRACE_LOG`` (the scan body logs one
entry per trace).
"""
from __future__ import annotations

import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.analysis import contracts
from repro.core import dram, streaming, traces, workload
from repro.core.timing import paper_config, shared_static

# Grids and jit budgets live in repro.analysis.contracts (the compile-
# contract registry) so this benchmark and the analyzer can't drift apart;
# the aliases keep the benchmark-side names stable.
GRID = contracts.TIMINGS_GRID
CAPACITY_GRID = contracts.CAPACITY_GRID
SEGMENT_GRID = contracts.SEGMENT_GRID
# the default fig-12 capacity grid: the hot-loop steps/sec workload
HOTLOOP_GRID = [dict(cache_rows=cr) for cr in (4, 8, 16, 32, 64)]

BENCH_JSON = "BENCH_hotloop.json"
BENCH_WAVE_JSON = "BENCH_wavefront.json"
BENCH_TRACEGEN_JSON = "BENCH_tracegen.json"
BENCH_STREAM_JSON = "BENCH_streaming.json"
BENCH_SHARD_JSON = "BENCH_shardsweep.json"
# the wavefront scheduler's bank-level-parallelism window (DESIGN.md §10)
WAVE_LOOKAHEAD = 32


def _stack_params(cfgs):
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[c.params() for c in cfgs])


def _assert_counters_equal(ref, got, ctx):
    for name, x, y in zip(ref._fields, ref, got):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"sweep engine diverged from per-config run: {ctx} field {name}"


def _shape_grid_jits(tr, grid_kw, label):
    """Batch one shape-changing grid; return its jit count after asserting
    bitwise equality with per-config unpadded runs."""
    cfgs = [paper_config("figcache_fast", **kw) for kw in grid_kw]
    static = shared_static(cfgs)
    j0 = dram.jit_trace_count()
    after = jax.block_until_ready(
        dram.run_sweep(tr, static, _stack_params(cfgs)))
    jits = dram.jit_trace_count() - j0
    for i, cfg in enumerate(cfgs):
        ref = dram.run_channel_exact(tr, cfg)
        got = jax.tree.map(lambda a, i=i: a[i], after)
        _assert_counters_equal(ref, got, f"{label}[{i}]")
    return jits


@functools.partial(jax.jit, static_argnums=(1,))
def _sweep_dense(trace, static, params_batch):
    """The dense reference body vmapped over a params batch: the hot-loop
    baseline ``run_sweep`` is measured against."""
    return jax.vmap(lambda p: dram.simulate_dense(trace, static, p))(
        params_batch)


def _hotloop_report(tr):
    """steps/sec of the fused vs dense scan bodies on the fig-12 capacity
    grid (one compiled scan each), plus their bitwise cross-check."""
    cfgs = [paper_config("figcache_fast", **kw) for kw in HOTLOOP_GRID]
    static = shared_static(cfgs)
    batch = _stack_params(cfgs)
    n_steps = len(cfgs) * int(np.asarray(tr.t_issue).size)
    reps = 1 if common.IS_QUICK else 3
    out, rate, jits = {}, {}, {}
    for body, sweep in (("dense", _sweep_dense), ("fused", dram.run_sweep)):
        j0 = dram.jit_trace_count()
        out[body] = jax.block_until_ready(
            sweep(tr, static, batch))                    # warm/compile
        jits[body] = dram.jit_trace_count() - j0
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(sweep(tr, static, batch))
        rate[body] = n_steps * reps / (time.time() - t0)
    _assert_counters_equal(out["dense"], out["fused"], "hotloop")
    speedup = rate["fused"] / rate["dense"]
    # the DESIGN.md §9 acceptance bar is >= 2x; under --quick CI (one rep,
    # shared noisy runner) enforce a looser tripwire so a real regression
    # to parity still fails loudly without flaking on machine noise
    floor = 1.3 if common.IS_QUICK else 2.0
    assert speedup >= floor, \
        f"hot-loop speedup {speedup:.2f}x below the {floor}x floor"
    return {
        "steps_per_sec_dense": round(rate["dense"]),
        "steps_per_sec_fused": round(rate["fused"]),
        "hotloop_speedup": round(rate["fused"] / rate["dense"], 2),
        "jits_hotloop_dense": jits["dense"],
        "jits_hotloop_fused": jits["fused"],
        "n_steps_per_rep": n_steps,
    }


def _wavefront_report(tr):
    """Wavefront vs serial fused scan on the fig-12 capacity grid
    (DESIGN.md §10), written to ``BENCH_wavefront.json``.

    Two regimes, both bitwise-checked against the serial oracle on the
    SAME (linearized wave) service order:

     * ``single`` — the single-stream regime the wave engine targets (one
       config, one channel: the ``run_single_core`` / interactive path,
       where the serial scan is per-step dispatch-bound).  Every fig-12
       grid point runs serially and wavefront; the asserted floor is the
       acceptance bar (>= 2x requests/sec; ~3x measured).
     * ``batched`` — the sweep-engine dispatch (params x channel vmap).
       Here the serial fused scan is already at the CPU's gather/scatter
       throughput floor, so waves cannot add SIMD; the ratio is recorded
       (expected < 1) to document the regime split honestly.
    """
    from repro.core.sched import wavefront

    cfgs = [paper_config("figcache_fast", **kw) for kw in HOTLOOP_GRID]
    static = shared_static(cfgs)
    reps = 1 if common.IS_QUICK else 3

    def rate(fn, n_req):
        jax.block_until_ready(fn())          # warm/compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.time()
            jax.block_until_ready(fn())
            best = min(best, time.time() - t0)
        return n_req / best

    # ---- single-stream regime: per-config, channel 0 of the workload ---
    tr1 = jax.tree.map(lambda x: jnp.asarray(x)[0], tr)
    wtr1 = wavefront.form_waves(tr1, lookahead=WAVE_LOOKAHEAD)
    lin1 = wavefront.linearize_waves(wtr1)
    n1 = int(np.asarray(lin1.t_issue).size)
    t_serial = t_wave = 0.0
    jits_wave = 0
    for cfg in cfgs:
        p = cfg.params()
        serial = jax.block_until_ready(dram._simulate_jit(lin1, static, p))
        # bracket ONLY the wave-scan calls: the serial warm-up above may
        # itself compile (fresh single-channel trace shape) and must not
        # count against the wavefront record
        j0 = dram.jit_trace_count()
        wave = jax.block_until_ready(
            wavefront._simulate_waves_jit(wtr1, static, p))
        jits_wave += dram.jit_trace_count() - j0
        _assert_counters_equal(serial, wave, f"wavefront[{cfg.cache_rows}]")
        t_serial += n1 / rate(lambda: dram._simulate_jit(lin1, static, p),
                              n1)
        t_wave += n1 / rate(
            lambda: wavefront._simulate_waves_jit(wtr1, static, p), n1)
    n_single = len(cfgs) * n1
    single = {
        "steps_per_sec_serial": round(n_single / t_serial),
        "steps_per_sec_wave": round(n_single / t_wave),
        "wavefront_speedup": round(t_serial / t_wave, 2),
    }
    # DESIGN.md §10 acceptance bar: >= 2x requests/sec in the single-stream
    # regime; --quick CI (one rep, shared noisy runner) gets a looser
    # tripwire so a regression to parity still fails without flaking
    floor = 1.2 if common.IS_QUICK else 2.0
    assert single["wavefront_speedup"] >= floor, \
        f"wavefront speedup {single['wavefront_speedup']}x below {floor}x"

    # ---- batched regime (recorded, not asserted — see docstring) --------
    batch = _stack_params(cfgs)
    wtr = wavefront.form_waves(tr, lookahead=WAVE_LOOKAHEAD)
    lin = wavefront.linearize_waves(wtr)
    nb = len(cfgs) * int(np.asarray(lin.t_issue).size)
    serial = jax.block_until_ready(dram.run_sweep(lin, static, batch))
    j0 = dram.jit_trace_count()
    wave = jax.block_until_ready(
        wavefront.run_sweep_waves(wtr, static, batch))
    jits_wave += dram.jit_trace_count() - j0
    _assert_counters_equal(serial, wave, "wavefront-batched")
    rs = rate(lambda: dram.run_sweep(lin, static, batch), nb)
    rw = rate(lambda: wavefront.run_sweep_waves(wtr, static, batch), nb)
    stats = wavefront.wave_stats(wtr)
    return {
        **single,
        "batched_steps_per_sec_serial": round(rs),
        "batched_steps_per_sec_wave": round(rw),
        "batched_wavefront_ratio": round(rw / rs, 2),
        "wave_mean_fill": stats["mean_fill"],
        "wave_width": stats["width"],
        "wave_lookahead": WAVE_LOOKAHEAD,
        "jits_wavefront": jits_wave,
    }


def _tracegen_report():
    """Trace-generation throughput: device workload engine vs the numpy
    oracle on an 8-core multiprogrammed mix (DESIGN.md §11), written to
    ``BENCH_tracegen.json``.

    Full mode builds the acceptance-bar workload — a 1M-request 8-core
    mix (4 channels x 250k) — and asserts the device path is >= 10x the
    numpy ``traces.build_trace`` reqs/sec; ``--quick`` CI shrinks the
    trace (device dispatch overhead dominates there) and enforces a 2x
    tripwire so a regression to parity still fails loudly.  Device
    timings exclude the one-time generator compile (which is also
    counted: one per static structure, asserted <= 1 for the re-run).
    """
    name, frac, apps = traces.eight_core_workloads()[15]   # 100% intensive
    per_channel = 2048 if common.IS_QUICK else 250_000
    n = 4 * per_channel
    spec = workload.spec_from_apps(apps, 4, per_channel, seed=2)
    jax.block_until_ready(workload.generate(spec))         # compile + warm
    reps = 1 if common.IS_QUICK else 3
    j0 = workload.gen_trace_count()
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        jax.block_until_ready(workload.generate(spec))
        best = min(best, time.time() - t0)
    jits = workload.gen_trace_count() - j0
    assert jits <= 1, f"warm trace generation retraced {jits}x"
    t0 = time.time()
    tr_np = traces.build_trace(apps, 4, per_channel, 2)
    t_np = time.time() - t0
    rate_dev, rate_np = n / best, n / t_np
    speedup = rate_dev / rate_np
    floor = 2.0 if common.IS_QUICK else 10.0
    assert speedup >= floor, \
        f"device tracegen {speedup:.1f}x below the {floor}x floor"
    return {
        "tracegen_reqs": n,
        "reqs_per_sec_numpy": round(rate_np),
        "reqs_per_sec_device": round(rate_dev),
        "tracegen_speedup": round(speedup, 1),
        "tracegen_quick": common.IS_QUICK,
    }


def _long_stream_demo():
    """Full mode only: replay a >4M-request epoch-synthesized stream —
    larger than the monolithic scan's declared ``TRACE_LEN_BOUND``
    capacity — to completion through the chunked path (DESIGN.md §13)."""
    from repro.analysis.jaxpr_audit import TRACE_LEN_BOUND
    per_channel, epochs = 65_536, 16
    total = 4 * per_channel * epochs          # 4.19M request slots
    assert total > TRACE_LEN_BOUND
    # small interarrival keeps the 4M-request clock far below the int32
    # tick budget even after 16 carried epoch offsets
    spec = workload.preset("stream", n_cores=8, n_channels=4,
                           per_channel=per_channel, seed=11,
                           interarrival_ns=4.0)
    cfg = paper_config("figcache_fast")
    t0 = time.time()
    cnt = jax.block_until_ready(streaming.simulate_stream(
        workload.generate_stream(spec, epochs), cfg))
    dt = time.time() - t0
    served = int(np.asarray(cnt.reads).sum() + np.asarray(cnt.writes).sum())
    return {
        "long_stream_reqs": total,
        "long_stream_served": served,
        "long_stream_reqs_per_sec": round(total / dt),
        "long_stream_exceeds_monolithic_bound": total > TRACE_LEN_BOUND,
    }


def _streaming_report(tr_small):
    """Chunked streamed replay vs the monolithic sweep on the fig-12
    capacity grid (DESIGN.md §13), written to ``BENCH_streaming.json``.

    Full mode replays a 4x128k-channel workload at chunk 64k and asserts
    >= 0.9x monolithic steps/sec — the price of chunking must stay inside
    JAX's async-dispatch overlap.  ``--quick`` CI replays the small shared
    trace at chunk 1k, where per-segment dispatch overhead is the whole
    story, and enforces a 0.4x tripwire so a real regression (e.g. a
    device sync per segment) still fails loudly."""
    cfgs = [paper_config("figcache_fast", **kw) for kw in CAPACITY_GRID]
    static = shared_static(cfgs)
    batch = _stack_params(cfgs)
    if common.IS_QUICK:
        tr, chunk, floor = tr_small, 1024, 0.4
    else:
        _name, _frac, apps = traces.eight_core_workloads()[15]
        tr = traces.build_trace(apps, 4, 131_072, 2)
        chunk, floor = 65_536, 0.9
    T = int(np.asarray(tr.t_issue).shape[-1])
    n_steps = len(cfgs) * int(np.asarray(tr.t_issue).size)
    reps = 1 if common.IS_QUICK else 3

    def mono():
        return dram.run_sweep(tr, static, batch)

    def chunked():
        return streaming.sweep_stream(
            streaming.iter_chunks(tr, chunk), static, batch)

    j0 = dram.jit_trace_count()
    ref = jax.block_until_ready(mono())           # warm both paths
    got = jax.block_until_ready(chunked())
    jits = dram.jit_trace_count() - j0
    _assert_counters_equal(ref, got, "streaming")
    rate = {}
    for label, fn in (("monolithic", mono), ("chunked", chunked)):
        t0 = time.time()
        for _ in range(reps):
            jax.block_until_ready(fn())
        rate[label] = n_steps * reps / (time.time() - t0)
    rel = rate["chunked"] / rate["monolithic"]
    assert rel >= floor, \
        f"chunked replay {rel:.2f}x of monolithic at chunk={chunk}, " \
        f"below the {floor}x floor"

    # codec compression on the measured trace's channel 0 (realistic page
    # reuse; adversarial no-reuse traces can inflate instead — the chunk
    # cluster table is a bet on locality, documented in DESIGN.md §13)
    ch0 = jax.tree.map(lambda a: np.asarray(a)[0], tr)
    enc = traces.encode_trace(ch0, chunk_len=min(traces.CHUNK_LEN, T))
    raw = sum(np.asarray(x).nbytes for x in ch0)
    report = {
        "streaming_chunk_len": chunk,
        "streaming_reqs": int(np.asarray(tr.t_issue).size),
        "steps_per_sec_monolithic": round(rate["monolithic"]),
        "steps_per_sec_chunked": round(rate["chunked"]),
        "streaming_relative": round(rel, 3),
        "streaming_floor": floor,
        "jits_streaming_warm": jits,
        "codec_raw_bytes": raw,
        "codec_encoded_bytes": traces.encoded_nbytes(enc),
        "codec_ratio": round(raw / traces.encoded_nbytes(enc), 2),
        "streaming_quick": common.IS_QUICK,
    }
    if not common.IS_QUICK:
        report.update(_long_stream_demo())
    return report


def _shardsweep_report():
    """Sharded orchestrated sweep vs the monolithic engine on the fig-12 x
    fig-13 cross grid (DESIGN.md §14), written to ``BENCH_shardsweep.json``.

    The orchestrator's value is durability, not speed — so the recorded
    ``shardsweep_relative`` is the honest price of the manifest writes,
    per-segment checkpoints, and mesh placement, while the bitwise check
    proves the price buys no semantics change.  The kill-and-resume pass
    measures a run killed mid-shard and resumed (``resume_overhead`` =
    killed+resumed wall / uninterrupted wall; the checkpointed prefix is
    reused, so this stays near 1 + one shard's re-tail).  The whole
    orchestrated run must fit the ``orchestrator.shard-sweep`` compile
    contract: sharding never splits or merges compilation units."""
    import tempfile

    from repro.core import simulator
    from repro.launch import orchestrator
    from repro.launch.mesh import make_sweep_mesh
    from repro.runtime.faults import FaultEvent, FaultPlan, InjectedKill

    if common.IS_QUICK:
        grid = [dict(cache_rows=cr, seg_blocks=sb)
                for cr in (8, 32) for sb in (16, 64)]
        per_channel, chunk = 2048, 1024
    else:
        grid = [dict(**c, **s)
                for c in CAPACITY_GRID for s in SEGMENT_GRID]
        per_channel, chunk = 16_384, 4096
    cfgs = [paper_config("figcache_fast", **kw) for kw in grid]
    specs = [workload.preset("zipf_reuse", n_cores=2, n_channels=2,
                             per_channel=per_channel, seed=21),
             workload.preset("stream", n_cores=2, n_channels=2,
                             per_channel=per_channel, seed=22)]
    n_steps = len(cfgs) * len(specs) * 2 * per_channel

    oracle = simulator.sweep_traces(specs, cfgs, chunk_len=chunk)  # warm
    t0 = time.time()
    simulator.sweep_traces(specs, cfgs, chunk_len=chunk)
    t_mono = time.time() - t0

    plan = orchestrator.make_plan(specs, cfgs, chunk_len=chunk)
    j0 = dram.jit_trace_count()
    with tempfile.TemporaryDirectory() as d:               # warm + contract
        orch = orchestrator.Orchestrator(plan, d, backoff_s=0.0)
        counts = orch.run()
        assert counts == {"done": len(plan.shards)}, counts
        got = orch.counters_by_config()
    jits = dram.jit_trace_count() - j0
    contracts.assert_jit_budget("orchestrator.shard-sweep", jits)
    assert len(got) == len(specs) * len(cfgs)
    for (w, i), cnt in got.items():
        _assert_counters_equal(oracle[w][i].counters, cnt,
                               f"shardsweep[{w},{i}]")
    with tempfile.TemporaryDirectory() as d:               # timed, warm
        t0 = time.time()
        orchestrator.Orchestrator(plan, d, backoff_s=0.0).run()
        t_orch = time.time() - t0

    # ---- kill mid-shard, resume in a "new process", same bits -------------
    fp = FaultPlan([FaultEvent(kind="kill", shard=0, segment=1,
                               mode="raise")])
    with tempfile.TemporaryDirectory() as d:
        t0 = time.time()
        try:
            orchestrator.Orchestrator(plan, d, fault_plan=fp,
                                      backoff_s=0.0).run()
            raise AssertionError("injected kill did not fire")
        except InjectedKill:
            pass
        orch2 = orchestrator.Orchestrator(plan, d, fault_plan=fp,
                                          backoff_s=0.0)
        assert orch2.run() == {"done": len(plan.shards)}
        t_killed = time.time() - t0
        got2 = orch2.counters_by_config()
    assert set(got2) == set(got)
    for k, cnt in got2.items():
        _assert_counters_equal(got[k], cnt, f"shardsweep-resume{k}")

    P = max(len(s.cfg_idxs) for s in plan.shards)
    mesh = make_sweep_mesh(P, 2)
    return {
        "shardsweep_configs": len(cfgs),
        "shardsweep_workloads": len(specs),
        "shardsweep_n_shards": len(plan.shards),
        "shardsweep_chunk_len": chunk,
        "shardsweep_steps": n_steps,
        "n_devices": len(jax.devices()),
        "mesh_shape": "x".join(f"{k}={v}" for k, v in mesh.shape.items()),
        "steps_per_sec_monolithic": round(n_steps / t_mono),
        "steps_per_sec_orchestrated": round(n_steps / t_orch),
        "shardsweep_relative": round(t_mono / t_orch, 3),
        "jits_shardsweep": jits,
        "resume_overhead": round(t_killed / t_orch, 2),
        "shardsweep_quick": common.IS_QUICK,
    }


def run():
    cfgs = [paper_config("figcache_fast", **kw) for kw in GRID]
    static = shared_static(cfgs)
    tr, _apps = common.eight_trace(common.WL_IDX[100][1], per_channel=2048)

    # ---- before: per-config fresh jit (seed behavior) ---------------------
    j0 = dram.jit_trace_count()
    t0 = time.time()
    before = []
    for cfg in cfgs:
        p = cfg.params()
        # params baked into the closure == one distinct compilation per
        # config point, exactly like the seed's make_step(cfg)
        f = jax.jit(lambda t, p=p: dram.simulate(t, static, p))  # repro: allow(jit-closure-cache)
        before.append(jax.block_until_ready(f(tr)))
    t_before = time.time() - t0
    jits_before = dram.jit_trace_count() - j0

    # ---- after: one compiled scan, vmapped over the params batch ----------
    batch = _stack_params(cfgs)
    j1 = dram.jit_trace_count()
    t0 = time.time()
    after = jax.block_until_ready(dram.run_sweep(tr, static, batch))
    t_after = time.time() - t0
    jits_after = dram.jit_trace_count() - j1

    # same physics in both modes, bit for bit
    for i, cnt in enumerate(before):
        _assert_counters_equal(cnt, jax.tree.map(lambda x, i=i: x[i], after),
                               f"timings[{i}]")

    # ---- shape-changing grids: capacity (fig 12), segment size (fig 13) ---
    jits_capacity = _shape_grid_jits(tr, CAPACITY_GRID, "capacity")
    jits_segment = _shape_grid_jits(tr, SEGMENT_GRID, "segment")
    # the acceptance bar for the padded-FTS model: at most ONE compiled
    # scan per shape-changing grid — never one per shape point.  0 means an
    # earlier dispatch with matching (static, trace, batch) shapes was
    # reused (e.g. fig12's grid in a full run.py sweep), which is the same
    # property in an even stronger form.  The budgets are the declared
    # compile contracts (repro.analysis.contracts), shared with the
    # analyzer CLI and the pytest gate.
    contracts.assert_jit_budget("sweep.timings", jits_after)
    contracts.assert_jit_budget("sweep.capacity", jits_capacity)
    contracts.assert_jit_budget("sweep.segment", jits_segment)

    # ---- hot loop: fused vs dense steps/sec (DESIGN.md §9) ----------------
    hot = _hotloop_report(tr)

    # ---- wavefront vs serial steps/sec (DESIGN.md §10) --------------------
    wavefront = _wavefront_report(tr)
    with open(BENCH_WAVE_JSON, "w") as f:
        json.dump(wavefront, f, indent=2, sort_keys=True)
        f.write("\n")

    # ---- trace generation: device workload engine vs numpy (§11) ----------
    tracegen = _tracegen_report()
    with open(BENCH_TRACEGEN_JSON, "w") as f:
        json.dump(tracegen, f, indent=2, sort_keys=True)
        f.write("\n")

    # ---- chunked streaming vs monolithic replay (§13) ---------------------
    stream = _streaming_report(tr)
    with open(BENCH_STREAM_JSON, "w") as f:
        json.dump(stream, f, indent=2, sort_keys=True)
        f.write("\n")

    # ---- fault-tolerant sharded orchestration (§14) -----------------------
    shard = _shardsweep_report()
    with open(BENCH_SHARD_JSON, "w") as f:
        json.dump(shard, f, indent=2, sort_keys=True)
        f.write("\n")

    n = len(cfgs)
    summary = {
        "n_configs": n,
        "jits_before": jits_before,
        "jits_after": jits_after,
        "jits_capacity": jits_capacity,
        "jits_segment": jits_segment,
        "us_per_config_before": round(t_before / n * 1e6),
        "us_per_config_after": round(t_after / n * 1e6),
        "wall_speedup": round(t_before / max(t_after, 1e-9), 2),
        **hot,
        "wavefront_speedup": wavefront["wavefront_speedup"],
        "tracegen_speedup": tracegen["tracegen_speedup"],
        "streaming_relative": stream["streaming_relative"],
        "shardsweep_relative": shard["shardsweep_relative"],
        "resume_overhead": shard["resume_overhead"],
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True)
        f.write("\n")
    rows = [summary]
    return rows, summary


if __name__ == "__main__":
    print(run()[1])
