"""Vectorized, cycle-approximate DRAM bank/row-buffer/FIGCache simulator.

The JAX analogue of the paper's Ramulator setup (§7): a ``jax.lax.scan`` over a
per-channel memory-request trace, ``jax.vmap``-ed over channels.  Per-bank
state = open row + busy-until timestamp + an FTS (``core/fts.py``).  Six
mechanisms (``core/timing.MechConfig``): base, lisa_villa, figcache_slow,
figcache_fast, figcache_ideal, lldram.  The relocation timing model (RELOC
column transfers through the global row buffer, overlapped destination ACTs,
distance independence) follows the paper's §5 FIGARO substrate; the caching
decisions layered on top (lookup/insert/evict) are §6 FIGCache, implemented
by ``core/fts.py``.

Modeling abstractions (documented in DESIGN.md §7):
 * per-bank in-order service with bank-level parallelism (a request waits only
   on its own bank); the *service order itself* is a first-class knob since
   PR 4 — ``core/sched/policies.py`` (DESIGN.md §10) reorders the trace
   under FCFS / FR-FCFS / write-drain controllers before this scan runs,
   and ``core/sched/wavefront.py`` retires whole distinct-bank waves per
   scan step using the same per-request decision function
   (``make_decision_fn``);
 * the processor is represented by the trace arrival times + an
   MLP-weighted latency→CPI conversion in ``simulator.py``.

Timestamps are int32 ticks (1/8 ns).  Latency accumulators are int32 ns.

Sweep engine (DESIGN.md §3): the scan body is built from the *static* half of
a config only (``timing.StaticConfig``); every numeric knob, *including the
effective FTS geometry* ``n_slots``/``segs_per_row``, arrives as a traced
``timing.MechParams`` pytree and the FTS masks itself to the live slot
prefix.  One compilation therefore serves every config sharing a static
structure, and ``run_sweep`` vmaps the very same scan over a stacked params
batch so a whole config grid executes as one XLA program.

Hot loop (DESIGN.md §9): the default ``"fused"`` scan body performs only the
work the step's outcome needs — the FTS decisions reduce *carried
aggregates* (``fts.row_sum`` / free-stack) instead of re-deriving them, and
every state change is a per-leaf ``(bank, slot)`` scalar scatter guarded by
value-level selects.  The pre-aggregate body survives as the ``"dense"``
variant (whole-FTS gathers, tree-wide selects, full write-backs): it is the
bitwise reference ``tests/test_hotloop.py`` pins the fused loop against and
the baseline ``benchmarks/sweep_engine.py`` measures steps/sec speedup over.
``StaticConfig.fts_kernel`` further routes the remaining max_slots-wide
reductions (tag compare + victim argmin) through the fused Pallas
``kernels/fts_lookup`` op (pure-JAX fallback off-TPU).
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import fts as fts_lib
from repro.core.timing import (DDR4, GEOM, DRAMGeometry, DRAMTimings,
                               MechConfig, MechParams, StaticConfig)
from repro.kernels.fts_lookup.ops import fts_lookup_op


class Trace(NamedTuple):
    """Per-channel request stream in SERVICE order.

    Generators emit traces sorted by ``t_issue`` (FCFS); a memory
    controller (``core/sched/policies.py``, DESIGN.md §10) may reorder
    them, after which ``t_issue`` is non-monotone — each request still
    waits for its own arrival (``t_ready = max(t_issue, ...)``).

    Shapes: single channel (T,), multi-channel (C, T).
    """
    t_issue: jax.Array   # int32 ticks
    bank: jax.Array      # int32 [0, n_banks)
    row: jax.Array       # int32 [0, n_rows)
    col: jax.Array       # int32 [0, row_blocks) — cache-block column
    is_write: jax.Array  # bool
    core: jax.Array      # int32 [0, n_cores)


N_MSHR = 8  # outstanding misses per core (paper Table 1) — closed-loop throttle

# Ragged-workload padding sentinel (DESIGN.md §9): a request with
# ``t_issue >= NOOP_ISSUE`` is a NO-OP — it retires with zero latency,
# touches no bank/bus/MSHR/FTS state and no counter.  ``simulator.
# sweep_traces`` pads unequal-length traces to a shared scan length with
# these, the trace-axis analogue of the FTS padding slots.
NOOP_ISSUE = int(fts_lib.BIG)

# Saturation ceiling for the per-core latency-sum counter.  A request's
# latency includes its queueing delay, so the only sound per-step bound is
# simulated time itself (< 2**30 ticks); an unclamped int32 sum can
# therefore wrap within the declared 1M-request scan capacity
# (``analysis.jaxpr_audit.TRACE_LEN_BOUND``).  Clamping at 2**30 - 1 keeps
# the pre-clamp add wrap-free (cap + per-step bound == INT32_MAX) and is
# bitwise-invisible below the cap (tests/test_analysis.py pins this).
LAT_SUM_CAP = (1 << 30) - 1

# Log2 latency-histogram buckets (DESIGN.md §16).  Bucket 0 holds exactly
# lat_ns == 0; bucket b >= 1 holds lat_ns in [2**(b-1), 2**b - 1] — i.e.
# the bucket index is the bit length of the latency, computed in-scan by
# one count-leading-zeros op (``32 - lax.clz``), no float log.  A request's
# latency in ns is bounded by simulated time / 8 < 2**27, so 28 buckets
# cover the whole range exactly; the defensive clip into the last bucket
# never fires within the T_MAX contract.  ``obs/latency.py`` holds the
# host-side mirror (bounds, percentiles, CDF).
HIST_BUCKETS = 28


def noop_pad(trace: Trace, length: int) -> Trace:
    """Right-pad a (T,)/(C, T) trace to ``length`` requests with no-ops.

    No-ops carry ``t_issue = NOOP_ISSUE`` (so the sorted-by-issue-time
    invariant holds) and neutral fields everywhere else."""
    cur = trace.t_issue.shape[-1]
    assert cur <= length, (cur, length)
    if cur == length:
        return trace

    def pad(x, fill):
        widths = [(0, 0)] * (x.ndim - 1) + [(0, length - cur)]
        return jnp.pad(x, widths, constant_values=fill)

    return Trace(t_issue=pad(trace.t_issue, NOOP_ISSUE),
                 bank=pad(trace.bank, 0), row=pad(trace.row, 0),
                 col=pad(trace.col, 0), is_write=pad(trace.is_write, False),
                 core=pad(trace.core, 0))


# Every trace of a simulator scan (== one XLA compilation) appends a tag here.
# ``benchmarks/sweep_engine.py`` reads it to report jit counts; tests use it
# to assert "one compiled scan per static structure".
JIT_TRACE_LOG: List[str] = []


def _note_trace(tag: str) -> None:
    """Record one jit trace.  Runs only while JAX traces (i.e. per compile)."""
    JIT_TRACE_LOG.append(tag)


def jit_trace_count() -> int:
    return len(JIT_TRACE_LOG)


class BankState(NamedTuple):
    open_row: jax.Array   # (n_banks,) int32; -1 closed; cache rows >= n_rows
    busy: jax.Array       # (n_banks,) int32 ticks
    fts: fts_lib.FTS      # leaves have leading (n_banks,) dim
    mshr_ring: jax.Array  # (n_cores, N_MSHR) int32 — completion times
    mshr_idx: jax.Array   # (n_cores,) int32 — ring cursor
    bus_free: jax.Array   # () int32 — channel data bus free time


class Counters(NamedTuple):
    acts_slow: jax.Array
    acts_fast: jax.Array
    reads: jax.Array
    writes: jax.Array
    reloc_blocks: jax.Array    # blocks moved into the cache
    wb_blocks: jax.Array       # dirty writeback blocks
    row_hits: jax.Array
    cache_hits: jax.Array
    insertions: jax.Array
    lat_sum_ns: jax.Array      # (n_cores,)
    req_cnt: jax.Array         # (n_cores,)
    t_end: jax.Array           # ticks


def init_state(static: StaticConfig, geom: DRAMGeometry = GEOM) -> BankState:
    """Initial per-bank state.  FTS arrays are allocated at the *padded*
    maximum; the effective geometry is applied per step from the traced
    ``MechParams`` (slots beyond ``n_slots`` stay invalid forever)."""
    max_slots = static.max_slots if static.has_cache else 1
    max_segs = static.max_segs_per_row if static.has_cache else 1
    one = fts_lib.init(max_slots, max_segs)
    fts = jax.tree.map(
        lambda a: jnp.broadcast_to(a, (geom.n_banks,) + a.shape).copy(), one)
    return BankState(
        open_row=jnp.full((geom.n_banks,), -1, jnp.int32),
        busy=jnp.zeros((geom.n_banks,), jnp.int32),
        fts=fts,
        mshr_ring=jnp.zeros((geom.n_cores, N_MSHR), jnp.int32),
        mshr_idx=jnp.zeros((geom.n_cores,), jnp.int32),
        bus_free=jnp.int32(0),
    )


def init_counters(geom: DRAMGeometry = GEOM) -> Counters:
    z = jnp.int32(0)
    return Counters(z, z, z, z, z, z, z, z, z,
                    jnp.zeros((geom.n_cores,), jnp.int32),
                    jnp.zeros((geom.n_cores,), jnp.int32), z)


class TelemetryWindows(NamedTuple):
    """In-scan flight-recorder accumulators (DESIGN.md §15).

    Per-window *deltas* of the interesting counters, carried through the
    scan when ``StaticConfig.telemetry`` (the window period, in REAL
    requests) is non-zero.  ``win_idx`` is the cursor: the ordinal of the
    window currently accumulating, where window ``w`` covers real requests
    ``[w * period, (w + 1) * period)``.  Indexing windows by the
    real-request count (``cnt.reads + cnt.writes``) rather than by scan
    position makes the series invariant to chunking and to no-op padding —
    the same property the counters themselves have.

    All leaves are int32 scalars except the plane fields ``w_bank_issues``
    ``(n_banks,)`` and ``w_hist`` ``(HIST_BUCKETS,)``.
    Every count field is bounded by the window period (one real request
    retires per serial scan step) except ``w_reloc_blocks`` (period x
    seg_blocks) and the time-like sums ``w_lat_ns``/``w_bus_wait``/
    ``w_mshr_wait``, which clamp at ``LAT_SUM_CAP`` exactly like
    ``Counters.lat_sum_ns``.  The bounds are declared to the sanitizer in
    ``analysis/jaxpr_audit.py`` (``TEL_CARRY_BOUNDS`` /
    ``HIST_CARRY_BOUNDS``).
    """
    win_idx: jax.Array        # ordinal of the accumulating window
    w_reqs: jax.Array         # real requests retired this window
    w_reads: jax.Array
    w_writes: jax.Array
    w_row_hits: jax.Array     # row-buffer hits
    w_cache_hits: jax.Array   # FIGCache hits
    w_ins: jax.Array          # cache insertions
    w_reloc_blocks: jax.Array  # blocks relocated into the cache
    w_lat_ns: jax.Array       # summed request latency (ns, clamped)
    w_bus_wait: jax.Array     # ticks bursts waited on the busy data bus
    w_mshr_wait: jax.Array    # ticks requests stalled on a full MSHR
    w_slo: jax.Array          # requests over MechParams.slo_ns this window
    w_bank_issues: jax.Array  # (n_banks,) requests issued per bank
    w_hist: jax.Array         # (HIST_BUCKETS,) log2 latency histogram (§16)


class TelemetryFrame(NamedTuple):
    """One segment's closed telemetry windows, oldest first.

    ``win`` leaves carry a leading window axis ``(W, ...)`` with
    ``W = min(T, T // period + 2) + 1`` — the most windows a T-step
    segment can close (a closure needs a real request, and the
    real-request ordinal advances by at most one per serial step) plus
    the live row the in-scan writer keeps for the accumulating window.
    The fixed W keeps the scan a single compilation; rows past the
    closure count hold the live partial / zero filler with
    ``valid=False`` that hosts MUST mask out (their content is NOT
    chunk-invariant — the masked series is).  The final, possibly partial
    window never closes in-scan; it stays in ``SimState.tel`` for the
    host to collect (``obs.WindowCollector``).
    """
    valid: jax.Array          # (W,) bool — row holds a closed window
    win: TelemetryWindows     # leaves (W, ...), closed-window accumulators


class TelemetryState(NamedTuple):
    """The cross-segment telemetry cursor (``SimState.tel``, DESIGN.md
    §15/§16): the open (accumulating) window plus the run-cumulative
    latency-distribution planes, which never reset at window boundaries
    and therefore live OUTSIDE the per-window ring buffer.

    ``hist`` is the §16 histogram pair: plane 0 counts reads, plane 1
    writes, so ``hist.sum(0)`` is the total distribution and each plane's
    total mass reconciles exactly with ``Counters.reads``/``writes``
    (tests/test_obs.py pins the identity).  ``slo`` counts requests whose
    latency exceeded ``MechParams.slo_ns`` — counted per request in-scan,
    never estimated from buckets.  The whole pytree is checkpointable and
    threads through the streaming drivers unchanged.
    """
    win: TelemetryWindows    # the open window's accumulators
    hist: jax.Array          # (2, n_cores, HIST_BUCKETS) cumulative rd/wr
    slo: jax.Array           # (n_cores,) cumulative over-SLO requests


def init_telemetry(geom: DRAMGeometry = GEOM) -> TelemetryState:
    z = jnp.int32(0)
    win = TelemetryWindows(z, z, z, z, z, z, z, z, z, z, z, z,
                           jnp.zeros((geom.n_banks,), jnp.int32),
                           jnp.zeros((HIST_BUCKETS,), jnp.int32))
    return TelemetryState(
        win=win,
        hist=jnp.zeros((2, geom.n_cores, HIST_BUCKETS), jnp.int32),
        slo=jnp.zeros((geom.n_cores,), jnp.int32))


# non-scalar (plane) window fields, excluded from the packed scalar lane
_TEL_PLANES = ("w_bank_issues", "w_hist")
# the scalar accumulators, in their packed-lane order
_TEL_SCALARS = tuple(f for f in TelemetryWindows._fields
                     if f not in _TEL_PLANES)


class TelemetryCarry(NamedTuple):
    """Packed IN-SCAN form of ``TelemetryWindows`` (DESIGN.md §15).

    The scalar accumulators ride one (12,) int32 vector lane so the scan
    body pays O(1) tensor ops for the whole window update, not one per
    metric — measured, this is the difference between a ~1.2x and a
    ~1.05x telemetry tax.  ``_tel_pack`` / ``_tel_unpack`` convert at
    segment entry/exit; everything outside the scan (``SimState.tel``,
    frames, checkpoints, the collector) sees the named
    ``TelemetryWindows`` form only.
    """
    scalars: jax.Array       # (12,) int32 — ``_TEL_SCALARS`` lane order
    bank_issues: jax.Array   # (n_banks,) int32
    hist_win: jax.Array      # (HIST_BUCKETS,) int32 — this window's hist


class _TelScan(NamedTuple):
    """The full telemetry scan carry: cursor + closed-window ring buffer.

    Closed windows are written INTO the carry (each step writes the
    post-update accumulators to the live row ``n``; see
    ``_telemetry_step``) instead of being emitted as per-step scan
    outputs: a telemetry scan therefore materializes no (T, ...) output
    slabs at all — only this fixed (W, ...) buffer, sized by
    ``_scan_segment`` per segment length — which is what keeps the
    telemetry tax in single digits.  The cumulative §16 planes (``hist``,
    ``slo``) never reset, so they ride the carry directly with no ring
    rows.  Segment-local: ``SimState`` carries only the unpacked
    ``TelemetryState`` across segments.
    """
    cur: TelemetryCarry      # the accumulating window, packed
    hist: jax.Array          # (2, n_cores, HIST_BUCKETS) cumulative rd/wr
    slo: jax.Array           # (n_cores,) cumulative over-SLO requests
    buf_scalars: jax.Array   # (W, 12) int32 — closed windows, oldest first
    buf_banks: jax.Array     # (W, n_banks) int32
    buf_hist: jax.Array      # (W, HIST_BUCKETS) int32
    n: jax.Array             # () int32 — closed-window count


def _tel_pack(tel: TelemetryWindows) -> TelemetryCarry:
    return TelemetryCarry(
        scalars=jnp.stack([jnp.asarray(getattr(tel, f), jnp.int32)
                           for f in _TEL_SCALARS], axis=-1),
        bank_issues=tel.w_bank_issues,
        hist_win=tel.w_hist)


def _tel_unpack(carry: TelemetryCarry) -> TelemetryWindows:
    lanes = {f: carry.scalars[..., i] for i, f in enumerate(_TEL_SCALARS)}
    return TelemetryWindows(w_bank_issues=carry.bank_issues,
                            w_hist=carry.hist_win, **lanes)


def hist_bucket(lat_ns: jax.Array) -> jax.Array:
    """The §16 log2 bucket of a (non-negative int32) latency: its bit
    length, clipped into the last bucket.  Exact integer arithmetic — one
    ``clz`` — so the host-side mirror (``obs.latency.bucket_index``) can
    reproduce it bit-for-bit."""
    bits = 32 - jax.lax.clz(jnp.maximum(lat_ns, 0))
    return jnp.minimum(bits, HIST_BUCKETS - 1)


def _telemetry_step(tel: _TelScan, period: int, *, real, bank, core,
                    is_write, row_hit, hit, n_ins, moved, lat_ns, bus_wait,
                    mshr_wait, slo_ns, step_id):
    """Advance the window accumulators by one (possibly no-op) request.

    A request belonging to the next window (``step_id`` at the boundary)
    first bumps the closed-window count, then resets the accumulators and
    folds itself into the fresh window.  Every step then writes the
    POST-update accumulators into the LIVE ring row ``n``: a row is
    complete the moment a later boundary bumps ``n`` past it, because the
    last real request of window ``k`` wrote window ``k``'s final values
    to row ``k`` before the close was detected.  Writing post-update
    values only — never buffering pre-update state — keeps the whole
    telemetry carry updatable in place (the pre-update variant forced
    per-step carry copies and doubled the measured tax).  Because
    ``step_id`` (the real-request count) advances by at most 1 per serial
    step, at most one boundary can be crossed per step and ``n`` stays
    inside the buffer (``_scan_segment`` sizes it with a spare row for
    the trailing partial).  No-ops are telemetry-inert: ``real`` gates
    both the boundary test and every delta, so padded replicas of a trace
    stay bitwise-identical — the counters' own invariant.

    The whole vector lane clamps at ``LAT_SUM_CAP`` like
    ``Counters.lat_sum_ns``: a no-op for the count lanes (bounded by the
    window period anyway), the wrap-free saturation bound for the
    time-sum lanes (cap + per-step bound == INT32_MAX).

    The §16 latency-distribution planes follow the same live-row
    discipline: the per-window histogram resets with the other window
    lanes and its post-update value lands in ring row ``n`` every step;
    the cumulative read/write planes and the over-SLO counts are plain
    monotone scatter-adds (one element each per real request), so XLA
    keeps every plane update in place.  ``over`` compares the request's
    EXACT latency against the traced threshold — over-SLO accounting is
    never derived from bucket boundaries.
    """
    vec = tel.cur.scalars
    r32 = real.astype(jnp.int32)
    bucket = hist_bucket(lat_ns)
    over = real & (slo_ns > 0) & (lat_ns > slo_ns)
    # windows never skip (step_id advances by exactly 1 per real request),
    # so the boundary test is a multiply against the NEXT window's start —
    # not a per-step integer division
    w = vec[0] + 1                     # lane 0 == win_idx
    crossed = real & (step_id >= w * period)
    n = tel.n + crossed.astype(jnp.int32)
    z = jnp.int32(0)
    # reset lanes on a boundary (win_idx lane resets TO the new ordinal),
    # then fold this request's deltas in, then saturate
    reset = jnp.zeros_like(vec).at[0].set(w)
    delta = jnp.stack([
        z,                                        # win_idx — set via reset
        r32,                                      # w_reqs
        ((~is_write) & real).astype(jnp.int32),   # w_reads
        (is_write & real).astype(jnp.int32),      # w_writes
        (row_hit & real).astype(jnp.int32),       # w_row_hits
        hit.astype(jnp.int32),                    # w_cache_hits
        n_ins,                                    # w_ins
        moved,                                    # w_reloc_blocks
        jnp.where(real, lat_ns, z),               # w_lat_ns
        jnp.where(real, bus_wait, z),             # w_bus_wait
        jnp.where(real, mshr_wait, z),            # w_mshr_wait
        over.astype(jnp.int32),                   # w_slo
    ])
    vec = jnp.minimum(jnp.where(crossed, reset, vec) + delta, LAT_SUM_CAP)
    banks = jnp.where(crossed, jnp.zeros_like(tel.cur.bank_issues),
                      tel.cur.bank_issues).at[bank].add(r32)
    hist_w = jnp.where(crossed, jnp.zeros_like(tel.cur.hist_win),
                       tel.cur.hist_win).at[bucket].add(r32)
    # cumulative planes: one scatter-add each, never reset
    hist = tel.hist.at[is_write.astype(jnp.int32), core, bucket].add(r32)
    slo = tel.slo.at[core].add(over.astype(jnp.int32))
    buf_s = tel.buf_scalars.at[n].set(vec)
    buf_b = tel.buf_banks.at[n].set(banks)
    buf_h = tel.buf_hist.at[n].set(hist_w)
    return _TelScan(TelemetryCarry(vec, banks, hist_w), hist, slo,
                    buf_s, buf_b, buf_h, n)


def _lisa_hops(row: jax.Array, geom: DRAMGeometry) -> jax.Array:
    """Distance (in subarrays) to the nearest interleaved fast subarray.

    LISA-VILLA interleaves 16 fast subarrays among 64 slow ones (1 per 4)."""
    sub = row // geom.rows_per_subarray
    m = jnp.remainder(sub, 4)
    return jnp.minimum(m, 4 - m)


class Decision(NamedTuple):
    """The bank-local half of one fused step (DESIGN.md §9/§10).

    Everything a request's outcome needs that depends only on *its own
    bank's* state (FTS decision + write-back values, row-buffer outcome,
    relocation cost) — and NOT on the channel-shared bus/MSHR timing.
    ``dram.make_step`` ("fused") computes a Decision and then resolves the
    shared timing serially; the bank-wavefront scan
    (``core/sched/wavefront.py``) vmaps the SAME decision function across a
    wave of distinct-bank requests and resolves the shared timing with a
    short in-wave ordered prefix.  That shared code path is what makes the
    two executions bitwise-equal by construction.

    All fields are no-op-safe: for a padding request (``t_issue >=
    NOOP_ISSUE``) every write value equals the old state and every counter
    delta is zero.
    """
    write: fts_lib.SlotWrite  # per-(bank, slot) FTS write-back values
    hit: jax.Array            # cache hit (cacheable & real)
    row_hit: jax.Array        # open-row hit on the (possibly cached) target
    served_fast: jax.Array    # served from fast-subarray timings
    pre_act: jax.Array        # ACT(+PRE) latency before the CAS
    reloc_cost: jax.Array     # insertion relocation ticks (0 if no insert)
    new_open: jax.Array       # row left open in the bank afterwards
    moved: jax.Array          # blocks relocated into the cache
    wb: jax.Array             # dirty-victim writeback blocks
    n_ins: jax.Array          # 1 if an insertion happened


def _placeholder_write(max_segs: int) -> fts_lib.SlotWrite:
    """A shape-consistent ``SlotWrite`` for cache-less mechanisms (never
    applied — ``has_cache`` gates ``fts_lib.apply_write``)."""
    z = jnp.int32(0)
    return fts_lib.SlotWrite(
        w=z, tag=z, valid=jnp.bool_(False), dirty=jnp.bool_(False),
        benefit=z, last_use=z, row_delta=z, evict_row=z,
        evict_mask=jnp.zeros((max_segs,), bool), tr_idx=z, miss_tag=z,
        miss_cnt=z, n_valid_inc=z)


def make_decision_fn(static: StaticConfig, geom: DRAMGeometry = GEOM):
    """Build the per-request decision function of the fused hot loop.

    ``decide(params, state, req, step_id) -> Decision`` reads only the
    request's own bank (scalar/one-row gathers from the banked state), so
    it can be ``jax.vmap``-ed over a wave of requests to *distinct* banks
    unchanged — the wavefront scan does exactly that (DESIGN.md §10).
    ``step_id`` is the number of real requests retired before this one
    (== ``cnt.reads + cnt.writes`` serially; wave callers add the in-wave
    prefix count), which feeds LRU stamps and the Random victim hash.
    """
    cache_base = jnp.int32(geom.n_rows)           # id-space for cache rows
    reserved_sub = geom.n_subarrays - 1           # figcache_slow region
    lisa = static.mechanism == "lisa_villa"
    slow_cache = static.mechanism == "figcache_slow"
    lldram = static.mechanism == "lldram"
    max_slots = static.max_slots if static.has_cache else 1
    max_segs = static.max_segs_per_row if static.has_cache else 1

    def decide(params: MechParams, state: "BankState", req: Trace,
               step_id) -> Decision:
        p = params
        spr = p.segs_per_row            # traced — rides in MechParams
        bank = req.bank
        f = state.fts
        real = req.t_issue < NOOP_ISSUE
        open_b = state.open_row[bank]

        # ---- cache lookup + victim candidate (one pass over the bank) ----
        if static.has_cache:
            seg = req.row * spr + req.col // p.seg_blocks
            if slow_cache:   # never cache the subarray hosting reserved rows
                cacheable = (req.row // geom.rows_per_subarray) != reserved_sub
            else:
                cacheable = jnp.bool_(True)
            row_benefit = static.policy == "row_benefit"
            if static.fts_kernel:
                # fused VMEM pass: tag compare + the policy's masked victim
                # argmin in ONE visit of the bank's row.  Relies on the
                # in-scan invariant "invalid => tag == -1" (fts.invalidate)
                if row_benefit:
                    score, limit = f.row_sum, (p.n_slots + spr - 1) // spr
                elif static.policy == "segment_benefit":
                    score, limit = f.benefit, p.n_slots
                elif static.policy == "lru":
                    score, limit = f.last_use, p.n_slots
                else:                       # random: no argmin needed
                    score, limit = f.tags, jnp.int32(0)
                hit_raw, slot, cand = fts_lookup_op(
                    f.tags, score, bank, seg, jnp.asarray(limit, jnp.int32))
            else:
                # tag-only compare: in-scan, invalid slots always hold
                # tags == -1 (init; eviction overwrites valid entries in
                # place; fts.invalidate — unused here — resets tags), and
                # segment ids are >= 0, so the valid bitmap is redundant.
                # The fused-vs-dense bitwise test pins this invariant.
                m = f.tags[bank] == seg
                hit_raw = jnp.any(m)
                slot = jnp.argmax(m).astype(jnp.int32)
                if row_benefit:
                    rows = jnp.arange(max_slots, dtype=jnp.int32)
                    cand = fts_lib.masked_argmin(f.row_sum[bank],
                                                 rows * spr < p.n_slots)
                elif static.policy in ("segment_benefit", "lru"):
                    arr = f.benefit if static.policy == "segment_benefit" \
                        else f.last_use
                    active = jnp.arange(max_slots, dtype=jnp.int32) < p.n_slots
                    cand = fts_lib.masked_argmin(arr[bank], active)
                else:
                    cand = jnp.int32(0)
            hit = hit_raw & cacheable & real

            # ---- replacement decision from carried aggregates ------------
            if row_benefit:
                row_sel, mask_sel = fts_lib.pick_victim_row(
                    f.row_sum[bank], f.evict_row[bank], f.evict_mask[bank],
                    spr, p.n_slots, new_row=cand)
                bidx = jnp.clip(row_sel * spr +
                                jnp.arange(max_segs, dtype=jnp.int32),
                                0, max_slots - 1)
                victim_slot, mask_new = fts_lib.pick_victim_in_row(
                    f.benefit[bank, bidx], mask_sel, row_sel, spr)
            elif static.policy == "random":
                victim_slot = fts_lib.random_victim(step_id, p.n_slots)
            else:
                victim_slot = cand
            n_valid_b = f.n_valid[bank]
            has_free = n_valid_b < p.n_slots
            free_slot = f.free_list[bank,
                                    jnp.minimum(n_valid_b, max_slots - 1)]

            # ---- insertion policy (consecutive-miss tracker) -------------
            n_track = f.miss_tags.shape[1]
            tr_idx = jnp.remainder(seg, n_track)
            same = f.miss_tags[bank, tr_idx] == seg
            cnt_new = jnp.where(same, f.miss_cnt[bank, tr_idx] + 1, 1)
            want = (p.insert_threshold <= 1) | (cnt_new >= p.insert_threshold)
            # the tracker advances on actual (cacheable) misses only
            advance = real & cacheable & ~hit_raw
            do_ins = ~hit & cacheable & want & real

            # ---- surgical per-(bank, slot) state update ------------------
            # exactly one slot w is written per step (hit slot or landing
            # slot); when nothing happens the write stores back old values
            ins_slot = jnp.where(has_free, free_slot, victim_slot)
            w = jnp.where(hit, slot, ins_slot)
            old_tag = f.tags[bank, w]
            old_valid = f.valid[bank, w]
            old_dirty = f.dirty[bank, w]
            old_benefit = f.benefit[bank, w]
            old_last = f.last_use[bank, w]
            ev_valid = do_ins & ~has_free & old_valid
            ev_dirty = ev_valid & old_dirty
            ev_tag = old_tag
            b_touch = jnp.minimum(old_benefit + 1, p.benefit_max)
            new_benefit = jnp.where(do_ins, 1,
                                    jnp.where(hit, b_touch, old_benefit))
            use_victim = do_ins & ~has_free
            if row_benefit:
                new_evict_row = jnp.where(use_victim, row_sel,
                                          f.evict_row[bank])
                new_evict_mask = jnp.where(use_victim, mask_new,
                                           f.evict_mask[bank])
            else:
                new_evict_row = f.evict_row[bank]
                new_evict_mask = f.evict_mask[bank]
            write = fts_lib.SlotWrite(
                w=w,
                tag=jnp.where(do_ins, seg, old_tag),
                valid=old_valid | do_ins,
                dirty=jnp.where(do_ins, req.is_write,
                                old_dirty | (hit & req.is_write)),
                benefit=new_benefit,
                last_use=jnp.where(hit | do_ins, step_id, old_last),
                row_delta=new_benefit - old_benefit,
                evict_row=new_evict_row,
                evict_mask=new_evict_mask,
                tr_idx=tr_idx,
                miss_tag=jnp.where(advance, seg, f.miss_tags[bank, tr_idx]),
                miss_cnt=jnp.where(advance, cnt_new, f.miss_cnt[bank, tr_idx]),
                n_valid_inc=(do_ins & has_free).astype(jnp.int32),
            )
        else:
            seg = jnp.int32(0)
            hit, slot = jnp.bool_(False), jnp.int32(0)
            do_ins = ev_valid = ev_dirty = jnp.bool_(False)
            ev_tag = ins_slot = jnp.int32(0)
            write = _placeholder_write(max_segs)

        target_row = jnp.where(hit, cache_base + slot // spr, req.row)

        # ---- service latency (bank-local half) ----------------------------
        served_fast = (hit & static.fast_cache) | lldram
        rcd = jnp.where(served_fast, p.rcd_fast, p.rcd)
        rp = jnp.where(served_fast, p.rp_fast, p.rp)
        row_hit = open_b == target_row
        closed = open_b < 0
        pre_act = jnp.where(row_hit, 0, rcd + jnp.where(closed, 0, rp))

        # ---- relocation cost (miss-path insertion) ------------------------
        if static.has_cache:
            if static.free_reloc:
                reloc_cost = jnp.int32(0)
            elif lisa:
                # whole-row relocation, distance-dependent (src row is open)
                hops = _lisa_hops(req.row, geom)
                reloc_cost = hops * p.lisa_hop + p.rcd_fast
                wb_hops = _lisa_hops(ev_tag, geom)
                reloc_cost += jnp.where(
                    ev_dirty, wb_hops * p.lisa_hop + p.rcd, 0)
            else:
                # FIGARO: seg_blocks RELOCs through the GRB.  The source row
                # is already open serving the miss (§8.1) and the destination
                # ACT overlaps via the per-subarray row-address latch (§4.1
                # "multiple activations without a precharge"), so only the
                # RELOC column transfers occupy the bank's column path.
                reloc_cost = p.seg_blocks * p.reloc
                # dirty-victim writeback needs the victim's home row opened
                reloc_cost += jnp.where(
                    ev_dirty, p.seg_blocks * p.reloc + p.rcd, 0)
            reloc_cost = jnp.where(do_ins, reloc_cost, 0)
            # after insertion the destination cache row is left open
            new_open = jnp.where(
                do_ins, cache_base + ins_slot // spr, target_row)
            moved = jnp.where(do_ins, p.seg_blocks, 0)
            wb = jnp.where(do_ins & ev_dirty, p.seg_blocks, 0)
            n_ins = do_ins.astype(jnp.int32)
        else:
            reloc_cost = jnp.int32(0)
            new_open = target_row
            moved = wb = n_ins = jnp.int32(0)

        return Decision(write=write, hit=hit, row_hit=row_hit,
                        served_fast=served_fast, pre_act=pre_act,
                        reloc_cost=reloc_cost, new_open=new_open,
                        moved=moved, wb=wb, n_ins=n_ins)

    return decide


def make_step(static: StaticConfig, geom: DRAMGeometry = GEOM,
              variant: str = "fused"):
    """Build the scan body for one *static structure*.

    The returned ``step(params, carry, req)`` closes over the padded FTS
    allocation and trace-time branches only; every numeric knob — the DRAM
    timings AND the effective FTS geometry ``n_slots``/``segs_per_row`` —
    comes in through the traced ``params`` (``timing.MechParams``), so one
    compilation of the scan serves arbitrarily many configs sharing
    ``static``, capacity and segment-size sweeps included (DESIGN.md §3).

    ``variant="fused"`` (default) is the surgical O(1)-update hot loop —
    carried FTS aggregates, per-(bank, slot) scalar scatters, no-op-request
    support, optional Pallas lookup — structured as the shared per-request
    ``make_decision_fn`` (the bank-local half, also vmapped by the
    wavefront scan of ``core/sched/wavefront.py``) plus the serial
    bus/MSHR timing resolution below.  ``variant="dense"`` is the pre-
    aggregate reference body (whole-FTS gathers / tree selects / full
    write-backs, no no-op support): bitwise-identical on real requests,
    kept as the equivalence bar and benchmark baseline (DESIGN.md §9).

    The carry is ``(BankState, Counters, tel)``.  With
    ``static.telemetry`` set, ``tel`` is the window accumulators plus a
    closed-window ring buffer (``_TelScan``, DESIGN.md §15); when
    disabled it is ``None`` — an empty pytree subtree, so the scan traces
    the exact jaxpr it did before telemetry existed.  The dense reference
    predates telemetry and rejects it.
    """
    if variant == "dense":
        return _make_step_dense(static, geom)
    assert variant == "fused", variant
    decide = make_decision_fn(static, geom)

    def step(params: MechParams, carry, req):
        state, cnt, tel = carry
        p = params
        bank = req.bank
        core = req.core
        real = req.t_issue < NOOP_ISSUE
        step_id = cnt.reads + cnt.writes
        dec = decide(params, state, req, step_id)

        # ---- channel-shared timing: MSHR closed loop + data bus -----------
        # a core may not have more than N_MSHR requests in flight — it
        # stalls until the request N_MSHR-ago completed
        mshr_slot = state.mshr_idx[core]
        mshr_free = state.mshr_ring[core, mshr_slot]
        t_ready = jnp.maximum(req.t_issue, mshr_free)
        t0 = jnp.maximum(t_ready, state.busy[bank])
        # the 64 B burst serializes on the shared channel data bus — a
        # contention source no in-DRAM cache can relieve
        done = jnp.maximum(t0 + dec.pre_act + p.cas, state.bus_free) + p.bl
        # bank occupancy: column accesses pipeline at tCCD; an ACT(+PRE)
        # occupies the bank for its own duration before the CAS can pipeline
        serv_end = t0 + dec.pre_act + p.ccd

        if static.has_cache:
            new_fts = fts_lib.apply_write(state.fts, bank, p.segs_per_row,
                                          dec.write)
        else:
            new_fts = state.fts
        state = BankState(
            open_row=state.open_row.at[bank].set(
                jnp.where(real, dec.new_open, state.open_row[bank])),
            busy=state.busy.at[bank].set(
                jnp.where(real, serv_end + dec.reloc_cost,
                          state.busy[bank])),
            fts=new_fts,
            mshr_ring=state.mshr_ring.at[core, mshr_slot].set(
                jnp.where(real, done, mshr_free)),
            mshr_idx=state.mshr_idx.at[core].set(
                jnp.where(real, (mshr_slot + 1) % N_MSHR, mshr_slot)),
            bus_free=jnp.where(real, done, state.bus_free),
        )

        # ---- counters ------------------------------------------------------
        act = ((~dec.row_hit) & real).astype(jnp.int32)
        lat_ns = ((done - t_ready) // 8).astype(jnp.int32)
        cnt = Counters(
            acts_slow=cnt.acts_slow + act * (~dec.served_fast),
            acts_fast=cnt.acts_fast + act * dec.served_fast,
            reads=cnt.reads + ((~req.is_write) & real).astype(jnp.int32),
            writes=cnt.writes + (req.is_write & real).astype(jnp.int32),
            reloc_blocks=cnt.reloc_blocks + dec.moved,
            wb_blocks=cnt.wb_blocks + dec.wb,
            row_hits=cnt.row_hits + (dec.row_hit & real).astype(jnp.int32),
            cache_hits=cnt.cache_hits + dec.hit.astype(jnp.int32),
            insertions=cnt.insertions + dec.n_ins,
            lat_sum_ns=jnp.minimum(
                cnt.lat_sum_ns.at[core].add(jnp.where(real, lat_ns, 0)),
                LAT_SUM_CAP),
            req_cnt=cnt.req_cnt.at[core].add(real.astype(jnp.int32)),
            # the request is not retired until its burst clears the shared
            # data bus, which can outlast the bank's own serv_end+reloc —
            # take the max over *both* (execution time feeds core/energy.py)
            t_end=jnp.maximum(cnt.t_end, jnp.where(
                real, jnp.maximum(done, serv_end + dec.reloc_cost), 0)),
        )

        # ---- telemetry windows (DESIGN.md §15) -----------------------------
        # gated on the STATIC knob: disabled builds trace the exact same
        # jaxpr as before this block existed — bitwise invisibility is
        # structural, not numerical (tests/test_obs.py golden-pins it)
        if static.telemetry:
            tel = _telemetry_step(
                tel, static.telemetry, real=real, bank=bank, core=core,
                is_write=req.is_write, row_hit=dec.row_hit, hit=dec.hit,
                n_ins=dec.n_ins, moved=dec.moved, lat_ns=lat_ns,
                bus_wait=done - (t0 + dec.pre_act + p.cas + p.bl),
                mshr_wait=t_ready - req.t_issue, slo_ns=p.slo_ns,
                step_id=step_id)
        return (state, cnt, tel), None

    return step


def _make_step_dense(static: StaticConfig, geom: DRAMGeometry = GEOM):
    """The pre-aggregate scan body (DESIGN.md §9 "dense"): whole-FTS bank
    gathers, tree-wide selects and full write-backs.  Bitwise-identical to
    the fused variant on real requests (``tests/test_hotloop.py``); does NOT
    understand ragged no-op padding.  Kept as the equivalence reference and
    the steps/sec baseline of ``benchmarks/sweep_engine.py``."""
    if static.telemetry:
        raise ValueError(
            "telemetry windows require the fused scan body; the dense "
            "reference predates them (set telemetry=0 or variant='fused')")
    cache_base = jnp.int32(geom.n_rows)           # id-space for cache rows
    reserved_sub = geom.n_subarrays - 1           # figcache_slow region
    lisa = static.mechanism == "lisa_villa"
    slow_cache = static.mechanism == "figcache_slow"
    lldram = static.mechanism == "lldram"

    def step(params: MechParams, carry, req):
        state, cnt, tel = carry
        p = params
        spr = p.segs_per_row            # traced — rides in MechParams
        bank = req.bank
        fts_b = jax.tree.map(lambda a: a[bank], state.fts)
        # closed loop: a core may not have more than N_MSHR requests in
        # flight — it stalls until the request N_MSHR-ago completed
        mshr_free = state.mshr_ring[req.core, state.mshr_idx[req.core]]
        t_ready = jnp.maximum(req.t_issue, mshr_free)
        t0 = jnp.maximum(t_ready, state.busy[bank])
        open_b = state.open_row[bank]
        step_id = cnt.reads + cnt.writes

        # ---- cache lookup -------------------------------------------------
        if static.has_cache:
            seg = req.row * spr + req.col // p.seg_blocks
            if slow_cache:   # never cache the subarray hosting reserved rows
                cacheable = (req.row // geom.rows_per_subarray) != reserved_sub
            else:
                cacheable = jnp.bool_(True)
            hit, slot = fts_lib.lookup(fts_b, seg)
            hit = hit & cacheable
        else:
            seg = jnp.int32(0)
            cacheable = jnp.bool_(False)
            hit, slot = jnp.bool_(False), jnp.int32(0)

        target_row = jnp.where(hit, cache_base + slot // spr, req.row)

        # ---- service latency ---------------------------------------------
        served_fast = (hit & static.fast_cache) | lldram
        rcd = jnp.where(served_fast, p.rcd_fast, p.rcd)
        rp = jnp.where(served_fast, p.rp_fast, p.rp)
        row_hit = open_b == target_row
        closed = open_b < 0
        pre_act = jnp.where(row_hit, 0, rcd + jnp.where(closed, 0, rp))
        # the 64 B burst serializes on the shared channel data bus — a
        # contention source no in-DRAM cache can relieve
        done = jnp.maximum(t0 + pre_act + p.cas, state.bus_free) + p.bl
        # bank occupancy: column accesses pipeline at tCCD; an ACT(+PRE)
        # occupies the bank for its own duration before the CAS can pipeline
        serv_end = t0 + pre_act + p.ccd

        # ---- miss path: insert-any-miss (+ optional threshold) ------------
        if static.has_cache:
            # the consecutive-miss tracker advances on actual (cacheable)
            # misses only; the hit path below is built from the pre-tracker
            # ``fts_b`` so hits leave the miss counters untouched
            want, fts_miss = fts_lib.should_insert(fts_b, seg,
                                                   p.insert_threshold)
            fts_miss = jax.tree.map(
                lambda m, b: jnp.where(cacheable, m, b), fts_miss, fts_b)
            do_ins = ~hit & cacheable & want
            # recompute=True: pay the seed's full-reduction insert cost
            # (free-slot argmin + segment-summed row benefits) — the dense
            # variant is the pre-aggregate baseline AND the oracle the
            # carried aggregates are pinned against
            ins = fts_lib.insert(fts_miss, seg, req.is_write, step_id,
                                 policy=static.policy, segs_per_row=spr,
                                 n_slots=p.n_slots, recompute=True)
            if static.free_reloc:
                reloc_cost = jnp.int32(0)
            elif lisa:
                # whole-row relocation, distance-dependent (src row is open)
                hops = _lisa_hops(req.row, geom)
                reloc_cost = hops * p.lisa_hop + p.rcd_fast
                wb_hops = _lisa_hops(ins.evicted_tag, geom)
                reloc_cost += jnp.where(
                    ins.evicted_dirty, wb_hops * p.lisa_hop + p.rcd, 0)
            else:
                # FIGARO: seg_blocks RELOCs through the GRB.  The source row
                # is already open serving the miss (§8.1) and the destination
                # ACT overlaps via the per-subarray row-address latch (§4.1
                # "multiple activations without a precharge"), so only the
                # RELOC column transfers occupy the bank's column path.
                reloc_cost = p.seg_blocks * p.reloc
                # dirty-victim writeback needs the victim's home row opened
                reloc_cost += jnp.where(
                    ins.evicted_dirty,
                    p.seg_blocks * p.reloc + p.rcd, 0)
            reloc_cost = jnp.where(do_ins, reloc_cost, 0)
            # after insertion the destination cache row is left open
            new_open = jnp.where(
                do_ins, cache_base + ins.slot // spr, target_row)
            touched = fts_lib.touch(fts_b, slot, req.is_write, step_id,
                                    p.benefit_max, spr)
            sel3 = lambda h, i, a, b, c: jnp.where(h, a, jnp.where(i, b, c))
            fts_new = jax.tree.map(
                functools.partial(sel3, hit, do_ins),
                touched, ins.fts, fts_miss)
            new_fts = jax.tree.map(
                lambda full, one: full.at[bank].set(one), state.fts, fts_new)
            moved = jnp.where(do_ins, p.seg_blocks, 0)
            wb = jnp.where(do_ins & ins.evicted_dirty, p.seg_blocks, 0)
            n_ins = do_ins.astype(jnp.int32)
        else:
            reloc_cost = jnp.int32(0)
            new_open = target_row
            new_fts = state.fts
            moved = wb = n_ins = jnp.int32(0)

        state = BankState(
            open_row=state.open_row.at[bank].set(new_open),
            busy=state.busy.at[bank].set(serv_end + reloc_cost),
            fts=new_fts,
            mshr_ring=state.mshr_ring.at[req.core,
                                         state.mshr_idx[req.core]].set(done),
            mshr_idx=state.mshr_idx.at[req.core].set(
                (state.mshr_idx[req.core] + 1) % N_MSHR),
            bus_free=done,
        )

        # ---- counters ------------------------------------------------------
        act = (~row_hit).astype(jnp.int32)
        lat_ns = ((done - t_ready) // 8).astype(jnp.int32)
        cnt = Counters(
            acts_slow=cnt.acts_slow + act * (~served_fast),
            acts_fast=cnt.acts_fast + act * served_fast,
            reads=cnt.reads + (~req.is_write).astype(jnp.int32),
            writes=cnt.writes + req.is_write.astype(jnp.int32),
            reloc_blocks=cnt.reloc_blocks + moved,
            wb_blocks=cnt.wb_blocks + wb,
            row_hits=cnt.row_hits + row_hit.astype(jnp.int32),
            cache_hits=cnt.cache_hits + hit.astype(jnp.int32),
            insertions=cnt.insertions + n_ins,
            lat_sum_ns=jnp.minimum(
                cnt.lat_sum_ns.at[req.core].add(lat_ns), LAT_SUM_CAP),
            req_cnt=cnt.req_cnt.at[req.core].add(1),
            # the request is not retired until its burst clears the shared
            # data bus, which can outlast the bank's own serv_end+reloc —
            # take the max over *both* (execution time feeds core/energy.py)
            t_end=jnp.maximum(cnt.t_end,
                              jnp.maximum(done, serv_end + reloc_cost)),
        )
        return (state, cnt, tel), None

    return step


class SimState(NamedTuple):
    """The FULL carried state of one simulator scan (DESIGN.md §13).

    Everything a ``lax.scan`` segment threads from one request to the
    next: the banked timing/FTS state and the counters.  Because the
    monolithic scan is a left fold of ``make_step`` over this very carry,
    running a trace as sequential *segments* — ``sim_init`` once, then
    ``run_segment`` per chunk, then ``finalize`` — is bitwise identical
    to the monolithic scan for ANY chunking, provided chunk padding uses
    the no-op sentinel (``NOOP_ISSUE``), which every step variant treats
    as state- and counter-inert (``tests/test_streaming.py`` pins both
    properties).  The pytree is checkpointable as-is
    (``checkpoint.save_sim_state``) so multi-million-request streamed
    replays survive preemption mid-trace.

    Leaves gain leading axes in the batched entry points: ``(C, ...)``
    per channel (``sim_init(..., channels=C)``), ``(P, [C,] ...)`` per
    params point (``sim_init(..., batch=P)`` / ``run_sweep_segment``).

    ``tel`` is the telemetry cursor (DESIGN.md §15/§16: the open window
    plus the cumulative latency-distribution planes): ``None`` — an EMPTY
    pytree subtree, so the disabled carry has exactly the seed's leaves —
    unless ``static.telemetry`` is set, in which case threading it across
    segments is what makes the chunked window series bitwise equal to the
    monolithic one.
    """
    bank: BankState
    cnt: Counters
    tel: TelemetryState | None = None


def sim_init(static: StaticConfig, geom: DRAMGeometry = GEOM,
             channels: int | None = None,
             batch: int | None = None) -> SimState:
    """Fresh scan carry for ``run_segment``/``run_sweep_segment``.

    ``channels`` broadcasts a leading per-channel axis (for (C, T) trace
    segments), ``batch`` a leading params axis; both compose as
    ``(batch, channels, ...)`` — the axis order the segment entry points
    vmap over."""
    st = SimState(bank=init_state(static, geom), cnt=init_counters(geom),
                  tel=init_telemetry(geom) if static.telemetry else None)
    dims = tuple(d for d in (batch, channels) if d is not None)
    if dims:
        st = jax.tree.map(
            lambda a: jnp.broadcast_to(a, dims + a.shape).copy(), st)
    return st


def finalize(state: SimState) -> Counters:
    """End a chunked replay: extract the final ``Counters``."""
    return state.cnt


def _scan_segment(step, params: MechParams, trace: Trace, state: SimState,
                  period: int = 0):
    if state.tel is None:
        tel0 = None
    else:
        # segment-local closed-window ring buffer (see _TelScan): sized to
        # the most windows a T-step segment can close, plus a spare row
        # for the trailing partial that _telemetry_step keeps live.  Row 0
        # is pre-seeded with the entering partial window so a boundary on
        # the very first step still closes a complete row.
        T = trace.t_issue.shape[-1]
        W = min(T, T // period + 2) + 1
        cur = _tel_pack(state.tel.win)
        tel0 = _TelScan(
            cur=cur,
            hist=state.tel.hist,
            slo=state.tel.slo,
            buf_scalars=jnp.zeros(
                (W, len(_TEL_SCALARS)), jnp.int32).at[0].set(cur.scalars),
            buf_banks=jnp.zeros(
                (W, state.tel.win.w_bank_issues.shape[-1]),
                jnp.int32).at[0].set(cur.bank_issues),
            buf_hist=jnp.zeros(
                (W, HIST_BUCKETS), jnp.int32).at[0].set(cur.hist_win),
            n=jnp.int32(0))
    carry, _ = jax.lax.scan(functools.partial(step, params),
                            (state.bank, state.cnt, tel0), trace)
    bank, cnt, tel = carry
    if tel is None:
        return SimState(bank, cnt, None), None
    frames = TelemetryFrame(
        valid=jnp.arange(tel.buf_scalars.shape[0]) < tel.n,
        win=_tel_unpack(TelemetryCarry(tel.buf_scalars, tel.buf_banks,
                                       tel.buf_hist)))
    return SimState(bank, cnt,
                    TelemetryState(_tel_unpack(tel.cur), tel.hist,
                                   tel.slo)), frames


def _scan_one(step, params: MechParams, trace: Trace,
              static: StaticConfig) -> Counters:
    carry0 = SimState(init_state(static), init_counters(),
                      init_telemetry() if static.telemetry else None)
    return _scan_segment(step, params, trace, carry0,
                         static.telemetry)[0].cnt


def _resume(trace: Trace, static: StaticConfig, params: MechParams,
            state: SimState, variant: str):
    """Shared segment core: advance ``state`` over one (T,)/(C, T) chunk.

    Returns ``(SimState, frames)``; ``frames`` is ``None`` unless
    ``static.telemetry``, in which case its leaves carry the closed-window
    axis ``(W, ...)`` (``(C, W, ...)`` for multi-channel chunks), with
    ``W = min(T, T // period + 2)`` and padding rows ``valid=False``.  The
    counters-only entry points simply drop the frames: telemetry rides the
    carry, so consuming or dropping frames never changes the counters."""
    step = make_step(static, variant=variant)
    per = static.telemetry
    if trace.t_issue.ndim == 1:
        return _scan_segment(step, params, trace, state, per)
    return jax.vmap(lambda tr, st: _scan_segment(step, params, tr, st, per))(
        trace, state)


def resume(trace: Trace, static: StaticConfig, params: MechParams,
           state: SimState, variant: str = "fused") -> SimState:
    """Un-jitted segment reference: one chunk of a chunked replay.

    ``state`` leaves must carry a leading (C,) axis iff the chunk's trace
    leaves are (C, T).  The jitted form is ``run_segment``: every chunk
    of the same shape reuses ONE compiled step (the fixed-shape chunks of
    the ``traces`` codec are built for exactly this)."""
    if isinstance(trace.t_issue, jax.core.Tracer):
        _note_trace(f"segment/{static.mechanism}/{variant}")
    return _resume(trace, static, params, state, variant)[0]


def resume_tel(trace: Trace, static: StaticConfig, params: MechParams,
               state: SimState, variant: str = "fused"):
    """Telemetry segment: like ``resume`` but returns ``(SimState,
    TelemetryFrame)`` so the host can collect the segment's closed
    windows (DESIGN.md §15).  Requires ``static.telemetry > 0``; the
    jitted form is ``run_segment_tel``."""
    if static.telemetry <= 0:
        raise ValueError("resume_tel needs StaticConfig.telemetry > 0 "
                         "(the window period in real requests)")
    if isinstance(trace.t_issue, jax.core.Tracer):
        _note_trace(f"segment_tel/{static.mechanism}/{variant}")
    return _resume(trace, static, params, state, variant)


run_segment = jax.jit(resume, static_argnums=(1,),
                      static_argnames=("variant",))
run_segment_tel = jax.jit(resume_tel, static_argnums=(1,),
                          static_argnames=("variant",))


def simulate(trace: Trace, static: StaticConfig, params: MechParams,
             variant: str = "fused") -> Counters:
    """Un-jitted reference: one params point, (T,) or (C, T) trace leaves.

    Literally ``finalize(resume(trace, ..., sim_init(...)))`` — the
    monolithic scan IS the one-chunk case of the segment API, which is
    what makes chunk-size invariance structural rather than asserted."""
    if isinstance(trace.t_issue, jax.core.Tracer):
        # log only when called under a jit trace (== one compilation);
        # eager reference runs must not inflate the jit count
        _note_trace(f"simulate/{static.mechanism}/{variant}")
    C = trace.t_issue.shape[0] if trace.t_issue.ndim == 2 else None
    state = sim_init(static, channels=C)
    return finalize(_resume(trace, static, params, state, variant)[0])


_simulate_jit = jax.jit(simulate, static_argnums=(1,),
                        static_argnames=("variant",))


def _sweep_resume(trace: Trace, static: StaticConfig,
                  params_batch: MechParams, state: SimState,
                  variant: str):
    """Shared batched-segment core: params leaves (P,), state leaves
    (P, ...) or (P, C, ...).  Returns ``(SimState, frames)`` with frame
    leaves ``(P, [C,] W, ...)`` when telemetry is on, else ``None``."""
    step = make_step(static, variant=variant)
    per = static.telemetry
    if trace.t_issue.ndim == 1:
        one = lambda p, st: _scan_segment(step, p, trace, st, per)
    else:
        one = lambda p, st: jax.vmap(
            lambda tr, s: _scan_segment(step, p, tr, s, per))(trace, st)
    return jax.vmap(one)(params_batch, state)


def sweep_resume(trace: Trace, static: StaticConfig,
                 params_batch: MechParams, state: SimState,
                 variant: str = "fused") -> SimState:
    """Un-jitted batched segment: ``run_sweep``'s one-chunk body, resumed
    from ``state`` (leading (P,) axes from ``sim_init(..., batch=P)``).
    The jitted form is ``run_sweep_segment``."""
    if isinstance(trace.t_issue, jax.core.Tracer):
        _note_trace(f"sweep_segment/{static.mechanism}/{variant}")
    return _sweep_resume(trace, static, params_batch, state, variant)[0]


def sweep_resume_tel(trace: Trace, static: StaticConfig,
                     params_batch: MechParams, state: SimState,
                     variant: str = "fused"):
    """Telemetry batched segment: ``sweep_resume`` returning the frames
    too — the whole capacity grid's window series in one compiled scan
    (DESIGN.md §15).  The jitted form is ``run_sweep_segment_tel``."""
    if static.telemetry <= 0:
        raise ValueError("sweep_resume_tel needs StaticConfig.telemetry > 0 "
                         "(the window period in real requests)")
    if isinstance(trace.t_issue, jax.core.Tracer):
        _note_trace(f"sweep_segment_tel/{static.mechanism}/{variant}")
    return _sweep_resume(trace, static, params_batch, state, variant)


run_sweep_segment = jax.jit(sweep_resume, static_argnums=(1,),
                            static_argnames=("variant",))
run_sweep_segment_tel = jax.jit(sweep_resume_tel, static_argnums=(1,),
                                static_argnames=("variant",))


@functools.partial(jax.jit, static_argnums=(1,), static_argnames=("variant",))
def run_sweep(trace: Trace, static: StaticConfig,
              params_batch: MechParams, variant: str = "fused") -> Counters:
    """Run a whole config grid sharing one static structure in ONE program.

    ``params_batch`` leaves carry a leading batch axis (P,).  Returns
    ``Counters`` with leading (P,) — or (P, C) for multi-channel traces —
    bitwise-equal to running each params point through ``run_channel``.
    """
    _note_trace(f"sweep/{static.mechanism}/{variant}")
    C = trace.t_issue.shape[0] if trace.t_issue.ndim == 2 else None
    P = jax.tree.leaves(params_batch)[0].shape[0]
    state = sim_init(static, channels=C, batch=P)
    return finalize(_sweep_resume(trace, static, params_batch, state,
                                  variant)[0])


def run_channel(trace: Trace, cfg: MechConfig,
                t: DRAMTimings = DDR4) -> Counters:
    """Simulate one channel's request stream ((T,) trace leaves)."""
    return _simulate_jit(trace, cfg.static, cfg.params(t))


def run_channels(traces: Trace, cfg: MechConfig,
                 t: DRAMTimings = DDR4) -> Counters:
    """Simulate C independent channels: traces leaves shaped (C, T)."""
    return _simulate_jit(traces, cfg.static, cfg.params(t))


def run_channel_exact(trace: Trace, cfg: MechConfig,
                      t: DRAMTimings = DDR4) -> Counters:
    """Unpadded reference run: FTS allocated at exactly ``cfg.n_slots``
    (``max == actual``, no masking headroom).  Benchmarks and tests use this
    as the bitwise-equivalence bar for the padded/masked path; it costs one
    compilation per distinct FTS shape, which is precisely what the padded
    path avoids.  Handles (T,) and (C, T) traces alike."""
    return _simulate_jit(trace, cfg.exact_static, cfg.params(t))
