"""Host-side chunked streaming simulation driver (DESIGN.md §13).

The monolithic scan caps trace length at device memory (and at the
audit's declared ``TRACE_LEN_BOUND``); the paper's evaluation replays
multi-million-request Ramulator traces (§7).  This module closes the gap:
a host loop feeds fixed-shape trace segments through the segment-carried
scan API (``dram.sim_init`` → ``run_segment``/``run_sweep_segment`` →
``finalize``), so a stream of any length replays through ONE compiled
step with O(chunk) device memory.  Because the monolithic scan is a left
fold of the same step over the same ``dram.SimState`` carry and chunk
padding uses the counter-inert no-op sentinel, ANY chunking of ANY trace
is bitwise identical to the monolithic scan (``tests/test_streaming.py``
pins chunk sizes {1, 7, 64, full} across all mechanisms and controllers,
resumed-from-checkpoint runs included).

Pipeline, per stream:

 * segments arrive from ``iter_chunks`` (slices of a materialized trace),
   ``decoded_segments`` (the ``traces`` chunk codec, decoded on device by
   one jitted op), or any generator (e.g. ``workload.generate_stream``);
 * a non-identity controller is applied by ``scheduled_segments`` — the
   carried ``sched_policies.StreamScheduler`` window reproduces the
   monolithic permutation exactly across chunk boundaries;
 * ``simulate_stream`` advances the ``SimState`` one segment at a time.
   JAX's async dispatch overlaps the host side (decoding / scheduling /
   packing the next segment) with the device executing the current one —
   the host never blocks on a result until ``finalize``;
 * every ``checkpoint_every`` segments the carry is snapshotted via
   ``checkpoint.save_sim_state``; ``resume_stream`` restores it and skips
   the already-simulated prefix.

Telemetry rides the same carry: with ``cfg.telemetry > 0`` and a
``telemetry=`` collector, the frames ``run_segment`` /
``run_sweep_segment`` return are handed to it, and the §15 window series
— including the §16 per-window latency-histogram rows and the cumulative
histogram / SLO planes in ``SimState.tel`` — is chunk-invariant by the
same argument: windows are indexed by the cumulative REAL-request count,
which no chunking or no-op padding can move.
"""
from __future__ import annotations

from typing import Iterable, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dram
from repro.core import traces as traces_lib
from repro.core.sched import policies as sched_policies
from repro.core.sched import wavefront
from repro.core.timing import DDR4, GEOM, DRAMTimings, MechConfig
from repro import checkpoint as ckpt_lib

__all__ = ["iter_chunks", "decoded_segments", "scheduled_segments",
           "simulate_stream", "sweep_stream", "resume_stream"]


def _noop_segment(shape) -> dram.Trace:
    z = np.zeros(shape, np.int32)
    return dram.Trace(t_issue=np.full(shape, dram.NOOP_ISSUE, np.int32),
                      bank=z, row=z.copy(), col=z.copy(),
                      is_write=np.zeros(shape, bool), core=z.copy())


def iter_chunks(trace: dram.Trace, chunk_len: int) -> Iterator[dram.Trace]:
    """Slice a materialized (T,)/(C, T) trace into ``chunk_len`` segments
    (ragged tail no-op padded to the shared fixed shape)."""
    T = np.asarray(trace.t_issue).shape[-1]
    for lo in range(0, max(T, 1), chunk_len):
        part = jax.tree.map(
            lambda a: np.asarray(a)[..., lo:lo + chunk_len], trace)
        yield dram.noop_pad(part, chunk_len)


def decoded_segments(encoded) -> Iterator[dram.Trace]:
    """Decode codec chunks into scan segments, one jitted device op total.

    ``encoded`` is a ``List[TraceChunk]`` (single channel → (L,)
    segments) or a per-channel ``List[List[TraceChunk]]`` (→ (C, L)
    segments).  Channels fragment independently (each chunk holds a
    channel-specific number of real requests before its filler tail), so
    multi-channel alignment simply stacks each channel's i-th chunk —
    chunk-interior no-ops keep the per-channel streams exact — and
    channels that ran out of chunks feed all-no-op rows."""
    if not encoded:
        return
    if isinstance(encoded[0], traces_lib.TraceChunk):
        for c in encoded:
            yield traces_lib.decode_chunk(c)
        return
    L = int(np.asarray(encoded[0][0].dt).shape[0])
    for per in encoded:
        assert per and int(np.asarray(per[0].dt).shape[0]) == L, \
            "all channels must share one codec chunk_len"
    for i in range(max(len(per) for per in encoded)):
        rows = [traces_lib.decode_chunk(per[i]) if i < len(per)
                else _noop_segment((L,)) for per in encoded]
        yield jax.tree.map(lambda *xs: jnp.stack(
            [jnp.asarray(x) for x in xs]), *rows)


def scheduled_segments(segments: Iterable[dram.Trace],
                       sc, geom=GEOM) -> Iterator[dram.Trace]:
    """Apply a controller to a segment stream with a carried window.

    Wraps one ``StreamScheduler`` per channel and re-packs their emitted
    requests into segments of the input's fixed shape (no-op fill where a
    channel's window is still holding requests back).  The concatenated
    per-channel output is bitwise the monolithic ``schedule`` order, so a
    scheduled streamed replay equals the scheduled monolithic one."""
    it = iter(segments)
    try:
        first = next(it)
    except StopIteration:
        return
    shape = np.asarray(first.t_issue).shape
    multi = len(shape) == 2
    C, L = (shape if multi else (1, shape[0]))
    scheds = [sched_policies.StreamScheduler(sc, geom) for _ in range(C)]
    pending: List[dict] = [
        {f: [] for f in dram.Trace._fields} for _ in range(C)]

    def absorb(emitted: dram.Trace, c: int):
        for f in dram.Trace._fields:
            pending[c][f].append(np.asarray(getattr(emitted, f)))

    def pack() -> Iterator[dram.Trace]:
        # emit full segments while any channel holds >= L requests; a
        # channel with fewer contributes what it has plus no-op fill
        def avail(c):
            return sum(a.shape[0] for a in pending[c][ "t_issue"])
        while max(avail(c) for c in range(C)) >= L:
            rows = []
            for c in range(C):
                cat = {f: np.concatenate(pending[c][f]) if pending[c][f]
                       else np.zeros(0, np.int32) for f in dram.Trace._fields}
                head = dram.Trace(**{f: v[:L] for f, v in cat.items()})
                for f in dram.Trace._fields:
                    pending[c][f] = [cat[f][L:]]
                rows.append(dram.noop_pad(head, L))
            yield _stack(rows) if multi else rows[0]

    def final() -> Iterator[dram.Trace]:
        while any(pending[c]["t_issue"] and
                  sum(a.shape[0] for a in pending[c]["t_issue"])
                  for c in range(C)):
            rows = []
            for c in range(C):
                cat = {f: np.concatenate(pending[c][f]) if pending[c][f]
                       else np.zeros(0, np.int32) for f in dram.Trace._fields}
                head = dram.Trace(**{f: v[:L] for f, v in cat.items()})
                for f in dram.Trace._fields:
                    pending[c][f] = [cat[f][L:]]
                rows.append(dram.noop_pad(head, L))
            yield _stack(rows) if multi else rows[0]

    def _stack(rows):
        return jax.tree.map(lambda *xs: np.stack(
            [np.asarray(x) for x in xs]), *rows)

    def feed(seg):
        for c in range(C):
            row = seg if not multi else jax.tree.map(
                lambda a: np.asarray(a)[c], seg)
            absorb(scheds[c].feed(row), c)

    feed(first)
    yield from pack()
    for seg in it:
        feed(seg)
        yield from pack()
    for c in range(C):
        absorb(scheds[c].flush(), c)
    yield from pack()
    yield from final()


def _wave_bucket(n: int) -> int:
    """Power-of-two wave-count bucket: chunked wave traces pad to the
    next bucket so the number of distinct compiled wave-scan shapes stays
    logarithmic in the chunk length."""
    b = 1
    while b < n:
        b <<= 1
    return b


def _check_telemetry(telemetry, static, wavefront_exec=False):
    """Validate a telemetry collector against the run's static config."""
    if telemetry is None:
        return
    if not static.telemetry:
        raise ValueError(
            "a telemetry collector needs a telemetry-enabled config "
            "(set MechConfig.telemetry to the window period)")
    if wavefront_exec:
        raise ValueError("telemetry windows are not supported under "
                         "wavefront execution")


def simulate_stream(segments: Iterable[dram.Trace], cfg: MechConfig,
                    t: DRAMTimings = DDR4, *,
                    wavefront_exec: bool = False,
                    state: Optional[dram.SimState] = None,
                    start_chunk: int = 0,
                    checkpoint_dir: Optional[str] = None,
                    checkpoint_every: int = 0,
                    telemetry=None) -> dram.Counters:
    """Replay a segment stream under one config; returns final counters.

    Bitwise-equal to the monolithic ``dram.run_channel`` on the
    concatenated stream (after ``cfg.sched`` scheduling, applied here via
    the carried ``scheduled_segments`` window).  ``wavefront_exec`` forms
    per-chunk waves and drives ``wavefront.run_segment_waves`` instead of
    the serial segment scan.  ``state``/``start_chunk`` resume a
    checkpointed replay (see ``resume_stream``); ``checkpoint_dir`` +
    ``checkpoint_every`` snapshot the carry every N segments.

    ``telemetry`` is a window-frame collector (``obs.WindowCollector`` —
    anything with ``add(frames)``/``close(state)``) and requires
    ``cfg.telemetry > 0``: each segment's frames (from ``run_segment``)
    are handed to the collector; because the cursor rides in
    ``SimState.tel``, the collected series is chunking-invariant
    (DESIGN.md §15)."""
    params = cfg.params(t)
    static = cfg.static
    _check_telemetry(telemetry, static, wavefront_exec)
    it: Iterable[dram.Trace] = segments
    if cfg.sched is not None and not cfg.sched.is_identity:
        it = scheduled_segments(it, cfg.sched)
    for i, seg in enumerate(it):
        if i < start_chunk:
            continue
        if state is None:
            sh = np.asarray(seg.t_issue).shape
            state = dram.sim_init(static,
                                  channels=sh[0] if len(sh) == 2 else None)
        if wavefront_exec:
            w = wavefront.form_waves(seg)
            w = wavefront.pad_waves(
                w, _wave_bucket(np.asarray(w.t_issue).shape[-2]))
            state = wavefront.run_segment_waves(w, static, params, state)
        else:
            state, frames = dram.run_segment(seg, static, params, state)
            if telemetry is not None:
                telemetry.add(frames)
        if checkpoint_dir and checkpoint_every and \
                (i + 1) % checkpoint_every == 0:
            ckpt_lib.save_sim_state(checkpoint_dir, i + 1, state)
    assert state is not None, "empty segment stream"
    if telemetry is not None:
        telemetry.close(state)
    return dram.finalize(state)


def resume_stream(segments: Iterable[dram.Trace], cfg: MechConfig,
                  checkpoint_dir: str, t: DRAMTimings = DDR4,
                  **kw) -> dram.Counters:
    """Restore the newest committed ``SimState`` under ``checkpoint_dir``
    and finish the stream.  ``segments`` must be the SAME stream the
    interrupted run consumed (the already-simulated prefix is skipped by
    segment count); the result is bitwise the uninterrupted replay's."""
    peek = iter(segments)
    # structure donor for the restore: fresh state of the run's layout
    first = next(peek)
    sh = np.asarray(first.t_issue).shape
    like = dram.sim_init(cfg.static,
                         channels=sh[0] if len(sh) == 2 else None)
    state, chunk = ckpt_lib.restore_sim_state(checkpoint_dir, like)

    def rechain():
        yield first
        yield from peek
    return simulate_stream(rechain(), cfg, t, state=state,
                           start_chunk=chunk, **kw)


def sweep_stream(segments: Iterable[dram.Trace],
                 static, params_batch, *,
                 state: Optional[dram.SimState] = None,
                 start_chunk: int = 0,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0,
                 telemetry=None) -> dram.Counters:
    """Batched streamed replay: ``dram.run_sweep``'s semantics over a
    segment stream (params leaves (P,)), one compiled step for all
    segments.  Callers pre-schedule or stream identity-order traces —
    the sweep layer (``simulator.sweep``) owns controller grouping.

    ``state``/``start_chunk``/``checkpoint_dir``/``checkpoint_every``
    mirror ``simulate_stream``: the batched carry checkpoints through the
    same substrate, so a killed sweep resumes mid-trace (the orchestrator,
    DESIGN.md §14, layers shard-level durability on top of this).
    ``telemetry`` collects the whole grid's window frames (leaves gain the
    (P, [C,]) lead axes) from ``run_sweep_segment`` — see
    ``simulate_stream``."""
    _check_telemetry(telemetry, static)
    P = jax.tree.leaves(params_batch)[0].shape[0]
    for i, seg in enumerate(segments):
        if i < start_chunk:
            continue
        if state is None:
            sh = np.asarray(seg.t_issue).shape
            state = dram.sim_init(static, batch=P,
                                  channels=sh[0] if len(sh) == 2 else None)
        state, frames = dram.run_sweep_segment(seg, static, params_batch,
                                               state)
        if telemetry is not None:
            telemetry.add(frames)
        if checkpoint_dir and checkpoint_every and \
                (i + 1) % checkpoint_every == 0:
            ckpt_lib.save_sim_state(checkpoint_dir, i + 1, state)
    assert state is not None, "empty segment stream"
    if telemetry is not None:
        telemetry.close(state)
    return dram.finalize(state)
