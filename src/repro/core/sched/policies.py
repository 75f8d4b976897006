"""Memory-controller scheduling policies as trace-preprocessing passes.

The simulator's seed contract was "the trace order IS the schedule"
(DESIGN.md §7).  This module adds the controller the paper actually
evaluates under (§7, FR-FCFS): a ``timing.SchedConfig`` names a scheduling
discipline and ``schedule`` realizes it as a **per-channel service-order
permutation** computed on the device *before* the compiled scan runs.
Arrival times (``t_issue``) are untouched — only the order in which the
controller serves requests changes — so a scheduled trace has exactly the
shape and dtype of its input and replays through the very same compiled
scan (one compilation serves a whole policy grid; DESIGN.md §10).

Model, per channel:

 * **Per-bank request queues** are implied by the window walk: the
   controller looks at the next ``queue_depth`` pending requests in arrival
   order (the transaction queue) — within that window each bank's requests
   appear in per-bank FIFO order, which is exactly a per-bank queue of
   depth <= queue_depth.
 * **FCFS** serves the window head, i.e. the identity permutation.
 * **FR-FCFS** serves the oldest *row hit* in the window — a request whose
   row matches the last row the controller scheduled to that bank — and
   falls back to the window head when there is none.  A **starvation cap**
   bounds unfairness: once the oldest pending request has been bypassed
   ``starve_cap`` times it is served unconditionally (``starve_cap=0``
   therefore degenerates to FCFS, a tested identity).
 * **Write-drain batching** composes in front as posted writes: writes are
   parked in a write queue while reads flow past, and once the queue holds
   ``drain_batch`` entries it drains as one batch sorted by (bank, row) —
   the row-locality batching real controllers drain writes for.  Deferred
   writes keep their arrival ``t_issue``, so their measured latency
   honestly includes the drain delay.  (Same-address read-after-write
   ordering is not preserved; the simulator carries no data values, so
   only latency statistics are affected — documented in DESIGN.md §10.)

No-op padding requests (``dram.NOOP_ISSUE``) are never reordered: the real
requests are scheduled and the no-ops follow them in index order,
preserving the "padding is a suffix" invariant of
``simulator.sweep_traces``.

``schedule`` computes every lane of a (..., T) batch in one jitted
program (``service_order``): the write drain is one stable sort per lane
and the FR-FCFS walk one ``lax.scan`` of T trips, vmapped over lanes, so
a point's whole stacked batch is one dispatch and nothing is copied to the
host.  ``write_drain_perm`` and ``frfcfs_perm`` are the same two passes in
plain Python, kept as the oracle the tests hold the device form to, and
``StreamScheduler`` is their incremental form for chunked replays.
"""
from __future__ import annotations

import functools
import math
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.dram import NOOP_ISSUE, Trace
from repro.obs.trace import span
from repro.core.timing import (GEOM, SCHED_FCFS, TICKS_PER_NS, DRAMGeometry,
                               SchedConfig)

__all__ = ["SchedConfig", "SCHED_FCFS", "schedule", "frfcfs_perm",
           "write_drain_perm", "StreamScheduler"]


def write_drain_perm(bank: Sequence[int], row: Sequence[int],
                     is_write: Sequence[bool], order: Sequence[int],
                     drain_batch: int) -> List[int]:
    """Posted-write pre-pass: reads keep ``order``; writes queue up and
    drain as (bank, row)-sorted batches of ``drain_batch``.  Returns the
    new service order (a permutation of ``order``)."""
    out: List[int] = []
    wq: List[int] = []

    def drain():
        # sort stably by (bank, row): the drained batch sweeps each bank's
        # rows once instead of ping-ponging the row buffers
        wq.sort(key=lambda j: (bank[j], row[j]))
        out.extend(wq)
        wq.clear()

    for i in order:
        if is_write[i]:
            wq.append(i)
            if len(wq) >= drain_batch:
                drain()
        else:
            out.append(i)
    if wq:
        drain()
    return out


def frfcfs_perm(bank: Sequence[int], row: Sequence[int],
                t_issue: Sequence[int], order: Sequence[int],
                queue_depth: int, starve_cap: int, n_banks: int,
                arrival_window: int) -> List[int]:
    """FR-FCFS window walk over ``order``: serve the oldest row hit within
    the ``queue_depth`` transaction queue, head-of-queue after
    ``starve_cap`` bypasses of the oldest pending request.  A candidate
    may bypass only if it was issued within ``arrival_window`` ticks of
    the oldest pending request — the queue holds *arrived* requests, not
    the issue-future.  Returns the service order."""
    order = list(order)
    n = len(order)
    win = order[:queue_depth]          # the transaction-queue window
    nxt = min(queue_depth, n)          # next arrival to refill the window
    last_row = [-1] * n_banks          # last row scheduled per bank
    out: List[int] = []
    bypass = 0
    for _ in range(n):
        pick = 0
        if bypass < starve_cap and win:
            horizon = t_issue[win[0]] + arrival_window
            for k, i in enumerate(win):
                if t_issue[i] > horizon:
                    continue           # not plausibly arrived yet
                if row[i] == last_row[bank[i]]:
                    pick = k
                    break
        i = win.pop(pick)
        bypass = 0 if pick == 0 else bypass + 1
        out.append(i)
        last_row[bank[i]] = row[i]
        if nxt < n:
            win.append(order[nxt])
            nxt += 1
    return out


def _schedule_channel(t: np.ndarray, bank: np.ndarray, row: np.ndarray,
                      is_write: np.ndarray, sc: SchedConfig,
                      n_banks: int) -> np.ndarray:
    """Service-order permutation for one channel's arrays, in plain
    Python: the oracle of ``service_order``."""
    real = np.flatnonzero(t < NOOP_ISSUE)
    bl, rl, wl = bank.tolist(), row.tolist(), is_write.tolist()
    order: List[int] = real.tolist()
    if sc.write_drain:
        order = write_drain_perm(bl, rl, wl, order, sc.drain_batch)
    if sc.policy == "frfcfs":
        order = frfcfs_perm(bl, rl, t.tolist(), order, sc.queue_depth,
                            sc.starve_cap, n_banks,
                            sc.arrival_window_ns * TICKS_PER_NS)
    noops = np.flatnonzero(t >= NOOP_ISSUE)
    return np.concatenate([np.asarray(order, np.int64), noops]) \
        if noops.size else np.asarray(order, np.int64)


def _drain_slots(bank, row, wr, rd, D: int):
    """Every real request's slot in ``write_drain_perm``'s order, for one
    lane (``wr``/``rd``: real writes/reads).  A read takes the first free
    slot: one after every earlier read and every batch of D writes closed
    before it.  A full batch starts at the free slot where its last write
    arrives, the final partial batch after everything else, and within a
    batch writes go by (bank, row), then arrival."""
    T = wr.shape[0]
    n = functools.partial(jnp.sum, dtype=jnp.int32)
    rank = jnp.cumsum(wr, dtype=jnp.int32) - 1       # among the writes
    closes = wr & (rank % D == D - 1)
    free = jnp.cumsum(rd, dtype=jnp.int32) - rd \
        + D * (jnp.cumsum(closes, dtype=jnp.int32) - closes)
    start = lax.cummin(jnp.where(closes, free, n(rd) + D * n(closes)),
                       reverse=True)
    # each batch's D write ranks sorted by (bank, row, rank); empty slots
    # of the partial batch sort last
    nb = -(-T // D)
    at = jnp.where(wr, rank, nb * D)

    def batched(x):
        return jnp.full(nb * D, 2 ** 31 - 1, jnp.int32).at[at].set(
            x, mode="drop").reshape(nb, D)

    by = lax.sort((batched(bank), batched(row),
                   jnp.arange(nb * D, dtype=jnp.int32).reshape(nb, D)),
                  num_keys=3)[-1]
    place = jnp.zeros(nb * D, jnp.int32).at[by.ravel()].set(
        jnp.tile(jnp.arange(D, dtype=jnp.int32), nb), unique_indices=True)
    return jnp.where(wr, start + place[rank], free)


def _drain_order(t, bank, row, is_write, sc: SchedConfig):
    """One lane's order after the write-drain stage, no-ops last in index
    order: each request's slot, then one scatter (no sort of the lane)."""
    T = t.shape[0]
    real = t < NOOP_ISSUE
    slot = jnp.where(real, jnp.cumsum(real, dtype=jnp.int32) - 1,
                     jnp.sum(real, dtype=jnp.int32)
                     + jnp.cumsum(~real, dtype=jnp.int32) - 1)
    if sc.write_drain:
        slot = jnp.where(real, _drain_slots(bank, row, real & is_write,
                                            real & ~is_write,
                                            sc.drain_batch), slot)
    return jnp.zeros(T, jnp.int32).at[slot].set(
        jnp.arange(T, dtype=jnp.int32), unique_indices=True)


def _frfcfs_walk(order, t, bank, row, n_real, sc: SchedConfig,
                 n_banks: int):
    """``frfcfs_perm`` over one lane's ``order[:n_real]`` as a scan of T
    trips (the trips past ``n_real`` emit garbage the caller drops).  The
    carry holds the window's positions and their (t, bank, row), so a
    trip reads nothing but its refill, ``order[s + queue_depth]``."""
    T, Q = order.shape[0], sc.queue_depth
    # the differences compared are of real arrival times (< 2**30)
    aw = min(sc.arrival_window_ns * TICKS_PER_NS, 2 ** 31 - 1)
    cols = jnp.pad(jnp.stack([order, t[order], bank[order], row[order]]),
                   ((0, 0), (0, Q)))
    slot = jnp.arange(Q, dtype=jnp.int32)
    banks = jnp.arange(n_banks, dtype=jnp.int32)

    def trip(carry, refill):
        win, last_row, m, bypass, s = carry        # win: (4, Q)
        _, t_w, bank_w, row_w = win
        open_row = jnp.sum(jnp.where(bank_w[:, None] == banks, last_row, 0),
                           axis=1)
        hit = (slot < m) & (t_w - t_w[0] <= aw) & (row_w == open_row)
        first = jnp.min(jnp.where(hit, slot, Q))
        pick = jnp.where((bypass < sc.starve_cap) & (first < Q), first, 0)
        i, _, b, r = jnp.sum(jnp.where(slot == pick, win, 0), axis=1)
        last_row = jnp.where(banks == b, r, last_row)
        bypass = jnp.where(pick == 0, 0, bypass + 1)
        shifted = jnp.concatenate([win[:, 1:], refill[:, None]], axis=1)
        win = jnp.where(slot < pick, win, shifted)
        m = jnp.where(s + Q < n_real, m, m - 1)
        return (win, last_row, m, bypass, s + 1), i

    zero = jnp.int32(0)
    init = (cols[:, :Q], jnp.full(n_banks, -1, jnp.int32),
            jnp.minimum(Q, n_real), zero, zero)
    return lax.scan(trip, init, cols[:, Q:].T)[1]


def _lane_order(t, bank, row, is_write, sc: SchedConfig, n_banks: int):
    order = _drain_order(t, bank, row, is_write, sc)
    if sc.policy != "frfcfs":
        return order
    n_real = jnp.sum(t < NOOP_ISSUE, dtype=jnp.int32)
    walked = _frfcfs_walk(order, t, bank, row, n_real, sc, n_banks)
    return jnp.where(jnp.arange(t.shape[0]) < n_real, walked, order)


@functools.partial(jax.jit, static_argnames=("sc", "n_banks"))
def service_order(trace: Trace, sc: SchedConfig, n_banks: int) -> Trace:
    """Every lane of a (..., T) trace in ``sc``'s service order: one
    program for the whole batch, on the device."""
    shape = trace.t_issue.shape
    lanes = jax.tree.map(lambda x: jnp.reshape(x, (-1, shape[-1])), trace)
    perm = jax.vmap(functools.partial(_lane_order, sc=sc, n_banks=n_banks))(
        lanes.t_issue, lanes.bank, lanes.row, lanes.is_write)
    return jax.tree.map(
        lambda x: jnp.take_along_axis(x, perm, axis=1).reshape(shape), lanes)


def schedule(trace: Trace, sc: Optional[SchedConfig],
             geom: DRAMGeometry = GEOM) -> Trace:
    """Reorder a trace into the service order ``sc``'s controller would
    issue.  Every leading index is a lane (one channel): (T,), (C, T), or
    a stacked (W*C, T) batch.  FCFS (or ``sc=None``) returns the trace
    object untouched — the existing zero-controller behavior; any other
    controller is one ``service_order`` program over every lane.  Each
    call is one ``repro.sched.schedule`` span (stats: ``policy``;
    ``requests``, the trace's entries; ``lanes``)."""
    shape = np.shape(trace.t_issue)
    with span("repro.sched.schedule",
              policy=SCHED_FCFS.policy if sc is None else sc.policy,
              requests=math.prod(shape), lanes=math.prod(shape[:-1])):
        if sc is None or sc.is_identity:
            return trace
        return service_order(trace, sc, geom.n_banks)


class StreamScheduler:
    """The carried scheduler window of a chunked replay (DESIGN.md §13).

    ``schedule`` needs the whole trace in hand; a streamed replay only
    ever holds one chunk.  This class re-expresses the same two passes —
    posted-write drain in front of the FR-FCFS window walk — as an
    incremental pipeline whose carried state (write queue, transaction-
    queue window, per-bank last-scheduled row, starvation counter)
    survives chunk boundaries.  Both walks decide from a *bounded* window
    (``drain_batch`` writes / ``queue_depth`` requests), so emitting a
    pick only once the window is provably identical to the monolithic
    walk's — full, or flushing at end of stream — reproduces the
    monolithic permutation **exactly**; ``tests/test_streaming.py`` pins
    ``feed``+``flush`` against ``schedule`` bitwise.

    One instance schedules ONE channel.  ``feed`` takes (T,) trace leaves
    (chunk-interior no-ops are dropped — they are padding, not requests;
    the streaming driver re-packs emitted requests into fixed-shape
    segments and re-pads itself) and returns whatever requests became
    committable; ``flush`` drains the carried windows at end of stream.
    """

    def __init__(self, sc: Optional[SchedConfig],
                 geom: DRAMGeometry = GEOM):
        self.sc = sc
        self.identity = sc is None or sc.is_identity
        self.n_banks = geom.n_banks
        self.wq: List[tuple] = []      # posted writes awaiting a drain
        self.win: List[tuple] = []     # FR-FCFS transaction-queue window
        self.last_row = [-1] * geom.n_banks
        self.bypass = 0

    @staticmethod
    def _records(trace: Trace) -> List[tuple]:
        t = np.asarray(trace.t_issue)
        keep = np.flatnonzero(t < NOOP_ISSUE)
        cols = [np.asarray(x)[keep].tolist()
                for x in (t, trace.bank, trace.row, trace.col,
                          trace.is_write, trace.core)]
        return list(zip(*cols)) if keep.size else []

    @staticmethod
    def _emit(records: List[tuple]) -> Trace:
        if not records:
            z = np.zeros(0, np.int32)
            return Trace(z, z, z, z, np.zeros(0, bool), z)
        a = list(zip(*records))
        return Trace(t_issue=np.asarray(a[0], np.int32),
                     bank=np.asarray(a[1], np.int32),
                     row=np.asarray(a[2], np.int32),
                     col=np.asarray(a[3], np.int32),
                     is_write=np.asarray(a[4], bool),
                     core=np.asarray(a[5], np.int32))

    def _drain_writes(self) -> List[tuple]:
        # (bank, row)-sorted batch: same key as write_drain_perm's drain
        self.wq.sort(key=lambda r: (r[1], r[2]))
        out, self.wq = self.wq, []
        return out

    def _stage_drain(self, records: List[tuple]) -> List[tuple]:
        if not (self.sc and self.sc.write_drain):
            return records
        out: List[tuple] = []
        for r in records:
            if r[4]:
                self.wq.append(r)
                if len(self.wq) >= self.sc.drain_batch:
                    out.extend(self._drain_writes())
            else:
                out.append(r)
        return out

    def _frfcfs_step(self) -> tuple:
        """One pick of the monolithic window walk (``frfcfs_perm``) from
        the carried window — callable only when the window state equals
        the monolithic walk's (full window, or end-of-stream)."""
        sc, win = self.sc, self.win
        pick = 0
        if self.bypass < sc.starve_cap and win:
            horizon = win[0][0] + sc.arrival_window_ns * TICKS_PER_NS
            for k, r in enumerate(win):
                if r[0] > horizon:
                    continue
                if r[2] == self.last_row[r[1]]:
                    pick = k
                    break
        r = win.pop(pick)
        self.bypass = 0 if pick == 0 else self.bypass + 1
        self.last_row[r[1]] = r[2]
        return r

    def _stage_frfcfs(self, records: List[tuple],
                      flush: bool) -> List[tuple]:
        if not (self.sc and self.sc.policy == "frfcfs"):
            return records
        out: List[tuple] = []
        qd = self.sc.queue_depth
        for r in records:
            self.win.append(r)
            # the monolithic walk always decides from a full qd window
            # while input remains (pick + immediate refill), so a pick is
            # committed exactly when the carried window reaches qd
            if len(self.win) >= qd:
                out.append(self._frfcfs_step())
        if flush:
            # end of stream: the monolithic walk's window dwindles qd-1..1
            while self.win:
                out.append(self._frfcfs_step())
        return out

    def feed(self, trace: Trace) -> Trace:
        """Schedule one chunk's worth of requests; returns the requests
        whose service position is now decided (possibly spanning earlier
        chunks, possibly empty while windows fill)."""
        records = self._records(trace)
        if self.identity:
            return self._emit(records)
        return self._emit(self._stage_frfcfs(self._stage_drain(records),
                                             flush=False))

    def flush(self) -> Trace:
        """End of stream: drain the write queue and the FR-FCFS window."""
        if self.identity:
            return self._emit([])
        tail: List[tuple] = self._drain_writes() if (
            self.sc and self.sc.write_drain) else []
        return self._emit(self._stage_frfcfs(tail, flush=True))
