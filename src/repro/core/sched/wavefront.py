"""Bank-wavefront execution of the DRAM simulator scan (DESIGN.md §10).

The serial fused scan (``dram.make_step``) burns one ``lax.scan`` step per
request even though requests to *distinct banks* are independent in the
bank-local half of the model (FTS decision, row-buffer outcome, relocation
cost) and couple only through the thin channel-shared state (data bus,
MSHR rings).  This module converts that last serial bottleneck into a
vectorized one:

 * ``form_waves`` — a host-side **compile pass** that groups a (scheduled)
   trace into *waves*: maximal order-preserving runs of requests to
   distinct banks, padded to a fixed width ``W`` with no-op requests that
   are assigned the wave's **unused** banks (so every wave's bank column
   holds ``W`` distinct banks — scatters are deterministic and no-op lanes
   write their own untouched bank's state back).
 * ``make_wave_step`` — the wave scan body: one ``lax.scan`` step consumes
   a whole wave.  The bank-local half runs as ``jax.vmap`` of the exact
   same ``dram.make_decision_fn`` the serial scan uses; the channel-shared
   half (bus serialization, MSHR closed loop) is resolved by the
   **in-wave ordered prefix** in closed form — per-core prefix counts
   locate each lane's pre-wave MSHR slot and a ``cummax`` unrolls the bus
   recurrence — no inner loop at all.  Per-request ``step_id`` (LRU
   stamps, Random victim hash) is the carried retire count plus the
   in-wave prefix count of real lanes.

Because the decision function is shared and the prefix replays the serial
bus/MSHR arithmetic lane by lane, wavefront results are **bitwise-equal**
to the serial fused scan on the same (FCFS-)ordered trace — the pinning
discipline of the fused-vs-dense split, enforced by ``tests/test_sched.py``
across all six mechanisms x four replacement policies and by the
``BENCH_wavefront.json`` report of ``benchmarks/sweep_engine.py``.

Where it pays (measured, DESIGN.md §10): the wave step's per-lane work is
gather/scatter-bound on CPU, so in the *batched* sweep regime (params x
channel vmap, e.g. the fig12 grid as one ``run_sweep``) the serial fused
scan is already at the index-op throughput floor and waves cannot beat
it — ``run_sweep`` stays the batched engine.  In the **single-stream
regime** (one config, one channel: ``run_single_core``-style runs,
interactive exploration) the serial scan is per-step *dispatch*-bound and
the wave scan retires a whole wave per step for the same overhead: ~3x
requests/sec at width 8 with a ``lookahead=32`` window (the floor
asserted by ``benchmarks/sweep_engine.py``).

The Pallas ``fts_lookup`` path is not used inside waves (its scalar-
prefetched bank selection does not vmap over the lane axis); the pure-JAX
formulation it falls back to is bitwise-identical (``tests/test_hotloop.
py``), so a ``fts_kernel=True`` static still reproduces the serial scan's
counters exactly.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dram
from repro.core import fts as fts_lib
from repro.core.timing import (DDR4, GEOM, DRAMGeometry, DRAMTimings,
                               MechConfig, MechParams, StaticConfig)

__all__ = ["form_waves", "linearize_waves", "wave_stats", "make_wave_step",
           "pad_waves", "resume_waves", "run_segment_waves",
           "simulate_waves", "run_sweep_waves", "run_channel_waves"]

# Default wave width: half the banks.  Wider waves raise the padded-lane
# gather/scatter cost faster than occupancy (workload windows rarely hold
# more than ~7 distinct banks); 8 is the measured sweet spot on the paper
# workloads.  ``form_waves(width=...)`` overrides per call.
DEFAULT_WIDTH = 8


def _form_channel(t: np.ndarray, bank: np.ndarray, core: np.ndarray,
                  width: int, n_banks: int,
                  lookahead: int) -> List[List[int]]:
    """Greedy wave formation for one channel.  No-op requests are dropped
    (inert by the DESIGN.md §9 contract).

    ``lookahead = 0`` is strictly order-preserving: a wave closes when it
    is full or when its next request's bank repeats, so the linearized
    wave order IS the input order (the FCFS-bitwise case).

    ``lookahead > 0`` models the controller's bank-level parallelism: the
    oldest request of any bank not yet in the wave may be pulled forward
    past blocked (same-bank) requests, from a transaction-queue window of
    ``lookahead`` pending requests.  Per-bank FIFO order is preserved by
    construction (the window is walked oldest-first), so the linearized
    wave order is a bounded reordering — exactly what a controller that
    issues to ready banks out of order produces.  The serial oracle for a
    lookahead trace is the linearized order (``linearize_waves``).

    Waves additionally take at most ``dram.N_MSHR`` requests per core —
    a core cannot have more in flight anyway — which lets the wave step
    resolve every MSHR read from pre-wave state.
    """
    idxs = np.flatnonzero(t < dram.NOOP_ISSUE).tolist()
    bl, cl = bank.tolist(), core.tolist()
    waves: List[List[int]] = []
    cur: List[int] = []
    used = [False] * n_banks
    core_cnt: dict = {}
    if lookahead <= 0:
        for i in idxs:
            b = bl[i]
            if used[b] or len(cur) == width \
                    or core_cnt.get(cl[i], 0) >= dram.N_MSHR:
                waves.append(cur)
                cur = []
                used = [False] * n_banks
                core_cnt = {}
            cur.append(i)
            used[b] = True
            core_cnt[cl[i]] = core_cnt.get(cl[i], 0) + 1
        if cur:
            waves.append(cur)
        return waves
    win = idxs[:lookahead]
    nxt = min(lookahead, len(idxs))
    while win:
        pick = None
        if len(cur) < width:
            blocked = list(used)
            for k, i in enumerate(win):
                b = bl[i]
                if blocked[b]:
                    continue
                if core_cnt.get(cl[i], 0) >= dram.N_MSHR:
                    # the skipped lane's bank must block for the rest of
                    # the wave, or a younger same-bank request would be
                    # pulled past it (per-bank FIFO is the contract)
                    blocked[b] = True
                    continue
                pick = k
                break
        if pick is None:               # wave full or every window bank busy
            waves.append(cur)
            cur = []
            used = [False] * n_banks
            core_cnt = {}
            continue
        i = win.pop(pick)
        cur.append(i)
        used[bl[i]] = True
        core_cnt[cl[i]] = core_cnt.get(cl[i], 0) + 1
        if nxt < len(idxs):
            win.append(idxs[nxt])
            nxt += 1
    if cur:
        waves.append(cur)
    return waves


def _emit_channel(leaves: dict, waves: List[List[int]], n_waves: int,
                  width: int, n_banks: int) -> dict:
    """Materialize one channel's (n_waves, width) wave-major arrays.
    Padding lanes take the wave's unused banks (distinct from every real
    lane's bank), ``t_issue = NOOP_ISSUE`` and neutral fields."""
    out = {
        "t_issue": np.full((n_waves, width), dram.NOOP_ISSUE, np.int32),
        "bank": np.zeros((n_waves, width), np.int32),
        "row": np.zeros((n_waves, width), np.int32),
        "col": np.zeros((n_waves, width), np.int32),
        "is_write": np.zeros((n_waves, width), bool),
        "core": np.zeros((n_waves, width), np.int32),
    }
    # all-noop filler waves (ragged channel counts) use banks 0..width-1
    out["bank"][:] = np.arange(width, dtype=np.int32)
    for w, members in enumerate(waves):
        k = len(members)
        for name in out:
            out[name][w, :k] = leaves[name][members]
        used = set(leaves["bank"][members].tolist())
        pads = [b for b in range(n_banks) if b not in used][:width - k]
        out["bank"][w, k:] = np.asarray(pads, np.int32)
    return out


def form_waves(trace: dram.Trace, width: int | None = None,
               lookahead: int = 0,
               geom: DRAMGeometry = GEOM) -> dram.Trace:
    """Compile a (T,) / (C, T) trace into wave-major (n_waves, W) /
    (C, n_waves, W) leaves for the wave scan.

    ``width`` defaults to ``DEFAULT_WIDTH`` (a wave can never hold two
    requests to one bank, so ``geom.n_banks`` caps it); any ``width <=
    geom.n_banks`` is valid and trades wave occupancy against per-step
    padding work.  ``lookahead = 0`` preserves the input service order
    exactly (bitwise FCFS oracle); ``lookahead > 0`` pulls requests of
    idle banks forward from a bounded transaction-queue window (bank-level
    parallelism — see ``_form_channel``), with the linearized wave order
    (``linearize_waves``) as the serial oracle.  Channels are formed
    independently and padded to a shared wave count with all-no-op waves.
    """
    W = min(DEFAULT_WIDTH, geom.n_banks) if width is None else width
    assert 1 <= W <= geom.n_banks, (W, geom.n_banks)
    t = np.asarray(trace.t_issue)
    leaves = {name: np.asarray(x) for name, x in trace._asdict().items()}
    if t.ndim == 1:
        waves = _form_channel(t, leaves["bank"], leaves["core"], W,
                              geom.n_banks, lookahead)
        out = _emit_channel(leaves, waves, max(len(waves), 1), W,
                            geom.n_banks)
        return dram.Trace(**out)
    per_chan = [_form_channel(t[c], leaves["bank"][c], leaves["core"][c],
                              W, geom.n_banks, lookahead)
                for c in range(t.shape[0])]
    n_waves = max(1, max(len(w) for w in per_chan))
    chans = [_emit_channel({k: v[c] for k, v in leaves.items()},
                           per_chan[c], n_waves, W, geom.n_banks)
             for c in range(t.shape[0])]
    return dram.Trace(**{k: np.stack([ch[k] for ch in chans])
                         for k in chans[0]})


def linearize_waves(wtrace: dram.Trace) -> dram.Trace:
    """Flatten a wave-compiled trace back into the serial service order the
    wave scan implements (wave-major, pads dropped; multi-channel outputs
    are right-padded with no-ops to the longest channel).  The serial scan
    on this trace is the bitwise oracle of the wave scan on ``wtrace`` —
    for ``lookahead = 0`` formations it equals the input order."""
    t = np.asarray(wtrace.t_issue)
    leaves = {name: np.asarray(x) for name, x in wtrace._asdict().items()}
    if t.ndim == 2:
        flat = {k: v.reshape(-1) for k, v in leaves.items()}
        keep = np.flatnonzero(flat["t_issue"] < dram.NOOP_ISSUE)
        return dram.Trace(**{k: v[keep] for k, v in flat.items()})
    chans = [linearize_waves(dram.Trace(
        **{k: v[c] for k, v in leaves.items()})) for c in range(t.shape[0])]
    t_max = max(np.asarray(c.t_issue).shape[0] for c in chans)
    chans = [dram.noop_pad(c, t_max) for c in chans]
    return dram.Trace(*[np.stack([np.asarray(getattr(c, f)) for c in chans])
                        for f in dram.Trace._fields])


def wave_stats(wtrace: dram.Trace) -> dict:
    """Occupancy of a wave-compiled trace: how many scan steps it saved."""
    t = np.asarray(wtrace.t_issue)
    real = int((t < dram.NOOP_ISSUE).sum())
    n_waves = int(np.prod(t.shape[:-1]))
    return {
        "n_requests": real,
        "n_waves": n_waves,
        "width": int(t.shape[-1]),
        "mean_fill": round(real / max(n_waves, 1), 2),
    }


def make_wave_step(static: StaticConfig, geom: DRAMGeometry = GEOM):
    """Build the wave scan body: ``step(params, carry, wave)`` where the
    ``wave`` leaves are ``(W,)`` distinct-bank requests in service order.
    Carry and counters are exactly ``dram.make_step``'s."""
    # the Pallas lookup's scalar-prefetched bank selection does not vmap
    # over the lane axis; its pure-JAX formulation is bitwise-identical
    # (tests/test_hotloop.py), so the wave body always uses that one
    static = dataclasses.replace(static, fts_kernel=False)
    decide = jax.vmap(dram.make_decision_fn(static, geom),
                      in_axes=(None, None, 0, 0))
    has_cache = static.has_cache

    def step(params: MechParams, carry, wave: dram.Trace):
        state, cnt = carry
        p = params
        W = wave.t_issue.shape[0]
        real = wave.t_issue < dram.NOOP_ISSUE
        reali = real.astype(jnp.int32)
        # step_id = retired-real count before each lane (serial semantics)
        k_inc = jnp.cumsum(reali)               # real lanes <= i, inclusive
        step_ids = (cnt.reads + cnt.writes) + k_inc - reali
        # ---- bank-local half: the serial decision fn, vmapped ------------
        dec = decide(params, state, wave, step_ids)

        # ---- channel-shared half: the in-wave ordered prefix, closed form.
        # The serial recurrences resolve without a lane loop:
        #  * MSHR — wave formation caps same-core lanes at N_MSHR, so every
        #    lane's ring read refers to PRE-wave state: its slot is the
        #    pre-wave cursor advanced by the count of earlier same-core
        #    real lanes (m), never a slot written in this wave.
        #  * bus — each real lane applies done = max(a, bus) + bl; unrolling
        #    the composition gives done_i = max(bus0, max_{real j<=i}(a_j +
        #    (1 - K_j) * bl)) + K_i * bl with K = cumsum(real), a cummax.
        busy0 = state.busy
        core = wave.core
        lane = jnp.arange(W)
        m = jnp.sum((lane[:, None] > lane[None, :])
                    & (core[:, None] == core[None, :]) & real[None, :],
                    axis=1).astype(jnp.int32)
        mshr_slot = jnp.remainder(state.mshr_idx[core] + m, dram.N_MSHR)
        mshr_free = state.mshr_ring[core, mshr_slot]
        t_ready = jnp.maximum(wave.t_issue, mshr_free)
        # distinct banks per wave: every lane's bank busy is pre-wave
        t0 = jnp.maximum(t_ready, busy0[wave.bank])
        a = t0 + dec.pre_act + p.cas
        g = jnp.where(real, a + (1 - k_inc) * p.bl, -fts_lib.BIG)
        done = jnp.maximum(state.bus_free, jax.lax.cummax(g)) + k_inc * p.bl
        serv_end = t0 + dec.pre_act + p.ccd
        busy_new = serv_end + dec.reloc_cost
        lat_ns = ((done - t_ready) // 8).astype(jnp.int32)
        # pads scatter out of bounds -> dropped (a real lane of the same
        # core may own the same pre-wave slot; pads must not race it)
        ring = state.mshr_ring.at[
            core, jnp.where(real, mshr_slot, dram.N_MSHR)].set(
                done, mode="drop")
        idx = jnp.remainder(
            state.mshr_idx + jnp.zeros_like(state.mshr_idx).at[core].add(
                reali), dram.N_MSHR)
        bus = jnp.maximum(state.bus_free, jnp.max(g)) + k_inc[-1] * p.bl
        t_end = jnp.maximum(cnt.t_end, jnp.max(
            jnp.where(real, jnp.maximum(done, busy_new), 0)))

        # ---- scatters: every wave has W *distinct* banks -----------------
        b = wave.bank
        if has_cache:
            new_fts = fts_lib.apply_write(state.fts, b, p.segs_per_row,
                                          dec.write)
        else:
            new_fts = state.fts
        state = dram.BankState(
            open_row=state.open_row.at[b].set(
                jnp.where(real, dec.new_open, state.open_row[b])),
            busy=busy0.at[b].set(jnp.where(real, busy_new, busy0[b])),
            fts=new_fts,
            mshr_ring=ring,
            mshr_idx=idx,
            bus_free=bus,
        )

        isum = lambda m: jnp.sum(m.astype(jnp.int32))
        act = (~dec.row_hit) & real
        cnt = dram.Counters(
            acts_slow=cnt.acts_slow + isum(act & ~dec.served_fast),
            acts_fast=cnt.acts_fast + isum(act & dec.served_fast),
            reads=cnt.reads + isum((~wave.is_write) & real),
            writes=cnt.writes + isum(wave.is_write & real),
            reloc_blocks=cnt.reloc_blocks + jnp.sum(dec.moved),
            wb_blocks=cnt.wb_blocks + jnp.sum(dec.wb),
            row_hits=cnt.row_hits + isum(dec.row_hit & real),
            cache_hits=cnt.cache_hits + isum(dec.hit),
            insertions=cnt.insertions + jnp.sum(dec.n_ins),
            # saturates at the same cap as the serial scan (dram.LAT_SUM_CAP)
            # so the bitwise-equality contract holds through saturation
            lat_sum_ns=jnp.minimum(
                cnt.lat_sum_ns.at[wave.core].add(jnp.where(real, lat_ns, 0)),
                dram.LAT_SUM_CAP),
            req_cnt=cnt.req_cnt.at[wave.core].add(reali),
            t_end=t_end,
        )
        return (state, cnt), None

    return step


def pad_waves(wtrace: dram.Trace, n_waves: int) -> dram.Trace:
    """Right-pad a wave-compiled (n, W) / (C, n, W) trace to ``n_waves``
    waves with all-no-op filler waves (banks 0..W-1, inert by the §9
    contract).  Chunked wavefront replay pads every chunk's wave count to
    a shared bucket so all chunks reuse one compiled wave scan
    (``core/streaming.py``)."""
    t = np.asarray(wtrace.t_issue)
    cur, W = t.shape[-2], t.shape[-1]
    assert cur <= n_waves, (cur, n_waves)
    if cur == n_waves:
        return wtrace
    lead = t.shape[:-2]
    fill = {
        "t_issue": np.full(lead + (n_waves - cur, W), dram.NOOP_ISSUE,
                           np.int32),
        "bank": np.broadcast_to(np.arange(W, dtype=np.int32),
                                lead + (n_waves - cur, W)).copy(),
        "row": np.zeros(lead + (n_waves - cur, W), np.int32),
        "col": np.zeros(lead + (n_waves - cur, W), np.int32),
        "is_write": np.zeros(lead + (n_waves - cur, W), bool),
        "core": np.zeros(lead + (n_waves - cur, W), np.int32),
    }
    return dram.Trace(**{
        k: np.concatenate([np.asarray(v), fill[k]], axis=-2)
        for k, v in wtrace._asdict().items()})


def _scan_waves_segment(step, params: MechParams, wtrace: dram.Trace,
                        state: dram.SimState) -> dram.SimState:
    carry, _ = jax.lax.scan(functools.partial(step, params),
                            (state.bank, state.cnt), wtrace)
    return dram.SimState(*carry)


def _scan_waves(step, params: MechParams, wtrace: dram.Trace,
                static: StaticConfig) -> dram.Counters:
    carry0 = dram.SimState(dram.init_state(static), dram.init_counters())
    return _scan_waves_segment(step, params, wtrace, carry0).cnt


def _resume_waves(wtrace: dram.Trace, static: StaticConfig,
                  params: MechParams, state: dram.SimState
                  ) -> dram.SimState:
    if static.telemetry:
        # the wave scan carries (bank, cnt) only — it would silently drop
        # the telemetry cursor (DESIGN.md §15); refuse rather than lie
        raise ValueError("telemetry windows are not supported under "
                         "wavefront execution (set telemetry=0)")
    step = make_wave_step(static)
    if wtrace.t_issue.ndim == 2:
        return _scan_waves_segment(step, params, wtrace, state)
    return jax.vmap(lambda tr, st: _scan_waves_segment(step, params, tr, st)
                    )(wtrace, state)


def resume_waves(wtrace: dram.Trace, static: StaticConfig,
                 params: MechParams, state: dram.SimState) -> dram.SimState:
    """Advance a ``dram.SimState`` over one wave-compiled chunk.

    The wave scan's carry IS ``dram.SimState`` (``make_wave_step`` shares
    the serial step's carry), so a wavefront replay chunks exactly like
    the serial one: ``dram.sim_init`` → ``resume_waves`` per chunk (waves
    formed per chunk by ``form_waves``) → ``dram.finalize``.  Wave
    *packing* differs across chunk boundaries — a wave never spans two
    chunks — but the in-wave prefix replays serial semantics lane by
    lane, so counters stay bitwise-equal to the monolithic serial scan
    regardless (``tests/test_streaming.py``).  Jitted form:
    ``run_segment_waves``."""
    if isinstance(wtrace.t_issue, jax.core.Tracer):
        dram._note_trace(f"wave_segment/{static.mechanism}")
    return _resume_waves(wtrace, static, params, state)


run_segment_waves = jax.jit(resume_waves, static_argnums=(1,))


def simulate_waves(wtrace: dram.Trace, static: StaticConfig,
                   params: MechParams) -> dram.Counters:
    """Un-jitted reference over a wave-compiled trace: (n_waves, W) or
    (C, n_waves, W) leaves, one params point."""
    if isinstance(wtrace.t_issue, jax.core.Tracer):
        dram._note_trace(f"wave/{static.mechanism}")
    C = wtrace.t_issue.shape[0] if wtrace.t_issue.ndim == 3 else None
    state = dram.sim_init(static, channels=C)
    return dram.finalize(_resume_waves(wtrace, static, params, state))


_simulate_waves_jit = jax.jit(simulate_waves, static_argnums=(1,))


@functools.partial(jax.jit, static_argnums=(1,))
def run_sweep_waves(wtrace: dram.Trace, static: StaticConfig,
                    params_batch: MechParams) -> dram.Counters:
    """Wavefront counterpart of ``dram.run_sweep``: one compiled wave scan
    vmapped over a stacked params batch.  Counters are bitwise-equal to
    ``dram.run_sweep`` on the trace the waves were formed from."""
    dram._note_trace(f"wave_sweep/{static.mechanism}")
    step = make_wave_step(static)
    if wtrace.t_issue.ndim == 2:
        one = lambda prm: _scan_waves(step, prm, wtrace, static)
    else:
        one = lambda prm: jax.vmap(
            lambda tr: _scan_waves(step, prm, tr, static))(wtrace)
    return jax.vmap(one)(params_batch)


def run_channel_waves(trace: dram.Trace, cfg: MechConfig,
                      t: DRAMTimings = DDR4,
                      width: int | None = None) -> dram.Counters:
    """Convenience: form waves for ``trace`` and simulate one config —
    the wavefront analogue of ``dram.run_channel``."""
    wtr = form_waves(trace, width=width)
    return _simulate_waves_jit(wtr, cfg.static, cfg.params(t))
