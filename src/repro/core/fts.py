"""FIGCache Tag Store (FTS) — the paper's §6 FIGCache policy engine (tag
lookup, insert-any-miss, benefit-based replacement) as a pure-JAX state
machine, layered on the §5 FIGARO relocation substrate modeled in
``core/dram.py``.

The exact same structure drives (a) the cycle-approximate DRAM simulator
(`core/dram.py`) and (b) the TPU-side FIGCache-KV segment cache
(`figkv/kv_cache.py`): entries = {tag, valid, dirty, benefit}, fully
associative within a bank, *insert-any-miss* insertion, and the paper's
*RowBenefit* replacement (evict at row granularity: pick the cache row with
the lowest summed benefit, mark all its segments in a bitvector, then refill
marked slots lowest-benefit-first).  SegmentBenefit / LRU / Random
alternatives implement Figure 14's comparison points.

Shape polymorphism (DESIGN.md §3): arrays are allocated at a **padded
maximum** (``max_slots`` slots, ``max_segs_per_row``-wide eviction
bitvector) and the *effective* geometry — ``n_slots`` active slots arranged
as rows of ``segs_per_row`` segments — arrives as **traced** int32 scalars.
The invariant that makes this exact:

    slots with index >= n_slots are PADDING: their tags stay -1, their
    valid bits stay False, and no code path may select them as a free slot
    or a victim.

Carried aggregates (DESIGN.md §9): the store maintains three derived
quantities as state so the hot-loop decisions are O(1)-update instead of
O(max_slots)-recompute —

  * ``row_sum (max_rows,)`` — per-cache-row benefit sum over active slots
    (row = slot // segs_per_row; ``max_rows == max_slots`` covers
    ``segs_per_row == 1``).  Updated by the benefit delta of every
    ``touch`` / ``insert`` / ``invalidate``.  RowBenefit victim selection
    reduces THIS array (one argmin over rows) plus a one-row
    (max_segs_per_row,) gather — it no longer segment-sums ``benefit``.
  * ``free_list (max_slots,) / n_valid ()`` — a LIFO free-slot stack.
    ``insert`` pops in O(1) (``free_list[n_valid]``), ``invalidate``
    pushes in O(1); ``has_free`` is the O(1) compare
    ``n_valid < n_slots``.  This replaces the full free-slot argmin.
    With no ``invalidate`` in a store's life (the simulator scan) the pop
    order is exactly the old lowest-index-first order; after out-of-order
    invalidations, holes refill most-recently-freed-first.

Aggregate maintenance needs the row geometry, so ``touch`` and
``invalidate`` now take ``segs_per_row``; a store must see ONE consistent
``segs_per_row`` across its lifetime (the simulator's is a per-scan
constant, figkv's a config constant).  ``lookup`` needs no padding mask
(padding can never match a tag); ``insert`` and the benefit-based victim
pickers mask their argmin reductions to the active prefix.  With
``n_slots == max_slots`` and ``segs_per_row == max_segs_per_row`` every
operation is bitwise-identical to an unpadded tag store (regression:
``tests/test_padded_fts.py``; aggregate == recompute property:
``tests/test_hotloop.py``), which is what lets one compiled scan serve an
entire capacity or segment-size sweep (``core/dram.py:run_sweep``).

All ops are branchless (arithmetic select) so they jit/scan/vmap cleanly.
``onehot_read`` / ``onehot_write`` are the simulator hot loop's indexing
rule for the store's small per-bank leaves (DESIGN.md §9).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

BIG = jnp.int32(1 << 30)


class FTS(NamedTuple):
    tags: jax.Array      # (max_slots,) int32 — segment id, valid bit separate
    valid: jax.Array     # (max_slots,) bool
    dirty: jax.Array     # (max_slots,) bool
    benefit: jax.Array   # (max_slots,) int32 — saturating counter
    last_use: jax.Array  # (max_slots,) int32 — step stamp (LRU policy)
    evict_row: jax.Array   # () int32 — row marked for eviction (-1: none)
    evict_mask: jax.Array  # (max_segs_per_row,) bool — paper's bitvector
    miss_tags: jax.Array   # (n_track,) int32 — insertion-threshold tracking
    miss_cnt: jax.Array    # (n_track,) int32
    # -- carried aggregates (DESIGN.md §9) --------------------------------
    row_sum: jax.Array    # (max_rows,) int32 — per-row benefit sum
    free_list: jax.Array  # (max_slots,) int32 — LIFO free-slot stack
    n_valid: jax.Array    # () int32 — valid count == stack pointer


def init(max_slots: int, max_segs_per_row: int, n_track: int = 256) -> FTS:
    """Allocate a tag store at its padded maximum geometry.

    Callers that do not sweep shapes (e.g. ``figkv/``) simply pass their
    exact geometry here and omit ``n_slots`` everywhere else — padding with
    ``max == actual`` is the unpadded tag store.
    """
    return FTS(
        tags=jnp.full((max_slots,), -1, jnp.int32),
        valid=jnp.zeros((max_slots,), bool),
        dirty=jnp.zeros((max_slots,), bool),
        benefit=jnp.zeros((max_slots,), jnp.int32),
        last_use=jnp.zeros((max_slots,), jnp.int32),
        evict_row=jnp.int32(-1),
        evict_mask=jnp.zeros((max_segs_per_row,), bool),
        miss_tags=jnp.full((n_track,), -1, jnp.int32),
        miss_cnt=jnp.zeros((n_track,), jnp.int32),
        row_sum=jnp.zeros((max_slots,), jnp.int32),
        free_list=jnp.arange(max_slots, dtype=jnp.int32),
        n_valid=jnp.int32(0),
    )


def _onehot(i: jax.Array, n: int, trailing: int) -> jax.Array:
    """``i.shape + (n,) + (1,) * trailing`` bool mask, True at ``i``."""
    m = jnp.asarray(i, jnp.int32)[..., None] == jnp.arange(n, dtype=jnp.int32)
    return m.reshape(m.shape + (1,) * trailing)


def onehot_read(x: jax.Array, i: jax.Array) -> jax.Array:
    """``x[i]`` along axis 0 as a masked reduction, not a gather.

    The hot loop's rule for small leaves (DESIGN.md §9): on the bank, core
    and MSHR axes a dense compare/select over a few dozen entries costs
    nothing per lane, where a gather costs one serial index per lane on
    the TPU.  ``i`` is a scalar, or a vector of indices (one read each).
    Exact: the reduction adds zeros to (or ORs False into) one entry."""
    m = _onehot(i, x.shape[0], x.ndim - 1)
    axis = jnp.ndim(i)
    if x.dtype == jnp.bool_:
        return jnp.any(m & x, axis=axis)
    return jnp.sum(jnp.where(m, x, 0), axis=axis, dtype=x.dtype)


def onehot_write(x: jax.Array, i: jax.Array, v: jax.Array) -> jax.Array:
    """``x.at[i].set(v)`` along axis 0 as a one-hot select, not a scatter.

    ``i`` is a scalar (``v`` shaped ``x.shape[1:]``) or a vector of
    *distinct* indices with one row of ``v`` each — the bank wavefront's
    write, where distinctness makes the result the scatter's."""
    n, trailing = x.shape[0], x.ndim - 1
    v = jnp.asarray(v, x.dtype)
    if jnp.ndim(i) == 0:
        return jnp.where(_onehot(i, n, trailing), v, x)
    m = _onehot(i, n, trailing)                       # (K, n, 1...)
    v = v.reshape((v.shape[0], 1) + x.shape[1:])      # (K, 1, ...)
    if x.dtype == jnp.bool_:
        picked = jnp.any(m & v, axis=0)
    else:
        picked = jnp.sum(jnp.where(m, v, 0), axis=0, dtype=x.dtype)
    return jnp.where(jnp.any(m, axis=0), picked, x)


def _active(fts: FTS, n_slots) -> jax.Array:
    """(max_slots,) bool — True for the live (non-padding) slot prefix."""
    idx = jnp.arange(fts.tags.shape[0], dtype=jnp.int32)
    return idx < jnp.asarray(n_slots, jnp.int32)


def masked_argmin(x: jax.Array, mask: jax.Array) -> jax.Array:
    """First index of the minimum of ``x`` restricted to ``mask`` (BIG
    sentinel outside).  All-False mask -> index 0, like ``jnp.argmin``."""
    return jnp.argmin(jnp.where(mask, x, BIG)).astype(jnp.int32)


def lookup(fts: FTS, seg: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """-> (hit: bool, slot: int32). slot undefined when !hit.

    No padding mask needed: padded slots keep ``tags == -1, valid == False``
    for the lifetime of the store (the module invariant), so they can never
    match a segment id.
    """
    m = (fts.tags == seg) & fts.valid
    return jnp.any(m), jnp.argmax(m).astype(jnp.int32)


def touch(fts: FTS, slot: jax.Array, is_write: jax.Array, step: jax.Array,
          benefit_max, segs_per_row) -> FTS:
    """Cache hit: increment saturating benefit, set dirty on writes (§6).

    ``benefit_max`` / ``segs_per_row`` may be Python ints or traced int32
    (sweep engine); ``segs_per_row`` must be the store's one consistent row
    geometry (it routes the benefit delta into ``row_sum``).  ``slot`` must
    come from a successful ``lookup`` and is therefore always an active
    (non-padding) slot."""
    spr = jnp.asarray(segs_per_row, jnp.int32)
    b0 = fts.benefit[slot]
    b = jnp.minimum(b0 + 1, benefit_max)
    return fts._replace(
        benefit=fts.benefit.at[slot].set(b),
        dirty=fts.dirty.at[slot].set(fts.dirty[slot] | is_write),
        last_use=fts.last_use.at[slot].set(step),
        row_sum=fts.row_sum.at[slot // spr].add(b - b0),
    )


def should_insert(fts: FTS, seg: jax.Array, threshold) -> Tuple[jax.Array, FTS]:
    """Insertion policy (paper §9.4 sensitivity).  threshold=1 ==
    insert-any-miss (the §6 default).

    Higher thresholds track consecutive misses per segment in a small
    direct-mapped counter table (the 'additional metadata' §9.4 mentions).

    ``threshold`` may be a *traced* int32 (sweep engine, DESIGN.md §3), so
    the decision is branchless: the tracker is always advanced and the
    returned verdict is ``threshold <= 1 or count >= threshold``.  Callers
    must invoke this on actual (cacheable) misses only — the tracker counts
    consecutive misses, and advancing it on hits inflates the counts.
    """
    n = fts.miss_tags.shape[0]
    idx = jnp.remainder(seg, n)
    same = fts.miss_tags[idx] == seg
    cnt = jnp.where(same, fts.miss_cnt[idx] + 1, 1)
    fts = fts._replace(miss_tags=fts.miss_tags.at[idx].set(seg),
                       miss_cnt=fts.miss_cnt.at[idx].set(cnt))
    thr = jnp.asarray(threshold, jnp.int32)
    return (thr <= 1) | (cnt >= thr), fts


def pick_victim_row(row_sum: jax.Array, evict_row: jax.Array,
                    evict_mask: jax.Array, segs_per_row, n_slots,
                    new_row=None):
    """RowBenefit, O(max_rows) half: (victim row, refreshed bitvector).

    When the persistent bitvector is exhausted a new victim row is chosen —
    the live row with the lowest carried ``row_sum`` — and the bitvector is
    refreshed to the full row.  Row r is live iff it contains at least one
    active slot, i.e. ``r * segs_per_row < n_slots`` (analytic — it depends
    on the geometry only, never on the valid bits).  ``new_row`` lets a
    caller supply the argmin candidate (the Pallas ``fts_lookup`` kernel
    computes it fused with the tag compare)."""
    spr = jnp.asarray(segs_per_row, jnp.int32)
    n = jnp.asarray(n_slots, jnp.int32)
    max_segs = evict_mask.shape[0]
    need_new = (evict_row < 0) | ~jnp.any(evict_mask)
    if new_row is None:
        rows = jnp.arange(row_sum.shape[0], dtype=jnp.int32)
        new_row = masked_argmin(row_sum, rows * spr < n)
    row = jnp.where(need_new, new_row, evict_row)
    fresh = jnp.arange(max_segs, dtype=jnp.int32) < spr
    mask = jnp.where(need_new, fresh, evict_mask)
    return row, mask


def pick_victim_in_row(benefit_row: jax.Array, mask: jax.Array,
                       row: jax.Array, segs_per_row):
    """RowBenefit, O(max_segs_per_row) half: lowest-benefit marked slot of
    the victim row.  ``benefit_row`` is the (max_segs_per_row,) gather of
    ``benefit`` at ``row * segs_per_row + j``; returns (slot, mask with the
    chosen bit cleared)."""
    spr = jnp.asarray(segs_per_row, jnp.int32)
    j = jnp.arange(mask.shape[0], dtype=jnp.int32)
    jj = masked_argmin(benefit_row, (j < spr) & mask)
    return row * spr + jj, mask & (j != jj)


def gather_row(benefit: jax.Array, row: jax.Array, max_segs: int,
               segs_per_row) -> jax.Array:
    """(max_segs,) gather of one cache row's benefit counters."""
    spr = jnp.asarray(segs_per_row, jnp.int32)
    idx = row * spr + jnp.arange(max_segs, dtype=jnp.int32)
    return benefit[jnp.clip(idx, 0, benefit.shape[-1] - 1)]


def _pick_victim_row_benefit(fts: FTS, segs_per_row, n_slots):
    """Paper §6 RowBenefit: row-granularity eviction with a bitvector.

    Both reductions run over the carried aggregates (DESIGN.md §9): the
    victim row is an argmin over ``row_sum (max_rows,)`` and the in-row
    slot an argmin over the single gathered row — never a segment-sum over
    ``max_slots``.  With n_slots == max_slots this reproduces the unpadded
    reshape(n_rows, segs_per_row) reduction bit for bit.

    Precondition: ``n_slots`` must be a multiple of ``segs_per_row`` (cache
    rows are whole rows; ``MechConfig`` guarantees it via
    ``n_slots = cache_rows * segs_per_row``).  A ragged last row would let
    the persistent bitvector point at padding and silently evict slot 0 —
    the unpadded reshape would have raised on such a geometry instead.
    """
    row, mask = pick_victim_row(fts.row_sum, fts.evict_row, fts.evict_mask,
                                segs_per_row, n_slots)
    benefit_row = gather_row(fts.benefit, row, fts.evict_mask.shape[0],
                             segs_per_row)
    slot, mask = pick_victim_in_row(benefit_row, mask, row, segs_per_row)
    return slot, fts._replace(evict_row=row, evict_mask=mask)


def _pick_victim_row_benefit_recompute(fts: FTS, segs_per_row, n_slots):
    """Pre-aggregate RowBenefit reference: re-derive the per-row sums from
    ``benefit`` with two segment-sum scatters over ``max_slots`` every call
    (the seed implementation).  Kept as the recompute oracle the carried
    ``row_sum`` is pinned against — the dense scan body and the
    ``tests/test_hotloop.py`` property tests run THIS path and must match
    the O(1)-update path bit for bit."""
    max_slots = fts.benefit.shape[0]
    spr = jnp.asarray(segs_per_row, jnp.int32)
    idx = jnp.arange(max_slots, dtype=jnp.int32)
    active = _active(fts, n_slots)
    row_of = idx // spr
    seg_of = idx - row_of * spr
    need_new = (fts.evict_row < 0) | ~jnp.any(fts.evict_mask)
    # masked row-sum / row-liveness of the (max_rows, max_segs) view;
    # max_rows == max_slots covers segs_per_row == 1
    row_sum = jnp.zeros((max_slots,), jnp.int32).at[row_of].add(
        jnp.where(active, fts.benefit, 0))
    row_live = jnp.zeros((max_slots,), bool).at[row_of].max(active)
    new_row = masked_argmin(row_sum, row_live)
    row = jnp.where(need_new, new_row, fts.evict_row)
    max_segs = fts.evict_mask.shape[0]
    fresh = jnp.arange(max_segs, dtype=jnp.int32) < spr
    mask = jnp.where(need_new, fresh, fts.evict_mask)
    in_row = active & (row_of == row) & mask[seg_of]
    slot = masked_argmin(fts.benefit, in_row)
    mask = mask.at[jnp.remainder(slot, spr)].set(False)
    return slot, fts._replace(evict_row=row, evict_mask=mask)


def _pick_victim(fts: FTS, policy: str, segs_per_row, n_slots,
                 step: jax.Array, recompute: bool = False):
    if policy == "row_benefit":
        if recompute:
            return _pick_victim_row_benefit_recompute(fts, segs_per_row,
                                                      n_slots)
        return _pick_victim_row_benefit(fts, segs_per_row, n_slots)
    active = _active(fts, n_slots)
    if policy == "segment_benefit":
        return masked_argmin(fts.benefit, active), fts
    if policy == "lru":
        return masked_argmin(fts.last_use, active), fts
    if policy == "random":
        return random_victim(step, n_slots), fts
    raise ValueError(f"unknown replacement policy {policy!r}")


def random_victim(step: jax.Array, n_slots) -> jax.Array:
    """O(1) LCG-hashed victim slot for the Random policy."""
    h = (step * jnp.int32(1103515245) + 12345) & jnp.int32(0x7FFFFFFF)
    return jnp.remainder(h, jnp.asarray(n_slots, jnp.int32)).astype(jnp.int32)


class InsertResult(NamedTuple):
    fts: FTS
    slot: jax.Array          # where the new segment landed
    evicted_valid: jax.Array  # a valid entry was displaced
    evicted_dirty: jax.Array  # ... and it was dirty (-> writeback RELOCs)
    evicted_tag: jax.Array    # its segment id (for writeback addressing)


def insert(fts: FTS, seg: jax.Array, is_write: jax.Array, step: jax.Array,
           *, policy: str, segs_per_row, n_slots=None,
           benefit_init: int = 1, recompute: bool = False) -> InsertResult:
    """Insert `seg` (on a miss): free slot if any, else policy victim.

    ``segs_per_row`` and ``n_slots`` may be Python ints or traced int32
    scalars; ``n_slots=None`` means "all slots active" (unpadded store).
    ``n_slots`` must be a multiple of ``segs_per_row`` under the
    row_benefit policy (see ``_pick_victim_row_benefit``).  The free path
    is O(1): ``has_free`` is the carried-count compare and the landing slot
    is the free-stack top; victim selection reduces the carried aggregates,
    preserving the padding invariant (padded slots never turn valid).

    ``recompute=True`` re-derives every decision from the base arrays
    (full free-slot argmin, segment-summed row benefits — the seed's
    hot-loop cost) instead of reading the carried aggregates; the
    aggregates are still *maintained* (the free stack is reordered so the
    argmin-chosen slot is the one popped).  It is the oracle the O(1)
    path is pinned against (DESIGN.md §9) and the dense scan
    body's cost model.  Decision-equal to the O(1) path while the
    store's free set is a suffix of the slot range (always true without
    ``invalidate``; after out-of-order invalidations the recompute path
    refills lowest-index-first while the stack refills
    most-recently-freed-first)."""
    max_slots = fts.tags.shape[0]
    if n_slots is None:
        n_slots = max_slots
    n = jnp.asarray(n_slots, jnp.int32)
    spr = jnp.asarray(segs_per_row, jnp.int32)
    free_list = fts.free_list
    top = jnp.minimum(fts.n_valid, max_slots - 1)
    if recompute:
        active = _active(fts, n_slots)
        has_free = jnp.any(active & ~fts.valid)
        # padding reads as "occupied" so argmin lands on an active free slot
        free_slot = jnp.argmin(
            jnp.where(active, fts.valid, True)).astype(jnp.int32)
        # keep the carried stack consistent with the argmin choice: swap the
        # chosen slot to the stack top before the pop below.  An identity
        # whenever the free set is a suffix (i.e. the store never saw an
        # out-of-order invalidate), so the dense scan stays bitwise-equal
        # to the O(1) path; with holes it prevents the pop from dropping a
        # different slot than the one being filled.
        idx = jnp.arange(max_slots, dtype=jnp.int32)
        pos = masked_argmin(idx, (free_list == free_slot) & (idx >= top))
        old_top = free_list[top]
        free_list = free_list.at[top].set(
            jnp.where(has_free, free_slot, old_top))
        free_list = free_list.at[pos].set(
            jnp.where(has_free, old_top, free_list[pos]))
    else:
        has_free = fts.n_valid < n
        free_slot = free_list[top]
    victim_slot, fts_v = _pick_victim(fts, policy, spr, n_slots, step,
                                      recompute=recompute)
    # when a free slot exists, do not consume the eviction bitvector — the
    # victim pickers only ever touch evict_row / evict_mask
    evict_row = jnp.where(has_free, fts.evict_row, fts_v.evict_row)
    evict_mask = jnp.where(has_free, fts.evict_mask, fts_v.evict_mask)
    slot = jnp.where(has_free, free_slot, victim_slot)
    ev_valid = fts.valid[slot] & ~has_free
    ev_dirty = ev_valid & fts.dirty[slot]
    ev_tag = fts.tags[slot]
    b0 = fts.benefit[slot]
    binit = jnp.asarray(benefit_init, jnp.int32)
    fts = fts._replace(
        tags=fts.tags.at[slot].set(seg),
        valid=fts.valid.at[slot].set(True),
        dirty=fts.dirty.at[slot].set(is_write),
        benefit=fts.benefit.at[slot].set(binit),
        last_use=fts.last_use.at[slot].set(step),
        evict_row=evict_row,
        evict_mask=evict_mask,
        row_sum=fts.row_sum.at[slot // spr].add(binit - b0),
        free_list=free_list,
        n_valid=fts.n_valid + has_free.astype(jnp.int32),
    )
    return InsertResult(fts, slot, ev_valid, ev_dirty, ev_tag)


class SlotWrite(NamedTuple):
    """The surgical per-(bank, slot) FTS write-back of one simulator step
    (DESIGN.md §9/§10): exactly one slot ``w`` per bank is written, and
    every value equals the old one when the step changed nothing, so
    applying a write is always safe (no-op requests store back old state).

    Shapes are scalar for the serial fused scan and ``(W,)``-batched for
    the bank-wavefront scan (``core/sched/wavefront.py``): the SAME
    ``apply_write`` serves both because ``.at[bank, w]`` indexing and
    ``onehot_write`` accept a scalar bank or a vector of *distinct* banks
    alike — wave formation guarantees distinctness, which is what makes
    the vectorized writes deterministic.
    """
    w: jax.Array          # slot written (hit slot or insertion landing slot)
    tag: jax.Array
    valid: jax.Array
    dirty: jax.Array
    benefit: jax.Array
    last_use: jax.Array
    row_delta: jax.Array  # row_sum increment at w // segs_per_row
    evict_row: jax.Array
    evict_mask: jax.Array  # (max_segs_per_row,) bool
    tr_idx: jax.Array      # miss-tracker index touched
    miss_tag: jax.Array
    miss_cnt: jax.Array
    n_valid_inc: jax.Array


def apply_write(fts: FTS, bank: jax.Array, segs_per_row,
                wr: SlotWrite) -> FTS:
    """Apply one step's ``SlotWrite`` to a *banked* store (leaves with a
    leading ``(n_banks,)`` axis).  ``bank`` may be a scalar (serial scan) or
    a vector of distinct banks with ``(W,)``-batched write values (the
    wavefront scan) — integer scatters to distinct rows are deterministic,
    and ``row_sum`` uses ``.add`` so duplicate *rows within a bank* (never
    across banks) still cannot occur."""
    spr = jnp.asarray(segs_per_row, jnp.int32)
    return fts._replace(
        tags=fts.tags.at[bank, wr.w].set(wr.tag),
        valid=fts.valid.at[bank, wr.w].set(wr.valid),
        dirty=fts.dirty.at[bank, wr.w].set(wr.dirty),
        benefit=fts.benefit.at[bank, wr.w].set(wr.benefit),
        last_use=fts.last_use.at[bank, wr.w].set(wr.last_use),
        row_sum=fts.row_sum.at[bank, wr.w // spr].add(wr.row_delta),
        evict_row=onehot_write(fts.evict_row, bank, wr.evict_row),
        evict_mask=onehot_write(fts.evict_mask, bank, wr.evict_mask),
        miss_tags=fts.miss_tags.at[bank, wr.tr_idx].set(wr.miss_tag),
        miss_cnt=fts.miss_cnt.at[bank, wr.tr_idx].set(wr.miss_cnt),
        n_valid=onehot_write(fts.n_valid, bank,
                             onehot_read(fts.n_valid, bank) + wr.n_valid_inc),
    )


def invalidate(fts: FTS, slot: jax.Array, segs_per_row) -> FTS:
    """Drop an entry: clear its bits, return its benefit contribution and
    push the slot on the free stack — all O(1).  A no-op (bitwise) when the
    slot is already invalid.  Also resets the tag to -1, keeping the
    "invalid => tag == -1" invariant the fused tag compare relies on."""
    spr = jnp.asarray(segs_per_row, jnp.int32)
    was = fts.valid[slot]
    pos = jnp.maximum(fts.n_valid - 1, 0)
    return fts._replace(
        tags=fts.tags.at[slot].set(jnp.where(was, -1, fts.tags[slot])),
        valid=fts.valid.at[slot].set(False),
        dirty=fts.dirty.at[slot].set(False),
        benefit=fts.benefit.at[slot].set(0),
        row_sum=fts.row_sum.at[slot // spr].add(
            -jnp.where(was, fts.benefit[slot], 0)),
        free_list=fts.free_list.at[pos].set(
            jnp.where(was, slot, fts.free_list[pos])),
        n_valid=fts.n_valid - was.astype(jnp.int32),
    )
