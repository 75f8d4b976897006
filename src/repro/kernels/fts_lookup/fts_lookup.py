"""Pallas TPU kernel for the FTS hot-loop lookup: fused tag compare +
victim argmin over one bank's tag-store row.

Per simulator scan step the tag store must answer two questions about ONE
bank: "is segment `seg` cached (and where)?" — a compare over the
(max_slots,) tag row — and "which victim would the replacement policy pick?"
— a masked argmin over a per-slot (or per-row, for RowBenefit) score array.
In pure JAX these are separate HBM sweeps over (n_banks, max_slots) arrays;
here both ride ONE VMEM pass: scalar prefetch (SMEM) delivers the bank
index so the DMA engine fetches exactly the selected (1, max_slots) rows of
``tags`` and ``score``, and the kernel reduces them in a single visit —
the harness-side analogue of FIGARO reading a row once through the global
row buffer instead of once per question.

Precondition (guaranteed inside ``dram.make_step`` scans, see
``core/fts.py:invalidate``): invalid slots keep ``tags == -1`` and looked-up
segment ids are >= 0, so the tag compare needs no separate valid bitmap.

Outputs land in SMEM as one (3,) int32 vector: [hit, hit_slot, victim_cand]
(hit_slot = first matching slot, max_slots when no match; victim_cand =
first index of the masked score minimum, 0 when the mask is empty — the
same tie-breaking as ``jnp.argmin`` over a BIG-masked array).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1 << 30   # Python literal: a jnp scalar would be captured as a const


def _kernel(ids_ref, tags_ref, score_ref, out_ref):
    seg = ids_ref[1]
    limit = ids_ref[2]
    s = tags_ref.shape[1]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    m = tags_ref[...] == seg
    hit = jnp.any(m)
    hit_slot = jnp.min(jnp.where(m, idx, s))
    masked = jnp.where(idx < limit, score_ref[...], BIG)
    mn = jnp.min(masked)
    cand = jnp.min(jnp.where(masked == mn, idx, s - 1))
    out_ref[0] = hit.astype(jnp.int32)
    out_ref[1] = hit_slot.astype(jnp.int32)
    out_ref[2] = cand.astype(jnp.int32)


def fts_lookup(tags: jax.Array, score: jax.Array, bank: jax.Array,
               seg: jax.Array, limit: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """tags/score (n_banks, max_slots) int32 -> (3,) int32
    [hit, hit_slot, victim_cand] for the selected bank.

    ``limit`` masks the victim argmin to the active prefix of ``score``
    (``n_slots`` active slots, or the live-row count when ``score`` is the
    RowBenefit per-row sum); ``limit <= 0`` yields candidate 0.
    """
    n_banks, n_slots = tags.shape
    ids = jnp.stack([bank, seg, limit]).astype(jnp.int32)
    # Mosaic tiles the last two block dims by (8, 128) unless they span the
    # whole array, so a (1, n_slots) row block of the (n_banks, n_slots)
    # table is refused.  A unit sublane axis makes the row block span its
    # last two dims; the bank axis is squeezed and picked by the prefetch.
    row = pl.BlockSpec((None, 1, n_slots), lambda i, ids: (ids[0], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[row, row],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((3,), jnp.int32),
        interpret=interpret,
    )(ids, tags.reshape(n_banks, 1, n_slots),
      score.reshape(n_banks, 1, n_slots))
