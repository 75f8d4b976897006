"""Pallas TPU kernel for FIGARO RELOC: fine-grained segment relocation.

The DRAM mechanism (paper §4): one column moves between two subarrays' row
buffers through the shared global row buffer, with unaligned src/dst
addressing and distance-independent latency.  TPU adaptation: one *segment*
(a KV/embedding block, tens of KB) moves HBM->HBM between the slow pool and
the fast pool through VMEM (the GRB analogue), with src/dst indices delivered
via scalar prefetch (SMEM) so the DMA engine can compute block addresses
before the body runs — the analogue of RELOC carrying two column addresses in
one command.

grid = (n_moves,); every step copies one segment.  In-place aliasing
(input_output_aliases) makes this a true relocation, not a copy-and-rebuild.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(ids_ref, pool_ref, fast_in_ref, fast_out_ref):
    i = pl.program_id(0)
    ok = ids_ref[i] >= 0            # masked lane: leave destination intact

    @pl.when(ok)
    def _move():
        fast_out_ref[...] = pool_ref[...]

    @pl.when(jnp.logical_not(ok))
    def _keep():
        fast_out_ref[...] = fast_in_ref[...]


def reloc(pool: jax.Array, fast: jax.Array, src_segs: jax.Array,
          dst_slots: jax.Array, *, interpret: bool = False) -> jax.Array:
    """fast[dst_slots[i]] <- pool[src_segs[i]] for i in range(n_moves).

    pool (n_segs, E), fast (n_slots, E), ids (n_moves,) int32 (src<0 = no-op).
    Returns the updated fast pool (aliased with the input).
    """
    n_moves = src_segs.shape[0]
    n_slots, E = fast.shape
    # scalar-prefetch carries both address streams (RELOC's two column addrs)
    ids = jnp.concatenate([src_segs, dst_slots]).astype(jnp.int32)
    # one segment per block: a unit sublane axis lets the (1, E) block span
    # the last two dims, which Mosaic requires of blocks not (8, 128)-tiled
    src = pl.BlockSpec((None, 1, E),
                       lambda i, ids: (jnp.maximum(ids[i], 0), 0, 0))
    dst = pl.BlockSpec((None, 1, E), lambda i, ids: (ids[n_moves + i], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_moves,),
        in_specs=[src, dst],
        out_specs=dst,
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slots, 1, E), fast.dtype),
        input_output_aliases={2: 0},   # fast buffer updated in place
        interpret=interpret,
    )(ids, pool.reshape(pool.shape[0], 1, E), fast.reshape(n_slots, 1, E))
    return out.reshape(n_slots, E)
