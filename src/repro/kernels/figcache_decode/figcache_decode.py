"""Pallas TPU kernel: FIGCache-KV decode attention.

One query token attends the (hot fast-pool segments ∪ recent window) buffer
produced by the FIGCache-KV selection step — the TPU analogue of serving a
request from the fast subarray region.  The gathered KV buffer is small and
*contiguous* (that is the point of relocation: scattered hot segments become
streamable), so it tiles cleanly HBM->VMEM.

grid = (BH, L_blocks), kv dimension sequential with VMEM scratch carrying the
online-softmax state; the per-slot validity mask rides in as a block input.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref, *,
            n_l: int):
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)          # (1, D)
    k = k_ref[...].astype(jnp.float32)          # (bl, D)
    v = v_ref[...].astype(jnp.float32)
    ok = valid_ref[...] != 0                    # (1, bl)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (q.shape[-1] ** -0.5)
    s = jnp.where(ok, s, NEG)                   # (1, bl)

    m_prev = m_ref[...]                         # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ v
    m_ref[...] = m_new

    @pl.when(li == n_l - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                      ).astype(o_ref.dtype)


def figcache_decode(q, k, v, valid, *, heads_per_seq: int,
                    block_l: int = 256, interpret: bool = False):
    """q (BH, D); k/v (BH, L, D); valid (B, L); BH = B * heads_per_seq."""
    BH, D = q.shape
    B, L = valid.shape
    block_l = min(block_l, L)
    assert L % block_l == 0
    n_l = L // block_l
    H = heads_per_seq
    kern = functools.partial(_kernel, n_l=n_l)
    # q/out rows and mask rows get a unit sublane axis so each (1, D) or
    # (1, block_l) block spans the last two dims, as Mosaic requires of
    # blocks that are not (8, 128)-tiled; the row axis itself is squeezed
    row = pl.BlockSpec((None, 1, D), lambda b, j: (b, 0, 0))
    kv = pl.BlockSpec((None, block_l, D), lambda b, j: (b, j, 0))
    out = pl.pallas_call(
        kern,
        grid=(BH, n_l),
        in_specs=[
            row, kv, kv,
            pl.BlockSpec((None, 1, block_l), lambda b, j: (b // H, 0, j)),
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((BH, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q.reshape(BH, 1, D), k, v, valid.astype(jnp.int32).reshape(B, 1, L))
    return out.reshape(BH, D)
