"""Pallas TPU flash-attention (prefill compute hot-spot).

Grid = (batch*heads, q_blocks, kv_blocks) with the kv dimension 'arbitrary'
(sequential): running max / denominator / accumulator live in VMEM scratch
across kv steps.  Block shapes are MXU-aligned (multiples of 128 on the
lane dim; q/kv block sizes default 256/512 to fit bf16 tiles in ~2 MB VMEM:
q(256x128) + k(512x128) + v(512x128) + acc(256x128 f32) ≈ 0.7 MB).
Causal + sliding-window masking; fully-masked kv blocks are skipped.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_kv: int, causal: bool, window: int,
            n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_kv

    # skip kv blocks that are entirely masked out
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + block_q - 1
    if window:
        run &= k_start + block_kv - 1 > q_start - window

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                      # (bq, d)
        k = k_ref[0].astype(jnp.float32)                      # (bkv, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (q.shape[-1] ** -0.5)
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), bool)
        if causal:
            mask &= kp <= qp
        if window:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 256, block_kv: int = 512,
                    interpret: bool = False):
    """q/k/v (BH, S, D) -> (BH, S, D)."""
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    n_q = S // block_q
    n_kv = S // block_kv
    grid = (BH, n_q, n_kv)
    kern = functools.partial(_kernel, block_q=block_q, block_kv=block_kv,
                             causal=causal, window=window, n_kv=n_kv)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # running max
            pltpu.VMEM((block_q,), jnp.float32),      # running denom
            pltpu.VMEM((block_q, D), jnp.float32),    # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
