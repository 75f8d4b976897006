"""Decoder-stack orchestration: heterogeneous layer layouts, scan-over-layers
with activation rematerialization, cache threading for decode.

Layer layouts are expressed as *scan groups* of identical block structure:
  dense/mixtral/rwkv : [(L, [block of 1 layer])]           -> one scan
  deepseek-v2-lite   : [(1, [dense-ffn layer]), (26, [moe])] -> head + scan
  jamba              : [(4, [8-layer period block])]        -> scan of blocks
This keeps the lowered HLO layer-count-independent (one scan body per group),
which is what makes 95-layer dry-runs compile quickly and what remat expects.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models import attention, mamba, moe, rwkv6
from repro.models.layers import rms_norm, rms_norm_spec, swiglu, swiglu_spec
from repro.models.param import Spec, stack_layers
from repro.models.plan import Plan


@dataclasses.dataclass(frozen=True)
class LayerDef:
    mixer: str           # attn | mla | mamba | rwkv
    ffn: Optional[str]   # dense | moe | None (rwkv: built-in channel mix)


def layer_def(cfg: ModelConfig, i: int) -> LayerDef:
    if cfg.rwkv:
        return LayerDef("rwkv", None)
    if cfg.attn_layer_period:
        mixer = "attn" if i % cfg.attn_layer_period == cfg.attn_layer_offset \
            else "mamba"
    else:
        mixer = "mla" if cfg.mla is not None else "attn"
    ffn = "dense"
    if cfg.moe is not None and i >= cfg.moe.first_dense and \
            i % cfg.moe.layer_period == cfg.moe.layer_offset:
        ffn = "moe"
    return LayerDef(mixer, ffn)


def group_layout(cfg: ModelConfig) -> List[Tuple[int, List[LayerDef]]]:
    """[(repeat_count, block_defs)] — consecutive identical blocks merge."""
    defs = [layer_def(cfg, i) for i in range(cfg.n_layers)]
    if cfg.attn_layer_period:
        period = cfg.attn_layer_period * (
            cfg.moe.layer_period if cfg.moe else 1)
        period = cfg.attn_layer_period if cfg.moe is None else \
            _lcm(cfg.attn_layer_period, cfg.moe.layer_period)
        assert cfg.n_layers % period == 0
        block = defs[:period]
        return [(cfg.n_layers // period, block)]
    groups: List[Tuple[int, List[LayerDef]]] = []
    for d in defs:
        if groups and groups[-1][1] == [d]:
            groups[-1] = (groups[-1][0] + 1, [d])
        else:
            groups.append((1, [d]))
    return groups


def _lcm(a, b):
    import math
    return a * b // math.gcd(a, b)


# --------------------------------------------------------------------------
# Specs
# --------------------------------------------------------------------------

def _sublayer_spec(cfg: ModelConfig, plan: Plan, d: LayerDef):
    s: dict = {}
    if d.mixer == "rwkv":
        s["rwkv"] = rwkv6.rwkv_spec(cfg, plan)
        return s
    s["ln_mix"] = rms_norm_spec(cfg.d_model)
    if d.mixer == "attn":
        s["attn"] = attention.gqa_spec(cfg, plan)
    elif d.mixer == "mla":
        s["attn"] = attention.mla_spec(cfg, plan)
    elif d.mixer == "mamba":
        s["mamba"] = mamba.mamba_spec(cfg, plan)
    if d.ffn is not None:
        s["ln_ffn"] = rms_norm_spec(cfg.d_model)
        if d.ffn == "dense":
            s["ffn"] = swiglu_spec(cfg.d_model, plan.padded_ffn(cfg.d_ff))
        else:
            s["ffn"] = moe.moe_spec(cfg, plan)
    return s


def stack_spec(cfg: ModelConfig, plan: Plan):
    groups = []
    for count, block in group_layout(cfg):
        bspec = [_sublayer_spec(cfg, plan, d) for d in block]
        groups.append(stack_layers(bspec, count) if count > 1 else bspec)
    return {"groups": groups, "ln_f": rms_norm_spec(cfg.d_model)}


# --------------------------------------------------------------------------
# Caches / recurrent state
# --------------------------------------------------------------------------

def _sublayer_cache(cfg: ModelConfig, plan: Plan, d: LayerDef, batch: int,
                    s_max: int, quant: bool):
    if d.mixer == "rwkv":
        return rwkv6.init_state(cfg, batch)
    if d.mixer == "mamba":
        return mamba.init_state(cfg, batch)
    if d.mixer == "mla":
        m = cfg.mla
        # latent cache: one "head" carrying c_kv, one carrying k_rope
        rank = max(m.kv_lora_rank, m.qk_rope_head_dim)
        return attention.init_kv_cache(batch, s_max, 1, rank, quant=False) \
            ._replace(k=jnp.zeros((batch, s_max, 1, m.kv_lora_rank), jnp.bfloat16),
                      v=jnp.zeros((batch, s_max, 1, m.qk_rope_head_dim), jnp.bfloat16))
    hkv = plan.padded_kv_heads(cfg.n_kv_heads)
    s_alloc = min(s_max, cfg.sliding_window) if cfg.sliding_window else s_max
    return attention.init_kv_cache(batch, s_alloc, hkv, cfg.hd, quant)


def init_caches(cfg: ModelConfig, plan: Plan, batch: int, s_max: int):
    quant = plan.kv_quant
    out = []
    for count, block in group_layout(cfg):
        bc = [_sublayer_cache(cfg, plan, d, batch, s_max, quant)
              for d in block]
        if count > 1:
            bc = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (count,) + a.shape).copy(), bc)
        out.append(bc)
    return out


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _run_block(bparams, bcaches, x, cfg: ModelConfig, plan: Plan, defs,
               angles, decode: bool, hmask):
    """One (possibly multi-sublayer) block.  Returns (x, new_caches, aux)."""
    if plan.act_pspec is not None and not decode:
        # Megatron-SP: the residual stream (and thus every remat checkpoint)
        # lives sequence-sharded; GSPMD inserts the all-gather before
        # attention/mlp and the reduce-scatter after
        x = jax.lax.with_sharding_constraint(x, plan.act_pspec)
    new_caches = []
    aux = jnp.zeros((), jnp.float32)
    for p, c, d in zip(bparams, bcaches, defs):
        if d.mixer == "rwkv":
            x, st = rwkv6.rwkv_block(p["rwkv"], x, cfg, plan, state=c)
            new_caches.append(st)
            continue
        h = rms_norm(x, p["ln_mix"], cfg.norm_eps)
        if d.mixer == "attn":
            y, nc = attention.gqa_forward(
                p["attn"], h, cfg, plan, angles=angles, cache=c,
                decode=decode, hmask=hmask)
        elif d.mixer == "mla":
            y, nc = attention.mla_forward(
                p["attn"], h, cfg, plan, angles=angles, cache=c,
                decode=decode, hmask=hmask)
        else:  # mamba
            y, nc = mamba.mamba_forward(p["mamba"], h, cfg, plan,
                                        state=c, decode=decode)
        x = x + y
        if d.ffn is not None:
            h = rms_norm(x, p["ln_ffn"], cfg.norm_eps)
            if d.ffn == "dense":
                x = x + swiglu(p["ffn"], h)
            else:
                y, a = moe.moe_forward(p["ffn"], h, cfg, plan)
                x = x + y
                aux = aux + a["load_balance_loss"]
        new_caches.append(nc)
    if plan.act_pspec is not None and not decode:
        # constrain the block OUTPUT as well: the scan carry (= the remat
        # residual that lives for the whole backward) is stored seq-sharded
        x = jax.lax.with_sharding_constraint(x, plan.act_pspec)
    return x, new_caches, aux


def stack_forward(params, x: jax.Array, cfg: ModelConfig, plan: Plan, *,
                  angles=None, caches=None, decode: bool = False):
    """x (B,S,D) -> (normed (B,S,D), new_caches, aux)."""
    hmask = attention.head_mask(cfg, plan)
    layout = group_layout(cfg)
    if caches is None:
        caches = [[None] * len(block) for _, block in layout]
        track_cache = False
    else:
        track_cache = True
    new_caches = []
    aux_total = jnp.zeros((), jnp.float32)

    for gi, (count, block) in enumerate(layout):
        gparams = params["groups"][gi]
        gcaches = caches[gi]

        def block_fn(xc, pc):
            xx, auxc = xc
            bp, bc = pc
            xx, nc, aux = _run_block(bp, bc, xx, cfg, plan, block,
                                     angles, decode, hmask)
            return (xx, auxc + aux), nc

        fn = block_fn
        if plan.remat == "full" and not decode:
            fn = jax.checkpoint(block_fn,
                                policy=jax.checkpoint_policies.nothing_saveable)
        if count == 1:
            (x, aux_total), nc = fn((x, aux_total), (gparams, gcaches))
            new_caches.append(nc)
        elif plan.scan_layers:
            if track_cache:
                (x, aux_total), ncs = jax.lax.scan(
                    fn, (x, aux_total), (gparams, gcaches))
            else:
                (x, aux_total), ncs = jax.lax.scan(
                    lambda carry, bp: (
                        fn(carry, (bp, [None] * len(block)))[0], None),
                    (x, aux_total), gparams)
            new_caches.append(ncs)
        else:
            # unrolled: XLA's cost analysis counts a while-loop body
            # once, so only an unrolled stack gives exact per-layer cost
            ncs_list = []
            for i in range(count):
                bp = jax.tree.map(lambda a: a[i], gparams)
                bc = jax.tree.map(lambda a: a[i], gcaches) if track_cache \
                    else [None] * len(block)
                (x, aux_total), nc = fn((x, aux_total), (bp, bc))
                ncs_list.append(nc)
            ncs = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs_list) \
                if track_cache else None
            new_caches.append(ncs)

    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, (new_caches if track_cache else None), aux_total
