"""Step builders: plan selection, input specs, jitted train/prefill/decode
functions with full sharding contracts.  Shared by ``launch/train.py`` and
the tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import configs
from repro.configs import ModelConfig, ShapeConfig
from repro.models import build_model, Plan
from repro.models.plan import Plan as PlanCls
from repro.optim import AdamWState, adamw_init, adamw_update, cosine_schedule
from repro.optim.compress import ef_init, ef_int8_compress
from repro.launch import sharding as shd
from repro.launch.mesh import dp_axes, mesh_axes


# --------------------------------------------------------------------------
# Plan selection per (arch x shape x mesh)
# --------------------------------------------------------------------------

def make_plan(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
              overrides: Optional[dict] = None) -> Plan:
    ax = mesh_axes(mesh)
    tp = ax.get("model", 1)
    dp = int(np.prod([ax[a] for a in dp_axes(mesh)]))
    pods = ax.get("pod", 1)
    big = cfg.n_params() > 30e9
    kw: Dict[str, Any] = dict(
        tp=tp, dp=dp, pods=pods,
        kv_quant=(shape.kind == "decode" and big),
        weight_quant=False,
        remat="full" if shape.kind == "train" else "none",
        fsdp=(shape.kind == "train" and big),
        microbatches=4 if (shape.kind == "train" and big) else 1,
        seq_shard_decode=(shape.name == "long_500k"),
        moe_capacity=1.25 if shape.kind == "train" else 0.0,
    )
    dpa = ("pod", "data") if pods > 1 else "data"
    if shape.kind == "train" and tp > 1:
        kw["act_pspec"] = P(dpa, "model", None)
    if overrides:
        kw.update(overrides)
    plan = PlanCls(**kw)
    if tp > 1:
        object.__setattr__(plan, "hint_dp", dpa)   # enable interior hints
    return plan


# --------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — no allocation)
# --------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Batch stand-ins for one step of the given kind."""
    B, S = shape.global_batch, shape.seq_len
    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
    sds = jax.ShapeDtypeStruct
    if shape.kind == "decode":
        out = {"tokens": sds((B, 1), i32)}
        return out
    if cfg.family == "vlm":
        nv = cfg.n_vision_tokens
        out = {"tokens": sds((B, S - nv), i32),
               "vision_embeds": sds((B, nv, cfg.d_model), bf16),
               "positions3": sds((3, B, S), i32)}
        if shape.kind == "train":
            out["targets"] = sds((B, S - nv), i32)
        return out
    if cfg.is_encdec:
        out = {"audio_embeds": sds((B, cfg.n_audio_frames, cfg.d_model), bf16),
               "tokens": sds((B, S), i32)}
        if shape.kind == "train":
            out["targets"] = sds((B, S), i32)
        return out
    out = {"tokens": sds((B, S), i32)}
    if shape.kind == "train":
        out["targets"] = sds((B, S), i32)
    return out


# --------------------------------------------------------------------------
# Train step
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Hyper:
    peak_lr: float = 3e-4
    warmup: int = 100
    total_steps: int = 10000
    grad_compress: bool = False   # int8 error-feedback on the DP reduction


class TrainState:
    """(params bf16, AdamWState, optional EF error state).  Plain pytree."""
    pass


def init_train_state(model, rng, hyper: Hyper):
    params = model.init_params(rng)
    opt = adamw_init(params)
    err = ef_init(params) if hyper.grad_compress else None
    return {"params": params, "opt": opt, "err": err}


def abstract_train_state(model, hyper: Hyper):
    params = model.abstract_params()
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)
    opt = AdamWState(m=jax.tree.map(f32, params),
                     v=jax.tree.map(f32, params),
                     master=jax.tree.map(f32, params),
                     count=jax.ShapeDtypeStruct((), jnp.int32))
    err = jax.tree.map(f32, params) if hyper.grad_compress else None
    return {"params": params, "opt": opt, "err": err}


def train_state_shardings(model, mesh: Mesh, hyper: Hyper):
    axes = model.logical_axes()
    p_sh = shd.param_shardings(axes, mesh, fsdp=model.plan.fsdp,
                               abstract_tree=model.abstract_params())
    z_sh = shd.zero1_shardings(axes, model.abstract_params(), mesh)
    opt = AdamWState(m=z_sh, v=z_sh, master=z_sh,
                     count=shd.replicated(mesh))
    err = z_sh if hyper.grad_compress else None
    return {"params": p_sh, "opt": opt, "err": err}


def make_train_step(model, mesh: Mesh, hyper: Hyper):
    """Returns (jitted step, state_shardings, batch_shardings)."""
    plan = model.plan
    state_sh = train_state_shardings(model, mesh, hyper)

    def zero_like_grads(params):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    def train_step(state, batch):
        params = state["params"]
        mb = plan.microbatches

        def loss_fn(p, b):
            loss, metrics = model.loss(p, b)
            return loss, metrics

        if mb > 1:
            split = jax.tree.map(
                lambda a: a.reshape((mb, a.shape[0] // mb) + a.shape[1:])
                if a.ndim >= 1 and a.shape[0] % mb == 0 else
                a.reshape((1,) + a.shape).repeat(mb, 0), batch)
            # positions3 (3,B,S): microbatch on dim1
            if "positions3" in batch:
                p3 = batch["positions3"]
                split["positions3"] = p3.reshape(
                    (3, mb, p3.shape[1] // mb) + p3.shape[2:]).transpose(1, 0, 2, 3)

            def micro(acc, b):
                (l, m), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, b)
                # ZeRO-2: scatter each microbatch's grads before accumulating
                # (reduce-scatter inside the loop -> overlaps with backward,
                # and the f32 accumulator only ever exists scattered)
                g = jax.lax.with_sharding_constraint(g, state_sh["opt"].m)
                g = jax.tree.map(lambda a, s: a + s.astype(jnp.float32),
                                 acc, g)
                return g, (l, m)

            grads0 = jax.lax.with_sharding_constraint(
                zero_like_grads(params), state_sh["opt"].m)
            grads, (ls, ms) = jax.lax.scan(micro, grads0, split)
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss = ls.mean()
            metrics = jax.tree.map(lambda a: a.mean(), ms)
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)

        # ZeRO-2: constrain grads to the scattered layout (reduce-scatter)
        grads = jax.lax.with_sharding_constraint(
            grads, state_sh["opt"].m)
        if hyper.grad_compress:
            grads, new_err = ef_int8_compress(grads, state["err"])
        else:
            new_err = state["err"]

        lr = cosine_schedule(state["opt"].count, peak=hyper.peak_lr,
                             warmup=hyper.warmup, total=hyper.total_steps)
        new_params, new_opt = adamw_update(grads, state["opt"], lr=lr)
        new_params = jax.lax.with_sharding_constraint(
            new_params, state_sh["params"])
        new_state = {"params": new_params, "opt": new_opt, "err": new_err}
        metrics = dict(metrics, loss=loss, lr=lr)
        return new_state, metrics

    step = jax.jit(train_step,
                   in_shardings=(state_sh, None),
                   out_shardings=(state_sh, None),
                   donate_argnums=(0,))
    return step, state_sh


# --------------------------------------------------------------------------
# Serve steps
# --------------------------------------------------------------------------

def make_prefill_fn(model, mesh: Mesh, shape: ShapeConfig):
    plan = model.plan
    cfg = model.cfg

    def prefill(params, batch, caches):
        return model.prefill(params, batch, caches)

    p_sh = shd.param_shardings(model.logical_axes(), mesh)
    batch_abs = input_specs(cfg, shape)
    b_sh = shd.data_shardings(batch_abs, mesh)
    caches_abs = jax.eval_shape(
        lambda: model.init_decode(shape.global_batch, shape.seq_len))
    c_sh = shd.cache_shardings(caches_abs, mesh)
    out_c_sh = c_sh
    if cfg.is_encdec:   # prefill returns (self_kv, (cross_k, cross_v))
        hkv = plan.padded_kv_heads(cfg.n_kv_heads)
        cross = jax.ShapeDtypeStruct(
            (cfg.n_layers, shape.global_batch, cfg.n_audio_frames, hkv,
             cfg.hd), jnp.bfloat16)
        out_c_sh = shd.cache_shardings((caches_abs, (cross, cross)), mesh)
    fn = jax.jit(prefill, in_shardings=(p_sh, b_sh, c_sh),
                 out_shardings=(out_c_sh, None), donate_argnums=(2,))
    return fn, (p_sh, batch_abs, caches_abs)


def make_decode_fn(model, mesh: Mesh, shape: ShapeConfig):
    """serve_step: one new token against a seq_len KV cache."""
    plan = model.plan
    cfg = model.cfg

    def decode(params, caches, tokens, pos):
        return model.decode_step(params, caches, tokens, pos)

    p_sh = shd.param_shardings(model.logical_axes(), mesh)
    abstract_caches = jax.eval_shape(
        lambda: model.init_decode(shape.global_batch, shape.seq_len))
    if cfg.is_encdec:
        # decode caches = (self_kv, (cross_k, cross_v)) — cross KV comes from
        # the encoder at prefill time
        hkv = plan.padded_kv_heads(cfg.n_kv_heads)
        cross = jax.ShapeDtypeStruct(
            (cfg.n_layers, shape.global_batch, cfg.n_audio_frames, hkv,
             cfg.hd), jnp.bfloat16)
        abstract_caches = (abstract_caches, (cross, cross))
    c_sh = shd.cache_shardings(abstract_caches, mesh,
                               seq_shard=plan.seq_shard_decode)
    dpa = dp_axes(mesh)
    dpa = dpa[0] if len(dpa) == 1 else dpa
    tok_sh = NamedSharding(mesh, P(None if plan.seq_shard_decode else dpa,
                                   None))
    step = jax.jit(decode,
                   in_shardings=(p_sh, c_sh, tok_sh, None),
                   out_shardings=(c_sh, None),
                   donate_argnums=(1,))
    return step, p_sh, c_sh, abstract_caches
