"""Serving driver: batched prefill + decode with optional FIGCache-KV.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
        --prompt-len 64 --gen 32 --batch 4 [--figkv]

The standard path uses the exact KV cache; ``--figkv`` serves long contexts
through the paper's segment cache (hot segments in the fast pool).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch import compile_cache
from repro.models import build_model, Plan
from repro.figkv import figkv_init, figkv_prefill, figkv_decode_step


def run(arch: str, *, reduced: bool = True, prompt_len: int = 64,
        gen: int = 32, batch: int = 4, figkv: bool = False, seed: int = 0):
    cfg = configs.get_reduced(arch) if reduced else configs.get(arch)
    model = build_model(cfg, Plan(moe_capacity=0))
    rng = jax.random.PRNGKey(seed)
    params = model.init_params(rng)
    toks = jax.random.randint(jax.random.fold_in(rng, 1),
                              (batch, prompt_len), 0, cfg.vocab_size)
    batch_in = {"tokens": toks}
    if cfg.family == "vlm":
        batch_in["vision_embeds"] = jnp.zeros(
            (batch, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.is_encdec:
        batch_in["audio_embeds"] = jax.random.normal(
            jax.random.fold_in(rng, 2),
            (batch, cfg.n_audio_frames, cfg.d_model), jnp.bfloat16) * 0.1

    s_max = prompt_len + gen + 8
    caches = model.init_decode(batch, s_max)
    t0 = time.time()
    caches, logits = jax.jit(model.prefill)(params, batch_in, caches)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    step = jax.jit(model.decode_step)
    out_tokens = []
    t0 = time.time()
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    off = cfg.n_vision_tokens if cfg.family == "vlm" else 0
    for i in range(gen):
        out_tokens.append(np.asarray(tok))
        caches, logits = step(params, caches, tok, prompt_len + off + i)
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_decode = time.time() - t0
    toks_out = np.concatenate(out_tokens, 1)
    print(f"[serve] {arch}: prefill {prompt_len} toks in {t_prefill*1e3:.1f}ms; "
          f"decoded {gen} x {batch} in {t_decode*1e3:.1f}ms "
          f"({batch*gen/t_decode:.1f} tok/s)")
    if figkv and not cfg.attn_free and cfg.figkv is not None:
        demo_figkv(cfg, rng, prompt_len, gen, batch)
    return toks_out


def demo_figkv(cfg, rng, prompt_len, gen, batch):
    """Exercise the FIGCache-KV segment cache on one synthetic layer."""
    fig = cfg.figkv
    hkv = cfg.n_kv_heads
    hq = cfg.n_heads
    d = cfg.hd
    st = figkv_init(batch, prompt_len + gen + fig.seg_tokens, hkv, d, fig)
    k0 = jax.random.normal(rng, (batch, prompt_len, hkv, d), jnp.bfloat16)
    v0 = jax.random.normal(jax.random.fold_in(rng, 7),
                           (batch, prompt_len, hkv, d), jnp.bfloat16)
    st = figkv_prefill(st, k0, v0)
    step = jax.jit(lambda s, q, k, v: figkv_decode_step(
        s, q, k, v, fig, n_sel=8, recent=fig.seg_tokens * 2))
    t0 = time.time()
    for i in range(gen):
        q = jax.random.normal(jax.random.fold_in(rng, 100 + i),
                              (batch, 1, hq, d), jnp.bfloat16)
        kn = jax.random.normal(jax.random.fold_in(rng, 200 + i),
                               (batch, 1, hkv, d), jnp.bfloat16)
        vn = jax.random.normal(jax.random.fold_in(rng, 300 + i),
                               (batch, 1, hkv, d), jnp.bfloat16)
        st, out = step(st, q, kn, vn)
    jax.block_until_ready(out)
    hit = int(st.fts.valid.sum())
    print(f"[serve]   figkv: {gen} steps in {(time.time()-t0)*1e3:.1f}ms; "
          f"fast pool {hit}/{st.fts.valid.size} slots warm")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--figkv", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()
    run(args.arch, reduced=args.reduced, prompt_len=args.prompt_len,
        gen=args.gen, batch=args.batch, figkv=args.figkv)


if __name__ == "__main__":
    main()
