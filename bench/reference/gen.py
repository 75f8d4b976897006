"""Plain reference of the zipf_reuse trace synthesis (paper sec. 7 model).

Written from the generator's published description and imports nothing of
the program: one workload at a time, one core at a time, the same
counter-based draws (``jax.random.fold_in`` per request, visit and window
generation) and the same float32 arithmetic, then the per-channel merge
by the multiplicative address hash.  Run on the same device as the
program, its traces must match the program's bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NOOP_ISSUE = 1 << 30
TICKS_PER_NS = 8
N_BANKS = 16
N_ROWS = 32768
SEG16 = 16
SPR = 128 // SEG16          # generator segments per row
MAX_CONTEXTS = 8

INT_KNOBS = ("n_pages", "hot_segs", "contexts", "burst", "window")
FLOAT_KNOBS = ("zipf_a", "visit_mean", "rw", "refresh", "stream_frac")


def _knobs(cores):
    """Per-core knob vectors, typed as the generator takes them."""
    k = {f: jnp.array([int(c[f]) for c in cores], jnp.int32)
         for f in INT_KNOBS}
    k.update({f: jnp.array([float(c[f]) for c in cores], jnp.float32)
              for f in FLOAT_KNOBS})
    k["interarrival"] = jnp.array(
        [c["interarrival_ns"] * TICKS_PER_NS for c in cores], jnp.float32)
    return k


def _zipf_page(u, n_pages, a):
    """Bounded-Zipf(a) page by the inverse CDF; the log form near a = 1."""
    n = n_pages.astype(jnp.float32)
    near1 = jnp.abs(1.0 - a) < 1e-3
    e = jnp.where(near1, 1.0, 1.0 - a)
    k = jnp.where(near1, jnp.exp(u * jnp.log(n)),
                  (u * (n ** e - 1.0) + 1.0) ** (1.0 / e))
    return jnp.clip(k.astype(jnp.int32) - 1, 0, n_pages - 1)


def _per_id(key, ids, tag, m):
    k = jax.random.fold_in(key, tag)
    return jax.vmap(
        lambda i: jax.random.uniform(jax.random.fold_in(k, i), (m,)))(ids)


def _core_stream(key, p, n):
    """One core's n requests: (arrival ticks f32, page, column, write)."""
    i = jnp.arange(n, dtype=jnp.int32)
    u = jax.random.uniform(jax.random.fold_in(key, 0), (n, 5))
    ctx = jnp.minimum((u[:, 0] * p["contexts"]).astype(jnp.int32),
                      p["contexts"] - 1)
    opens = u[:, 1] < 1.0 / (1.0 + jnp.maximum(p["visit_mean"], 0.0))
    mine = ctx[:, None] == jnp.arange(MAX_CONTEXTS, dtype=jnp.int32)[None]
    of_ctx = lambda m: jnp.take_along_axis(m, ctx[:, None], axis=1)[:, 0]
    visit = of_ctx(jnp.cumsum((opens[:, None] & mine).astype(jnp.int32), 0))
    seen = jnp.cumsum(mine.astype(jnp.int32), axis=0)
    r = of_ctx(seen)
    last_open = of_ctx(jax.lax.cummax(
        jnp.where(opens[:, None] & mine, seen, -1), axis=0))
    pos = jnp.where(last_open < 0, r - 1, r - last_open)

    v = _per_id(key, visit * MAX_CONTEXTS + ctx, 1, 4)
    win = jnp.maximum(p["window"], 1)
    epoch = jnp.maximum(
        (p["window"].astype(jnp.float32)
         / jnp.maximum(p["refresh"], 1e-4)).astype(jnp.int32), 1)
    slot = jnp.where(v[:, 1] < 0.7, jnp.remainder(visit, win),
                     jnp.minimum((v[:, 2] * win).astype(jnp.int32), win - 1))
    gen = (i + slot * (epoch // win)) // epoch
    reuse = _zipf_page(_per_id(key, gen * 65536 + slot, 2, 1)[:, 0],
                       p["n_pages"], p["zipf_a"])
    fresh = v[:, 0] < p["stream_frac"]
    page = jnp.where(fresh, p["n_pages"] + jnp.remainder(
        visit * MAX_CONTEXTS + ctx, 1 << 20), reuse)

    first = jnp.remainder(page * 97, SPR)
    second = jnp.remainder(first + 1 + jnp.remainder(page * 31, SPR - 1), SPR)
    seg = jnp.where(fresh | (p["hot_segs"] == 1) | (u[:, 2] < 0.8),
                    first, second)
    c0 = jnp.minimum((v[:, 3] * SEG16).astype(jnp.int32), SEG16 - 1)
    col = seg * SEG16 + jnp.remainder(c0 + pos, SEG16)

    burst = jnp.maximum(p["burst"], 1)
    gap = -jnp.log1p(-jnp.minimum(u[:, 4], 0.999999)) \
        * p["interarrival"] * burst.astype(jnp.float32)
    t = jnp.cumsum(jnp.where(jnp.remainder(i, burst) == 0, gap, 0.0))
    return t, page, col, u[:, 3] < p["rw"]


def _channels(t, page, col, wr, n_channels, per_channel):
    """Hash every core's requests to (channel, bank, row), keep each
    channel's first ``per_channel`` by arrival, fill the rest with no-ops."""
    n_cores = t.shape[0]
    core = jnp.broadcast_to(
        jnp.arange(n_cores, dtype=jnp.int32)[:, None], t.shape)
    a = (page + core * 100003).astype(jnp.uint32)
    ch = (((a * jnp.uint32(2654435761)) >> 8)
          % jnp.uint32(n_channels)).astype(jnp.int32)
    bank = (((a * jnp.uint32(2246822519)) >> 12)
            % jnp.uint32(N_BANKS)).astype(jnp.int32)
    row = ((a * jnp.uint32(40503)) % jnp.uint32(N_ROWS)).astype(jnp.int32)
    t, ch, bank, row, col, wr, core = (
        x.reshape(-1) for x in (t, ch, bank, row, col, wr, core))
    t = jnp.minimum(t, jnp.float32(NOOP_ISSUE - 64))
    order = jnp.lexsort((t, ch))
    count = jnp.bincount(ch, length=n_channels)
    first = jnp.cumsum(count) - count
    j = jnp.arange(per_channel, dtype=jnp.int32)
    src = order[jnp.minimum(first[:, None] + j[None, :], t.size - 1)]
    ok = j[None, :] < count[:, None]
    pick = lambda x, fill: jnp.where(ok, x[src], fill)
    return {"t_issue": jnp.where(ok, t[src].astype(jnp.int32), NOOP_ISSUE),
            "bank": pick(bank, 0), "row": pick(row, 0), "col": pick(col, 0),
            "is_write": pick(wr, False), "core": pick(core, 0)}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _generate(knobs, seed, n_channels: int, per_channel: int):
    n_cores = knobs["n_pages"].shape[0]
    per_core = (13 * n_channels * per_channel // 10) // n_cores + 2048
    key = jax.random.PRNGKey(seed)
    keys = jax.vmap(lambda c: jax.random.fold_in(key, c))(
        jnp.arange(n_cores, dtype=jnp.int32))
    streams = jax.vmap(lambda k, p: _core_stream(k, p, per_core))(keys, knobs)
    return _channels(*streams, n_channels, per_channel)


def generate(cores, n_channels: int, per_channel: int, seed: int) -> dict:
    """One mix's trace as host numpy arrays, each ``(n_channels,
    per_channel)``.  Each core over-generates 30 % + 2048 requests beyond
    its share so that hash imbalance rarely leaves a channel short."""
    out = _generate(_knobs(cores), jnp.int32(seed), n_channels, per_channel)
    return {k: np.asarray(v) for k, v in out.items()}
