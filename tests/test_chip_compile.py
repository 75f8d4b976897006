"""Compile the main path's kernels and scan for a described TPU v5e.

No chip is needed: the TPU compiler is installed with JAX and compiles for
a topology that is described, not attached.  These compiles refuse what
interpret-mode tests cannot see (block shapes Mosaic cannot tile, VMEM
over-use, a kernel the scan's vmap cannot partition).  Nothing runs, so
they say nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process may hold the TPU library at a time, and pytest
workers import every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dram
from repro.core.timing import paper_config, shared_static
from repro.kernels.figaro_reloc.figaro_reloc import reloc
from repro.kernels.figcache_decode.figcache_decode import figcache_decode
from repro.kernels.fts_lookup import ops as fts_ops
from repro.kernels.fts_lookup.fts_lookup import fts_lookup


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one, so keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("max_slots", [512, 1024])   # the two FTS buckets
def test_fts_lookup_compiles(one_chip, max_slots):
    table = _sds((16, max_slots), jnp.int32, one_chip)
    scalar = _sds((), jnp.int32, one_chip)
    text = _compiled_text(fts_lookup, table, table, scalar, scalar, scalar)
    assert "tpu_custom_call" in text


def test_figaro_reloc_compiles(one_chip):
    # qwen1.5-0.5b FIGCache-KV segment: 16 tokens x 16 kv heads x 64 dims,
    # into a 64-row x 8-segment fast pool
    E = 16 * 16 * 64
    pool = _sds((64, E), jnp.bfloat16, one_chip)
    fast = _sds((512, E), jnp.bfloat16, one_chip)
    ids = _sds((8,), jnp.int32, one_chip)
    assert "tpu_custom_call" in _compiled_text(reloc, pool, fast, ids, ids)


def test_figcache_decode_compiles(one_chip):
    # qwen1.5-0.5b decode: batch 8 x 16 heads of 64 dims over 512 slots
    B, H, L, D = 8, 16, 512, 64
    q = _sds((B * H, D), jnp.bfloat16, one_chip)
    kv = _sds((B * H, L, D), jnp.bfloat16, one_chip)
    valid = _sds((B, L), jnp.bool_, one_chip)
    fn = lambda q, k, v, m: figcache_decode(q, k, v, m, heads_per_seq=H)
    assert "tpu_custom_call" in _compiled_text(fn, q, kv, kv, valid)


def test_run_sweep_compiles_with_fts_kernel(one_chip, monkeypatch):
    """The fused scan at campaign size (4 channels x 65536 requests) with
    the fused lookup, under the params x channel vmap.  ``fts_lookup_op``
    picks its branch from the default backend, which is the CPU here, so
    the test steers it onto the kernel."""
    monkeypatch.setattr(fts_ops, "_on_tpu", lambda: True)
    cfgs = [paper_config("figcache_fast", cache_rows=r, fts_kernel=True)
            for r in (16, 32, 64)]
    static = shared_static(cfgs)
    batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[c.params() for c in cfgs])
    batch = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), batch)
    C, T = 4, 65536
    trace = dram.Trace(*(_sds((C, T), dt, one_chip) for dt in (
        jnp.int32, jnp.int32, jnp.int32, jnp.int32, jnp.bool_, jnp.int32)))
    compiled = dram.run_sweep.lower(trace, static, batch).compile()
    print(compiled.memory_analysis())
    assert "tpu_custom_call" in compiled.as_text()


def _while_index_fusions(text):
    """Gather and scatter fusions (and bare ops) in the ``while`` bodies
    of an optimized HLO module's text."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    bodies = re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", text)
    assert bodies, "no while loop in the compiled scan"
    indexed = lambda ls: any(re.search(r" (gather|scatter)\(", l) for l in ls)
    n = 0
    for body in bodies:
        for line in comps[body]:
            called = re.search(r" fusion\(.*calls=%?([\w.\-]+)", line)
            n += indexed(comps[called.group(1)]) if called else \
                indexed([line])
    return n


# per-lane index fusions of one scan trip: each runs its lanes' indices one
# after another on the TPU (DESIGN.md §9); 10 and 34 before one-hot state
TRIP_INDEX_FUSIONS = {"base": 0, "figcache_fast": 17}


@pytest.mark.parametrize("mech", sorted(TRIP_INDEX_FUSIONS))
def test_run_sweep_trip_index_fusions(one_chip, mech):
    """The ``mechs`` cells' scan, 32 lanes x 8192 requests, compiled for a
    v5e: the ``while`` body's gather and scatter fusions."""
    cfg = paper_config(mech)
    batch = jax.tree.map(lambda a: _sds((1,) + a.shape, a.dtype, one_chip),
                         cfg.params())
    C, T = 32, 8192
    trace = dram.Trace(*(_sds((C, T), dt, one_chip) for dt in (
        jnp.int32, jnp.int32, jnp.int32, jnp.int32, jnp.bool_, jnp.int32)))
    text = dram.run_sweep.lower(trace, cfg.static, batch).compile().as_text()
    assert _while_index_fusions(text) <= TRIP_INDEX_FUSIONS[mech]
