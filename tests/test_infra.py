"""Infra tests: optimizer, schedule, compression, checkpoint, data pipeline,
fault-tolerance control plane, sharding rules."""
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.checkpoint import (AsyncCheckpointer, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.data import DataPipeline
from repro.optim import adamw_init, adamw_update, cosine_schedule
from repro.optim.compress import ef_init, ef_int8_compress
from repro.runtime import ElasticPlanner, HeartbeatMonitor, StepRunner


# ---------------- optimizer ----------------

def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0], jnp.bfloat16)}
    opt = adamw_init(params)
    target = jnp.array([1.0, 2.0])
    for i in range(300):
        g = {"w": (params["w"].astype(jnp.float32) - target).astype(jnp.bfloat16)}
        params, opt = adamw_update(g, opt, lr=jnp.float32(0.05),
                                   weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(params["w"], np.float32),
                               np.asarray(target), atol=0.1)


def test_cosine_schedule_shape():
    s = lambda t: float(cosine_schedule(jnp.int32(t), peak=1.0, warmup=10,
                                        total=100))
    assert s(0) == 0.0
    assert abs(s(10) - 1.0) < 0.11
    assert s(50) < s(10)
    assert s(100) >= 0.099   # floor


@settings(max_examples=20, deadline=None)
@given(st.lists(st.floats(-10, 10), min_size=4, max_size=16))
def test_ef_compression_error_feedback(vals):
    """Accumulated compressed updates converge to accumulated true grads
    (the error-feedback property)."""
    g = {"w": jnp.asarray(vals, jnp.float32)}
    err = ef_init(g)
    total_true = jnp.zeros_like(g["w"])
    total_sent = jnp.zeros_like(g["w"])
    for i in range(20):
        deq, err = ef_int8_compress(g, err)
        total_true += g["w"]
        total_sent += deq["w"]
    resid = np.abs(np.asarray(total_sent - total_true))
    scale = max(1e-6, float(jnp.max(jnp.abs(g["w"]))))
    assert resid.max() <= scale / 127 + 1e-5   # bounded by one quantum


# ---------------- checkpoint ----------------

def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
             "b": [jnp.int32(3), jnp.ones((2,), jnp.bfloat16)]}
    save_checkpoint(str(tmp_path), 7, state, extra={"cursor": 9})
    assert latest_step(str(tmp_path)) == 7
    restored, extra = restore_checkpoint(str(tmp_path), 7, state)
    assert extra == {"cursor": 9}
    for x, y in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x, dtype=np.float32),
                                      np.asarray(y, dtype=np.float32))


def test_uncommitted_checkpoints_invisible(tmp_path):
    state = {"a": jnp.zeros(3)}
    save_checkpoint(str(tmp_path), 1, state)
    os.remove(os.path.join(tmp_path, "step_1", "COMMITTED"))
    assert latest_step(str(tmp_path)) is None


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path))
    ck.save(2, {"w": jnp.ones((4,))})
    ck.wait()
    assert latest_step(str(tmp_path)) == 2


# ---------------- data pipeline ----------------

def test_pipeline_determinism_and_resume():
    cfg = configs.get_reduced("qwen1.5-0.5b")
    shape = configs.ShapeConfig("t", "train", 32, 2)
    p1 = DataPipeline(cfg, shape, seed=5)
    batches = [next(p1) for _ in range(5)]
    p2 = DataPipeline(cfg, shape, seed=5)
    p2.cursor.step = 3
    b3 = next(p2)
    np.testing.assert_array_equal(b3["tokens"], batches[3]["tokens"])


def test_pipeline_prefetch():
    cfg = configs.get_reduced("qwen1.5-0.5b")
    shape = configs.ShapeConfig("t", "train", 32, 2)
    p = DataPipeline(cfg, shape, seed=1)
    p.start_prefetch()
    b = p.get()
    assert b["tokens"].shape == (2, 32)
    p.stop()


# ---------------- fault tolerance ----------------

def test_heartbeat_straggler_and_death():
    t = [0.0]
    mon = HeartbeatMonitor(["a", "b", "c"], straggler_factor=2.0,
                           dead_after_s=10.0, now=lambda: t[0])
    for i in range(10):
        mon.beat("a", 1.0)
        mon.beat("b", 1.1)
        mon.beat("c", 5.0)       # slow
        t[0] += 1
    assert mon.stragglers() == ["c"]
    t[0] += 20                   # b stops beating
    mon.beat("a", 1.0)
    mon.beat("c", 5.0)
    dead = mon.dead()
    assert "b" in dead
    assert "b" not in mon.alive_workers()


def test_elastic_planner_drops_pod():
    pl = ElasticPlanner(pods=2, data=16, model=16)
    plan = pl.plan({1: 3})       # pod 1 lost 3 devices
    assert plan.dropped_pods == 1
    assert plan.mesh_shape == (16, 16)
    assert plan.batch_scale == 0.5
    assert not plan.needs_reshard   # pod axis is pure DP


def test_elastic_planner_shrinks_data_axis():
    pl = ElasticPlanner(pods=1, data=16, model=16)
    plan = pl.plan({0: 5})
    assert plan.needs_reshard
    assert plan.mesh_shape[0] < 16 and plan.mesh_shape[1] == 16


def test_step_runner_retries():
    calls = {"n": 0}

    def flaky(state, batch):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return state + 1, {"loss": 0.0}

    r = StepRunner(flaky, max_retries=2)
    state, m = r.run(0, 0, None)
    assert state == 1 and r.failures == 1


# ---------------- sharding rules ----------------

def test_sharding_rules():
    from repro.launch.sharding import param_pspec, zero1_pspec
    from jax.sharding import PartitionSpec as P
    assert param_pspec(("vocab", "embed")) == P("model", None)
    assert param_pspec(("embed", "q_heads", "head_dim")) == \
        P(None, "model", None)
    # zero1 adds dp on the first replicated divisible dim
    sp = zero1_pspec(("embed", "q_heads", "head_dim"), (1024, 16, 64), 8)
    assert sp == P("data", "model", None)
    # indivisible dims stay replicated
    sp = zero1_pspec(("embed",), (13,), 8)
    assert sp == P(None)


def test_cache_shardings_typed():
    from repro.launch.sharding import cache_shardings
    from repro.launch.mesh import make_test_mesh
    from repro.models import build_model, Plan
    cfg = configs.get_reduced("jamba-v0.1-52b")
    model = build_model(cfg, Plan())
    caches = jax.eval_shape(lambda: model.init_decode(2, 32))
    mesh = make_test_mesh(1, 1)
    sh = cache_shardings(caches, mesh)
    # structure must match exactly (tree prefix errors would throw in jit)
    jax.tree.map(lambda a, b: None, caches, sh)


# ---------------- persistent compile cache ----------------

def test_compile_cache_defaults_to_checkout(monkeypatch):
    from repro.launch import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        path = compile_cache.enable()
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_dir_is_used(tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR is left alone, and the compile lands
    there (a fresh process: the cache is set up at the first compile)."""
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch import compile_cache\n"
            "print(compile_cache.enable())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)
    assert os.listdir(tmp_path)
