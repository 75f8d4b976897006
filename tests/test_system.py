"""End-to-end behaviour tests: train loop runs, loss falls, checkpoint
restart is bit-exact on the data stream."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data import DataPipeline
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_test_mesh
from repro.models import build_model


def _small_shape(B=4, S=64):
    return configs.ShapeConfig("train_small", "train", S, B)


def test_train_loss_decreases():
    cfg = configs.get_reduced("qwen1.5-0.5b")
    shape = _small_shape()
    mesh = make_test_mesh(1, 1)
    hyper = steps_lib.Hyper(peak_lr=5e-3, warmup=5, total_steps=30)
    plan = steps_lib.make_plan(cfg, shape, mesh,
                               overrides={"microbatches": 1})
    model = build_model(cfg, plan)
    with jax.set_mesh(mesh):
        step, _ = steps_lib.make_train_step(model, mesh, hyper)
        state = steps_lib.init_train_state(model, jax.random.PRNGKey(0), hyper)
        pipe = DataPipeline(cfg, shape, seed=0)
        losses = []
        for _ in range(30):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses


def test_checkpoint_restart_resumes_stream(tmp_path):
    from repro.checkpoint import save_checkpoint, restore_checkpoint, latest_step
    cfg = configs.get_reduced("qwen2-7b")
    shape = _small_shape()
    mesh = make_test_mesh(1, 1)
    hyper = steps_lib.Hyper(peak_lr=1e-3, warmup=2, total_steps=20)
    plan = steps_lib.make_plan(cfg, shape, mesh,
                               overrides={"microbatches": 1})
    model = build_model(cfg, plan)
    with jax.set_mesh(mesh):
        step, state_sh = steps_lib.make_train_step(model, mesh, hyper)
        state = steps_lib.init_train_state(model, jax.random.PRNGKey(1), hyper)
        pipe = DataPipeline(cfg, shape, seed=3)
        for s in range(4):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            state, m = step(state, batch)
        save_checkpoint(str(tmp_path), 3, state,
                        extra={"data_step": pipe.cursor.step})
        # continue 2 more steps -> reference
        ref = state
        refpipe_step = pipe.cursor.step
        for s in range(2):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            ref, m_ref = step(ref, batch)

        # restart from disk
        assert latest_step(str(tmp_path)) == 3
        abstract = steps_lib.abstract_train_state(model, hyper)
        restored, extra = restore_checkpoint(str(tmp_path), 3, abstract)
        pipe2 = DataPipeline(cfg, shape, seed=3)
        pipe2.cursor.step = extra["data_step"]
        assert pipe2.cursor.step == refpipe_step
        state2 = jax.tree.map(jnp.asarray, restored)
        for s in range(2):
            batch = {k: jnp.asarray(v) for k, v in next(pipe2).items()}
            state2, m2 = step(state2, batch)
    a = jax.tree.leaves(ref["params"])
    b = jax.tree.leaves(state2["params"])
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_grad_compress_converges():
    cfg = configs.get_reduced("qwen1.5-0.5b")
    shape = _small_shape(B=4, S=32)
    mesh = make_test_mesh(1, 1)
    hyper = steps_lib.Hyper(peak_lr=5e-3, warmup=5, total_steps=25,
                            grad_compress=True)
    plan = steps_lib.make_plan(cfg, shape, mesh,
                               overrides={"microbatches": 1})
    model = build_model(cfg, plan)
    with jax.set_mesh(mesh):
        step, _ = steps_lib.make_train_step(model, mesh, hyper)
        state = steps_lib.init_train_state(model, jax.random.PRNGKey(0), hyper)
        pipe = DataPipeline(cfg, shape, seed=0)
        losses = []
        for _ in range(25):
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1


def test_microbatched_step_matches_single():
    """Grad accumulation (mb=2) must match the mb=1 step numerically
    (same data, deterministic init)."""
    cfg = configs.get_reduced("stablelm-12b")
    shape = _small_shape(B=4, S=32)
    mesh = make_test_mesh(1, 1)
    hyper = steps_lib.Hyper(peak_lr=1e-3, warmup=2, total_steps=10)
    out = {}
    for mb in (1, 2):
        plan = steps_lib.make_plan(cfg, shape, mesh,
                                   overrides={"microbatches": mb})
        model = build_model(cfg, plan)
        with jax.set_mesh(mesh):
            step, _ = steps_lib.make_train_step(model, mesh, hyper)
            state = steps_lib.init_train_state(model, jax.random.PRNGKey(7),
                                               hyper)
            pipe = DataPipeline(cfg, shape, seed=1)
            batch = {k: jnp.asarray(v) for k, v in next(pipe).items()}
            state, metrics = step(state, batch)
            out[mb] = (float(metrics["loss"]),
                       np.asarray(jax.tree.leaves(state["params"])[0],
                                  dtype=np.float32))
    assert abs(out[1][0] - out[2][0]) < 2e-2
    np.testing.assert_allclose(out[1][1], out[2][1], atol=3e-2)
