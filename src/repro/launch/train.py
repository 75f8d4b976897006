"""Training driver: end-to-end loop with data pipeline, fault tolerance,
checkpoint/restart, async checkpointing.

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --shape train_4k --steps 50 --reduced --ckpt /tmp/ckpt

``--reduced`` (the default) runs the small same-family config, the e2e
example path; ``--no-reduced`` asks for the published config, which needs a
real cluster.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.data import DataPipeline
from repro.launch import sharding as shd
from repro.launch import compile_cache, steps as steps_lib
from repro.launch.mesh import make_test_mesh
from repro.models import build_model
from repro.runtime import HeartbeatMonitor, StepRunner


def run(arch: str, shape_name: str, *, steps: int = 50, reduced: bool = True,
        ckpt_dir: str | None = None, ckpt_every: int = 20,
        grad_compress: bool = False, log_every: int = 5,
        batch_override: int | None = None, seq_override: int | None = None):
    cfg = configs.get_reduced(arch) if reduced else configs.get(arch)
    shape = configs.SHAPES[shape_name]
    if batch_override or seq_override:
        shape = configs.ShapeConfig(shape.name, shape.kind,
                                    seq_override or shape.seq_len,
                                    batch_override or shape.global_batch)
    mesh = make_test_mesh(1, 1) if reduced else None
    assert mesh is not None, "full-config training requires a real cluster"

    hyper = steps_lib.Hyper(peak_lr=1e-3, warmup=10, total_steps=steps,
                            grad_compress=grad_compress)
    plan = steps_lib.make_plan(cfg, shape, mesh,
                               overrides={"microbatches": 1, "remat": "full"})
    model = build_model(cfg, plan)

    with jax.set_mesh(mesh):
        step_fn, state_sh = steps_lib.make_train_step(model, mesh, hyper)
        start = 0
        pipe = DataPipeline(cfg, shape, seed=0)
        if ckpt_dir and (ls := latest_step(ckpt_dir)) is not None:
            abstract = steps_lib.abstract_train_state(model, hyper)
            state, extra = restore_checkpoint(ckpt_dir, ls, abstract, state_sh)
            start = ls + 1
            pipe.cursor.step = extra.get("data_step", start)
            print(f"[train] restored step {ls} from {ckpt_dir}")
        else:
            state = steps_lib.init_train_state(model, jax.random.PRNGKey(0),
                                               hyper)
        ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        monitor = HeartbeatMonitor(["w0"])
        runner = StepRunner(step_fn, checkpointer=ckpt, monitor=monitor,
                            ckpt_every=ckpt_every)
        pipe.start_prefetch()
        losses = []
        for s in range(start, steps):
            batch = {k: jnp.asarray(v) for k, v in pipe.get().items()}
            state, metrics = runner.run(
                s, state, batch, extra={"data_step": pipe.cursor.step})
            if s % log_every == 0 or s == steps - 1:
                loss = float(metrics["loss"])
                losses.append(loss)
                print(f"[train] step {s:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e}")
        pipe.stop()
        if ckpt:
            ckpt.wait()
        return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    args = ap.parse_args()
    compile_cache.enable()
    t0 = time.time()
    losses = run(args.arch, args.shape, steps=args.steps,
                 reduced=args.reduced, ckpt_dir=args.ckpt,
                 grad_compress=args.grad_compress,
                 batch_override=args.batch, seq_override=args.seq)
    print(f"[train] done in {time.time()-t0:.1f}s; "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
