"""Smoke run of the system's main paths on a TPU, through their entry points.

    python chip_smoke.py              # one chip: every phase below but mesh
    python chip_smoke.py --chips 4    # four chips: the orchestrated mesh run

One process; each phase prints one line (what ran, its shapes, wall time
to a finished result, and its check), and any failed check ends the run
with a non-zero exit.  The last line of standard output is one JSON object
naming the device.  Without a TPU the script exits non-zero before any
phase: there is no CPU fallback.

Phases on one chip, in order:
  campaign    the paper's 8-core campaign: 8 mixes x the six mechanisms,
              4 channels x 65536 requests per mix;
  crosscheck  one mix at 4096 requests per channel on the TPU and on the
              host CPU device, every counter bitwise equal, each group's
              scan shown to run on its device;
  kernel      one static group with the fused Pallas FTS lookup against
              the same group without it, bitwise equal, with the kernel
              present in the compiled program;
  streaming   a workload grid past one 65536-request chunk, chunked
              against monolithic, bitwise equal;
  serving     qwen1.5-0.5b at its published width with FIGCache-KV,
              every emitted token in the vocabulary.
With ``--chips 4``: the orchestrated sweep over the ("params", "channel")
mesh against the single-device sweep of the same grid, bitwise equal, with
every shard's carry spread over all four devices.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
CAMPAIGN_REQS = 65536      # per channel, per mix
CROSSCHECK_REQS = 4096
STREAM_CHUNK = 65536
TRACE_SEED = 2             # simulator.run_eight_core_batch's default


def check(ok: bool, line: str) -> None:
    """Print a phase's line with its verdict; a failed check ends the run."""
    print(f"{line} check={'pass' if ok else 'FAIL'}", flush=True)
    if not ok:
        raise SystemExit(f"check failed: {line}")


def counters_equal(a, b) -> bool:
    import numpy as np
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def scan_platforms(tr, cfgs) -> set:
    """Platforms the scans of ``cfgs``' static groups run on under the
    default device.  ``sweep``'s counters come back as host numpy, so this
    dispatches each group's ``dram.run_sweep`` on the same inputs and reads
    where its counters live before any post-processing."""
    import jax
    import jax.numpy as jnp
    from repro.core import dram, simulator
    tr = jax.tree.map(jnp.asarray, tr)
    out = set()
    for (static, _), idxs in simulator.static_groups(cfgs).items():
        batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[cfgs[i].params() for i in idxs])
        out |= {d.platform for leaf in jax.tree.leaves(
                    dram.run_sweep(tr, static, batch))
                for d in leaf.devices()}
    return out


def phase_campaign():
    import numpy as np
    from benchmarks import common
    from repro.core import dram, simulator, traces
    from repro.launch.orchestrator import counters_diagnosis

    mixes = [traces.eight_core_workloads()[i] for i in common.ALL_WL]
    t0 = time.perf_counter()
    trs = [traces.build_trace(apps, 4, CAMPAIGN_REQS, TRACE_SEED)
           for _, _, apps in mixes]
    print(f"[setup] host trace build: {len(mixes)} mixes x 4 channels x "
          f"{CAMPAIGN_REQS} requests in {time.perf_counter() - t0:.2f}s",
          flush=True)
    mechs = simulator.PAPER_MECHS
    t0 = time.perf_counter()
    res = simulator.sweep_traces(trs, simulator.mech_grid(mechs, None),
                                 [apps for _, _, apps in mixes])
    wall = time.perf_counter() - t0     # results are host numpy: finished
    real = [int(np.sum(np.asarray(tr.t_issue) < dram.NOOP_ISSUE))
            for tr in trs]
    bad = [(w, m) for w, row in enumerate(res)
           for m, r in zip(mechs, row)
           if counters_diagnosis(r.counters) is not None
           or int(np.sum(r.counters.reads) + np.sum(r.counters.writes))
           != real[w]]
    fast = [simulator.speedup_summary(dict(zip(mechs, row)))["figcache_fast"]
            for row in res]
    print(f"[campaign] figcache_fast average weighted speedup over base "
          f"{float(np.mean(fast)):.4f} (unvalidated against the paper's "
          f"16.3%)", flush=True)
    check(not bad,
          f"[campaign] sweep_traces {len(mixes)} mixes x {len(mechs)} "
          f"mechanisms, trace ({len(mixes) * 4}, {CAMPAIGN_REQS}), "
          f"{sum(real)} real requests per mechanism, wall {wall:.2f}s "
          f"incl. compile; every request retired, counters healthy"
          + (f" (bad: {bad})" if bad else ""))


def phase_crosscheck():
    import jax
    from benchmarks import common
    from repro.core import simulator, traces

    _, _, apps = traces.eight_core_workloads()[common.ALL_WL[0]]
    tr = traces.build_trace(apps, 4, CROSSCHECK_REQS, TRACE_SEED)
    cfgs = simulator.mech_grid(simulator.PAPER_MECHS, None)
    t0 = time.perf_counter()
    on_tpu = simulator.sweep(tr, cfgs, apps)
    t_tpu = time.perf_counter() - t0
    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        on_cpu = simulator.sweep(tr, cfgs, apps)
    t_cpu = time.perf_counter() - t0
    equal = all(counters_equal(a.counters, b.counters)
                for a, b in zip(on_tpu, on_cpu))
    where = [scan_platforms(tr, cfgs)]
    with jax.default_device(jax.devices("cpu")[0]):
        where.append(scan_platforms(tr, cfgs))
    check(equal and where == [{"tpu"}, {"cpu"}],
          f"[crosscheck] sweep {len(cfgs)} mechanisms, trace "
          f"(4, {CROSSCHECK_REQS}), tpu {t_tpu:.2f}s cpu {t_cpu:.2f}s "
          f"(both incl. compile), scans on {where[0]} vs {where[1]}, "
          f"bitwise equal={equal}")


def phase_kernel():
    import jax
    import jax.numpy as jnp
    from benchmarks import common
    from repro.core import dram, traces
    from repro.core.timing import paper_config, shared_static

    _, _, apps = traces.eight_core_workloads()[common.ALL_WL[0]]
    tr = jax.tree.map(jnp.asarray, traces.build_trace(
        apps, 4, CROSSCHECK_REQS, TRACE_SEED))
    rows = (16, 32, 64)

    def group(fts_kernel):
        cfgs = [paper_config("figcache_fast", cache_rows=r,
                             fts_kernel=fts_kernel) for r in rows]
        batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[c.params() for c in cfgs])
        return shared_static(cfgs), batch

    out, walls, texts = {}, {}, {}
    for k in (True, False):
        static, batch = group(k)
        t0 = time.perf_counter()
        compiled = dram.run_sweep.lower(tr, static, batch).compile()
        out[k] = jax.block_until_ready(compiled(tr, batch))
        walls[k] = time.perf_counter() - t0
        texts[k] = compiled.as_text()
    has_kernel = "tpu_custom_call" in texts[True]
    equal = counters_equal(out[True], out[False])
    check(equal and has_kernel,
          f"[kernel] run_sweep figcache_fast P={len(rows)} x C=4, trace "
          f"(4, {CROSSCHECK_REQS}), fts_kernel on {walls[True]:.2f}s off "
          f"{walls[False]:.2f}s (incl. compile), tpu_custom_call="
          f"{has_kernel}, bitwise equal={equal}")


def phase_streaming():
    import jax
    from repro.core import simulator, workload
    from repro.core.timing import paper_config

    per_channel = STREAM_CHUNK + STREAM_CHUNK // 2
    specs = [workload.preset(fam, n_cores=8, n_channels=4,
                             per_channel=per_channel, seed=3)
             for fam in ("zipf_reuse", "phase_mix")]
    cfgs = [paper_config("base"), paper_config("figcache_fast")]
    # synthesis compiles once per family; time it apart from the scans
    t0 = time.perf_counter()
    jax.block_until_ready(workload.generate_many(specs))
    print(f"[setup] device trace synthesis: {len(specs)} specs x 4 "
          f"channels x {per_channel} requests in "
          f"{time.perf_counter() - t0:.2f}s incl. compile", flush=True)
    t0 = time.perf_counter()
    mono = simulator.sweep_traces(specs, cfgs)
    t_mono = time.perf_counter() - t0
    t0 = time.perf_counter()
    chunked = simulator.sweep_traces(specs, cfgs, chunk_len=STREAM_CHUNK)
    t_chunk = time.perf_counter() - t0
    equal = all(counters_equal(a.counters, b.counters)
                for ra, rb in zip(mono, chunked) for a, b in zip(ra, rb))
    check(equal,
          f"[streaming] sweep_traces {len(specs)} specs x {len(cfgs)} "
          f"configs, trace ({4 * len(specs)}, {per_channel}), monolithic "
          f"{t_mono:.2f}s chunked@{STREAM_CHUNK} {t_chunk:.2f}s "
          f"(incl. compile), bitwise equal={equal}")


def phase_serving():
    import numpy as np
    from repro import configs
    from repro.launch import serve

    arch, prompt, gen, batch = "qwen1.5-0.5b", 512, 32, 8
    cfg = configs.get(arch)
    t0 = time.perf_counter()
    toks = serve.run(arch, reduced=False, prompt_len=prompt, gen=gen,
                     batch=batch, figkv=True)
    wall = time.perf_counter() - t0
    ok = (toks.shape == (batch, gen) and int(np.min(toks)) >= 0
          and int(np.max(toks)) < cfg.vocab_size)
    check(ok,
          f"[serving] {arch} published width ({cfg.n_layers} layers, "
          f"d_model {cfg.d_model}, vocab {cfg.vocab_size}) batch {batch} "
          f"prompt {prompt} gen {gen} figkv, wall {wall:.2f}s incl. "
          f"compile, tokens {toks.shape} in vocabulary={ok}")


def phase_mesh():
    import jax
    from repro.core import simulator, workload
    from repro.core.timing import paper_config
    from repro.launch import orchestrator

    n_dev = len(jax.devices())
    # P = 2 configs per group and C = 4 channels: make_sweep_mesh lays each
    # group over a (2, 2) ("params", "channel") mesh, one shard per device
    specs = [workload.preset("zipf_reuse", n_cores=8, n_channels=4,
                             per_channel=16384, seed=5)]
    cfgs = [paper_config(m, cache_rows=r)
            for m in ("figcache_fast", "figcache_slow") for r in (32, 64)]
    chunk = 4096
    plan = orchestrator.make_plan(specs, cfgs, chunk_len=chunk)
    with tempfile.TemporaryDirectory() as run_dir:
        t0 = time.perf_counter()
        orch = orchestrator.Orchestrator(plan, run_dir, use_mesh=True,
                                         backoff_s=0.0)
        status = orch.run()
        t_mesh = time.perf_counter() - t0
        got = orch.counters_by_config()
        devices = [e.get("devices") for e in orch.manifest["shards"].values()]
    t0 = time.perf_counter()
    ref = simulator.sweep_traces(specs, cfgs, chunk_len=chunk)
    t_one = time.perf_counter() - t0
    equal = len(got) == len(cfgs) and all(
        counters_equal(cnt, ref[w][i].counters)
        for (w, i), cnt in got.items())
    spread = all(d == list(range(n_dev)) for d in devices)
    check(n_dev == 4 and status == {"done": len(plan.shards)} and equal
          and spread,
          f"[mesh] orchestrator {len(plan.shards)} shards x P=2 over "
          f"{n_dev} devices, trace (4, 16384) chunk {chunk}, mesh "
          f"{t_mesh:.2f}s single-device {t_one:.2f}s (incl. compile), "
          f"shard devices {devices}, bitwise equal={equal}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the orchestrated mesh phase")
    args = ap.parse_args(argv)

    # the cross-check needs the host CPU device beside the TPU
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform}")

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch import compile_cache
    compile_cache.enable()

    phases = [phase_mesh] if args.chips == 4 else [
        phase_campaign, phase_crosscheck, phase_kernel, phase_streaming,
        phase_serving]
    for phase in phases:
        phase()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
