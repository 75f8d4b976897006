"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
(``bench/configs/``) and a traffic file (``bench/traffic/``), which names
the point path (``bench/paths/``).  Set-up imports the program, loads the
compile cache and runs one warm-up point; the window then runs whole
campaign points back to back until the first one that ends after
``--seconds``.  ``--trace 1`` runs the same window
under the JAX profiler and reports the per-layer metrics
(``bench/metrics/<name>.py``) instead of the end-to-end ones.  After the
window one point drawn from the seed is recomputed in full (every mix,
every configuration) by the plain reference (``bench/check.py``); the
numbers compared, the window's failed results among them, with their
limits, are the last lines on standard error and the last key of the
result.

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero before
any work and prints no result.  Earlier lines give the window's compiles
and garbage collections, the time of each point and how full each
caching configuration's tag store ran.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    """The devices of the run: ``n`` TPU chips, or exit non-zero."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {devs[0].platform}")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def load_metric(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Context:
    """What a per-layer metric reads: the reduced trace and the window's
    counts."""

    def __init__(self, red, n_points: int, sim_reqs: int):
        self.red, self.n_points, self.sim_reqs = red, n_points, sim_reqs


def main(argv=None, chips=require_chips) -> int:
    args = parse(argv)
    # the TPU profiler records every operation of every scan trip unless the
    # programs are compiled without per-operation trace points; a traced
    # window then overflows the trace buffers.  Every run compiles so, not
    # only traced ones, so that both run the same programs.
    os.environ["LIBTPU_INIT_ARGS"] = " ".join(filter(None, (
        os.environ.get("LIBTPU_INIT_ARGS"), "--xla_enable_hlo_trace=false")))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench.cell import Cell
    cell = Cell.load(bench, args.workload)
    devs = chips(cell.chips)
    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    # this process only: cache every program of the cell, however quick to
    # compile, so that only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return run(args, bench, cell, devs)


def run(args, bench: dict, cell, devs) -> int:
    import jax
    import numpy as np
    from bench import cell as C
    from bench import check, tracing
    from repro.core import dram, simulator, workload

    camp = C.Campaign(cell)
    C.run_point(camp, args.seed, -1)                   # warm-up
    n_jit = dram.jit_trace_count() + workload.gen_trace_count()
    compiles = []

    def on_compile(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    gc_s = []             # the window's garbage collections, in seconds
    gc_t0 = 0.0

    def on_gc(phase, _info):
        nonlocal gc_t0
        if phase == "start":
            gc_t0 = time.perf_counter()
        else:
            gc_s.append(time.perf_counter() - gc_t0)

    gc.callbacks.append(on_gc)

    prof = tempfile.TemporaryDirectory(prefix="bench-trace-") \
        if args.trace else None
    if prof:
        jax.profiler.start_trace(prof.name)
    points = []
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while not points or points[-1].t_end - t0 < args.seconds:
            points.append(C.run_point(camp, args.seed, len(points)))
    span = points[-1].t_end - t0
    jax.monitoring.unregister_event_duration_listener(on_compile)
    gc.callbacks.remove(on_gc)
    if prof:
        jax.profiler.stop_trace()
    setup_s = t0 - T_START
    n_jit = dram.jit_trace_count() + workload.gen_trace_count() - n_jit
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)

    real = [[C.real_requests(t) for t in p.traces] for p in points]
    failed = sum(C.failures(p, r) for p, r in zip(points, real))
    attempted = sum(len(p.results) * len(camp.cfgs) for p in points)
    sim_reqs = sum(int(np.sum(r.counters.reads) + np.sum(r.counters.writes))
                   for p in points for row in p.results for r in row)
    print(f"window: {len(points)} points in {span:.6f} s, compiles inside "
          f"the window: {n_jit} program traces, {len(compiles)} backend "
          f"compiles; {len(gc_s)} garbage collections, "
          f"{sum(gc_s):.6f} s in all, the longest {max(gc_s, default=0):.6f}"
          f" s", flush=True)
    print("point seconds: " + " ".join(
        f"{p.t_end - p.t_start:.4f}(synth {p.t_synth - p.t_start:.4f})"
        for p in points), flush=True)
    mechs = [c["mechanism"] for c in cell.config["configs"]]
    if len(set(mechs)) == len(mechs) and {"base", "figcache_fast"} <= set(
            mechs):
        fast = [simulator.speedup_summary(dict(zip(mechs, row)))
                ["figcache_fast"] for row in points[0].results]
        print(f"figcache_fast weighted speedup over base, point 0, mean of "
              f"{len(fast)} mixes: {float(np.mean(fast)):.6f} (unvalidated "
              f"against the paper)", flush=True)

    out = {"correct": False, "attempted": attempted, "failed": failed}
    if args.trace:
        with prof:
            red = tracing.reduce(tracing.load(prof.name))
        ctx = Context(red, len(points), sim_reqs)
        metrics = {}
        for m in bench["per_layer"]:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device_extra = {"busy_s": red.busy_s(), "window_s": red.window_s}
        out["breakdown"] = tracing.breakdown(red)
    else:
        metrics = {"sim_reqs_per_s": {"value": sim_reqs / span,
                                      "unit": "reqs/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
        device_extra = {}
    out["metrics"] = metrics
    out["device"] = {"platform": devs[0].platform,
                     "kind": devs[0].device_kind, "count": len(devs),
                     "memory_peak_bytes": peak, **device_extra}

    t_check = time.perf_counter()
    readings = {"failed": failed, **point_check(args.seed, cell, points)}
    log(f"check: {time.perf_counter() - t_check:.3f} s against the "
        f"reference")
    out["correct"] = check.verdict(readings)
    for k, v in readings.items():
        log(f"check {k}: {v!r} (limit {check.LIMITS[k]!r})")
    out["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                     for k, v in readings.items()}
    print(json.dumps(out), flush=True)
    return 0


def point_check(seed: int, cell, points) -> dict:
    """Recompute the point the seed draws, every mix under every
    configuration, with the plain reference and compare it with what the
    window produced; frees the window's device state first."""
    import numpy as np
    from bench import cell as C
    from bench import check
    t = cell.traffic
    p = points[check.draw(seed, len(points))]
    traces = [{k: np.asarray(v) for k, v in tr._asdict().items()}
              for tr in p.traces]
    for q in points:
        q.traces = None
    print(tag_store_fill(cell, p), flush=True)
    trace_bad = cnt_bad = 0
    gap = 0.0
    for mi, mix in enumerate(t["mixes"]):
        ref_trace = check.ref_gen.generate(mix["cores"], t["n_channels"],
                                           t["per_channel"],
                                           C.point_seed(seed, p.index, mi))
        refs = check.run_reference(cell.config, t, cell.config["configs"],
                                   mix, ref_trace)
        for i, (chans, nums) in enumerate(refs):
            r = p.results[mi][i]
            got = check.compare(traces[mi] if i == 0 else None,
                                r.counters._asdict(),
                                {k: getattr(r, k)
                                 for k in check.ref_post.NUMBERS},
                                ref_trace, chans, nums)
            trace_bad += got[0]
            cnt_bad += got[1]
            gap = max(gap, got[2])
    return {"trace_mismatch": trace_bad, "counter_mismatch": cnt_bad,
            "result_gap": gap}


def tag_store_fill(cell, p) -> str:
    """How full each caching configuration's tag store ran in point ``p``:
    insertions per bank (mean over banks, highest mix) against its slots
    per bank, and the dirty blocks written back on eviction."""
    import numpy as np
    from bench.reference.dram import Mech
    n_banks = cell.config["geometry"]["n_banks"]
    parts = []
    for i, cfg in enumerate(cell.config["configs"]):
        m = Mech(cfg, cell.config)
        if not m.has_cache:
            continue
        ins = max(float(np.sum(row[i].counters.insertions))
                  for row in p.results) / (cell.traffic["n_channels"]
                                           * n_banks)
        wb = sum(int(np.sum(row[i].counters.wb_blocks)) for row in p.results)
        parts.append(f"{m.name}/{m.cache_rows}x{m.spr}: {ins:.1f} of "
                     f"{m.cache_rows * m.spr} slots, {wb} blocks written back")
    return (f"tag store fill, point {p.index} (insertions per bank, highest "
            f"mix, against slots per bank): " + ("; ".join(parts) or "none"))


if __name__ == "__main__":
    sys.exit(main())
