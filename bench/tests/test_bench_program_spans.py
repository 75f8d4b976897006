"""The program's own spans (``repro.*``, ``obs.trace.span``) beside the
benchmark's: each per-layer metric reads what it read before, and every
program span of a point lies inside the point's ``bench.simulate``.  Run
with ``JAX_PLATFORMS=cpu``; nothing here needs a chip."""
from __future__ import annotations

import glob
import json
import os
import sys
import warnings

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), ROOT)
                if p not in sys.path]

from bench import cell as C  # noqa: E402
from bench import run as R  # noqa: E402
from bench import tracing  # noqa: E402

TESTDATA = os.path.join(ROOT, "bench", "testdata", "tiny_window.xplane.pb")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_metrics_read_pinned_values_on_the_recorded_window(bench):
    red = tracing.reduce(jax.profiler.ProfileData.from_file(TESTDATA))
    ctx = R.Context(red, n_points=3, sim_reqs=1000)
    got = {m["name"]: R.load_metric(m["name"]).read(ctx)
           for m in bench["per_layer"]}
    assert got == {
        "synth_ms_per_point": None,          # no jit_gen in the window
        "scan_ns_per_req": None,             # no jit_run_sweep either
        # the three simulate spans, 2408751 ns, over 3 points
        "host_ms_per_point": pytest.approx(2408751e-6 / 3, rel=1e-12),
        "device_idle_share": pytest.approx(1 - 26734 / 65472269,
                                           rel=1e-12),
        # the recorded window holds no program span
        **dict.fromkeys(["sched_ms_per_point", "stack_ms_per_point",
                         "dispatch_ms_per_point", "post_ms_per_point"]),
    }
    assert tracing.breakdown(red)["device_ops"] == [
        ["jit__lambda(11176515273480337168)", pytest.approx(26734e-9)]]


def _program_spans(log_dir):
    f, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(f)
    with warnings.catch_warnings():     # the stats' type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        return [(ev.name, int(ev.start_ns),
                 int(ev.start_ns + ev.duration_ns), dict(ev.stats))
                for plane in pd.planes
                if not plane.name.startswith("/device:")
                for line in plane.lines for ev in line.events
                if ev.name.startswith("repro.")]


def test_program_spans_lie_inside_simulate(bench, tmp_path):
    cell = C.Cell.load(bench, "mechs.frfcfs")
    cell.config["configs"] = cell.config["configs"][:3]
    cell.traffic.update(n_channels=2, per_channel=256,
                        mixes=cell.traffic["mixes"][:2])
    camp = C.Campaign(cell)
    C.run_point(camp, 2**33 + 5, -1)                     # warm-up
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            C.run_point(camp, 2**33 + 5, 0)
    finally:
        jax.profiler.stop_trace()
    red = tracing.reduce(tracing.load(str(tmp_path)))
    # the reduction keeps the benchmark's spans alone, as before
    assert sorted(n for n, _, _ in red.spans) == [
        "bench.point", "bench.simulate", "bench.synthesize"]
    (sim,) = red.spans_named("bench.simulate")
    prog = _program_spans(str(tmp_path))
    names = [n for n, *_ in prog]
    # 2 mixes scheduled under one FR-FCFS controller, stacked once;
    # base, lisa_villa and figcache_slow are three static groups
    assert sorted(set(names)) == ["repro.sched.schedule",
                                  "repro.sweep.dispatch", "repro.sweep.post",
                                  "repro.sweep.stack"]
    assert [names.count(n) for n in sorted(set(names))] == [2, 3, 6, 1]
    assert all(sim[0] <= s <= e <= sim[1] for _, s, e, _ in prog)
    assert {st["policy"] for n, _, _, st in prog
            if n == "repro.sched.schedule"} == {"frfcfs"}
