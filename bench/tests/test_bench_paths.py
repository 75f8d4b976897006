"""Point paths found by name (``bench/paths/``) and the program spans in
the reduced trace.  Run with ``JAX_PLATFORMS=cpu``; nothing here needs a
chip.  The harness's look for a chip is skipped."""
from __future__ import annotations

import json
import os
import sys

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
SEED = 2**36 + 7


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_unknown_path_fails_loudly(bench):
    cell = C.Cell.load(bench, "grid.fcfs")
    cell.traffic["path"] = "no_such_path"
    with pytest.raises(FileNotFoundError, match="no_such_path"):
        C.Campaign(cell)


PROBE = '''"""A point path that only this test's tree holds."""
from bench import cell as C

sweep = C.load_path("sweep")


def point(camp, seed, index):
    print(f"probe point {index}", flush=True)
    return sweep.point(camp, seed, index)
'''


def test_a_path_in_a_new_tree_needs_only_new_files(bench, tmp_path, capsys):
    """A cell whose point runs through a path module that exists only in
    a new tree: new files and new entries, nothing edited."""
    w = C.find_workload(bench, "mechs.fcfs")
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    tree = tmp_path / "bench"
    for d in ("configs", "traffic", "paths"):
        (tree / d).mkdir(parents=True)
    traffic = C.load_json(ROOT, "bench", "traffic", w["traffic"] + ".json")
    traffic.update(path="probe", n_channels=2, per_channel=256,
                   mixes=traffic["mixes"][:2])
    (tree / "traffic" / "probe.fcfs.json").write_text(json.dumps(traffic))
    config = C.load_json(ROOT, cfg["file"])
    config["configs"] = config["configs"][:3]
    (tmp_path / cfg["file"]).write_text(json.dumps(config))
    (tree / "paths" / "probe.py").write_text(PROBE)
    new = dict(bench, workloads=[dict(w, name="mechs.probe",
                                      traffic="probe.fcfs")])
    cell = C.Cell.load(new, "mechs.probe", root=str(tmp_path))
    args = R.parse(["--workload", cell.name, "--seed", str(SEED),
                    "--seconds", "0.01"])
    assert R.run(args, new, cell, jax.devices()[:1]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[:2] == ["probe point -1", "probe point 0"]
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["attempted"] >= 6


# -- program spans in the reduced trace -----------------------------------

def test_recorded_window_keeps_its_spans_and_has_no_program_spans():
    red = tracing.reduce(jax.profiler.ProfileData.from_file(TESTDATA))
    assert red.prog_spans == []
    assert [n for n, _, _ in red.spans] == ["bench.point",
                                            "bench.simulate"] * 3
    assert red.prog_named("repro.sweep.post") == []


def synthetic() -> tracing.Reduced:
    """One FR-FCFS-like point, 100 ns to 200 ns: the generator's program
    ends at 120; inside ``bench.simulate`` the stacking (130-135), the
    walk (135-190) and the dispatch (190-195) lie before the scan, which
    runs from 195 on one device and from 190 on the other, and the
    post-processing inside it."""
    return tracing.Reduced(
        window=(100, 200),
        modules={"/device:TPU:0": [("jit_gen(1)", 100, 120),
                                   ("jit_run_sweep(2)", 195, 200)],
                 "/device:TPU:1": [("jit_run_sweep(2)", 190, 200)]},
        spans=[("bench.point", 100, 200), ("bench.synthesize", 100, 130),
               ("bench.simulate", 130, 200)],
        prog_spans=[("repro.sweep.stack", 130, 135, {"workloads": 1}),
                    ("repro.sched.schedule", 135, 190, {"policy": "frfcfs"}),
                    ("repro.sweep.dispatch", 190, 195, {"configs": 3}),
                    ("repro.sweep.post", 196, 199, {"configs": 3}),
                    ("repro.sweep.post", 199, 200, {"configs": 3})])


def test_breakdown_names_a_gap_by_the_span_holding_most_of_it():
    red = synthetic()
    br = tracing.breakdown(red)
    # the gap 120-195 opens in bench.synthesize (10 ns) but the walk
    # holds 55 ns of it, the stacking and the dispatch 5 ns each
    assert br["idle_gaps"] == [["repro.sched.schedule", pytest.approx(75e-9)]]
    assert tracing.holder([], (0, 5)) == "window"
    assert tracing.holder([("a", 0, 2), ("b", 3, 10)], (1, 5)) == "b"


def test_program_span_readers_on_a_synthetic_trace(bench):
    red = synthetic()
    ctx = R.Context(red, n_points=1, sim_reqs=10)
    got = {m["name"]: R.load_metric(m["name"]).read(ctx)
           for m in bench["per_layer"]}
    # idle inside each span, averaged over the two devices, in ms
    assert got["stack_ms_per_point"] == pytest.approx((5 + 5) / 2 * 1e-6)
    assert got["sched_ms_per_point"] == pytest.approx((55 + 55) / 2 * 1e-6)
    assert got["dispatch_ms_per_point"] == pytest.approx((5 + 0) / 2 * 1e-6)
    assert got["post_ms_per_point"] == pytest.approx(0.0)
    # device time of the scan, averaged over both devices, per request
    assert got["scan_ns_per_req"] == pytest.approx((5 + 10) / 2 / 10)
    assert got["host_ms_per_point"] == pytest.approx((65 + 60) / 2 * 1e-6)
    assert tracing.idle_ms_per_point(red, [], 1) is None
    # a reader whose spans lie outside the window finds nothing
    red.prog_spans = [("repro.sweep.stack", 0, 50, {})]
    assert R.load_metric("stack_ms_per_point").read(ctx) is None


def test_reduction_keeps_program_spans_with_their_stats(bench, tmp_path):
    cell = C.Cell.load(bench, "mechs.frfcfs")
    cell.config["configs"] = cell.config["configs"][:2]
    cell.traffic.update(n_channels=2, per_channel=128,
                        mixes=cell.traffic["mixes"][:1])
    camp = C.Campaign(cell)
    C.run_point(camp, SEED, -1)                         # warm-up
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            C.run_point(camp, SEED, 0)
    finally:
        jax.profiler.stop_trace()
    red = tracing.reduce(tracing.load(str(tmp_path)))
    names = sorted(n for n, *_ in red.prog_spans)
    assert names == ["repro.sched.schedule", "repro.sweep.dispatch",
                     "repro.sweep.dispatch", "repro.sweep.post",
                     "repro.sweep.post", "repro.sweep.stack"]
    (st,), = [[st for n, _, _, st in red.prog_spans
               if n == "repro.sched.schedule"]]
    assert st["policy"] == "frfcfs" and st["requests"] == 256
    assert sum(s["d2h_copies"] for n, _, _, s in red.prog_spans
               if n == "repro.sweep.post") == 24

