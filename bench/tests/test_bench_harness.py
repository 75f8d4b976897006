"""The benchmark harness on the CPU: file layout, trace reduction, one
point, and the refusal to run without a TPU.  Run with
``JAX_PLATFORMS=cpu``; nothing here needs a chip."""
from __future__ import annotations

import json
import os
import re
import subprocess
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

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TESTDATA = os.path.join(ROOT, "bench", "testdata", "tiny_window.xplane.pb")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny_cell(bench, name="grid.fcfs", n_cfgs=3, per_channel=256):
    """A cell cut to two mixes of 2 channels x ``per_channel`` requests
    and its first ``n_cfgs`` configurations."""
    cell = C.Cell.load(bench, name)
    cell.config["configs"] = cell.config["configs"][:n_cfgs]
    cell.traffic.update(n_channels=2, per_channel=per_channel,
                        mixes=cell.traffic["mixes"][:2])
    return cell


def test_names_and_units_use_allowed_characters(bench):
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names[:len(bench["end_to_end"]) + len(bench["per_layer"])
                         + len(bench["workloads"])])) \
        == len(bench["end_to_end"]) + len(bench["per_layer"]) \
        + len(bench["workloads"])
    units = [m["unit"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(UNIT.match(u) for u in units), units


ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.mark.parametrize("group", sorted(ENTRY_KEYS))
def test_entries_have_exactly_their_keys(bench, group):
    assert set(bench) == {"command", "paths", "run_seconds", *ENTRY_KEYS}
    for e in bench[group]:
        assert set(e) - {"workloads"} == ENTRY_KEYS[group], e
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], e[k]


def test_every_entry_resolves_by_name(bench):
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"])), c["file"]
        assert c["file"].startswith(bench["paths"][0] + "/")
    for w in bench["workloads"]:
        cell = C.Cell.load(bench, w["name"])
        assert cell.config["configs"] and cell.traffic["mixes"]
        assert cell.chips == w["chips"]
    for m in bench["per_layer"]:
        assert callable(R.load_metric(m["name"]).read), m["name"]
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}


def test_trace_reduction_of_a_recorded_window():
    """Three runs of one small program, 20 ms apart, recorded on a v5e."""
    red = tracing.reduce(jax.profiler.ProfileData.from_file(TESTDATA))
    assert red.devices == ["/device:TPU:0"]
    assert red.window == (50552969, 116025238)
    # the first run starts before the window span on the host clock, so
    # only the last two count
    assert red.busy(red.devices[0]) == [(71537001, 71550377),
                                        (93313835, 93327193)]
    assert red.busy_s() == pytest.approx(26734e-9, abs=1e-15)
    assert red.module_s(lambda n: n.startswith("jit__lambda")) \
        == pytest.approx(26734e-9, abs=1e-15)
    assert len(red.spans_named("bench.simulate")) == 3
    br = tracing.breakdown(red)
    assert br["device_ops"] == [["jit__lambda(11176515273480337168)",
                                 pytest.approx(26734e-9)]]
    # each gap is named by the span holding most of it: the first one
    # opens before the first point but lies mostly inside it
    assert [g[0] for g in br["idle_gaps"]] == ["bench.point", "bench.point",
                                               "bench.point"]
    assert br["idle_gaps"][0][1] == pytest.approx(0.022698045)
    ctx = R.Context(red, n_points=3, sim_reqs=1000)
    idle = R.load_metric("device_idle_share").read(ctx)
    assert idle == pytest.approx(1 - 26734 / 65472269)
    # the program named in the synthesis reader never ran: nothing to read
    assert R.load_metric("synth_ms_per_point").read(ctx) is None
    # the device clock runs about 1 ms behind the host's here, so no
    # operation falls inside a simulate span: all of it reads as host time
    host = R.load_metric("host_ms_per_point").read(ctx)
    simulate = sum(e - s for s, e in red.spans_named("bench.simulate"))
    assert host == pytest.approx(simulate / 3 * 1e-6)


def test_interval_arithmetic():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3),
                                                               (5, 9)]
    assert tracing.gaps([(2, 3), (5, 6)], (0, 10)) == [(0, 2), (3, 5),
                                                       (6, 10)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 6)]) == 8


def test_one_tiny_point_counts_attempted_and_failed(bench):
    cell = tiny_cell(bench)
    camp = C.Campaign(cell)
    p = C.run_point(camp, 2**40 + 3, 0)
    real = [C.real_requests(t) for t in p.traces]
    assert real == [512, 512]
    assert len(p.results) * len(p.results[0]) == 6
    assert C.failures(p, real) == 0
    # a result that retired one request too few is a failure
    r = p.results[0][1]
    r.counters = r.counters._replace(reads=r.counters.reads - 1)
    assert C.failures(p, real) == 1


def test_run_prints_the_contract_line(bench, capsys):
    cell = tiny_cell(bench)
    args = R.parse(["--workload", cell.name, "--seed", str(2**35 + 1),
                    "--seconds", "0.01"])
    assert R.run(args, bench, cell, jax.devices()[:1]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    out = json.loads(lines[-1])
    points = int(re.search(r"window: (\d+) points", lines[0]).group(1))
    assert "compiles inside the window: 0 program traces" in lines[0]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 6 * points
    assert set(out["metrics"]) == {"sim_reqs_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "mechs.fcfs", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout
