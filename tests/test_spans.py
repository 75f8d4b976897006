"""Program spans on the profiler's clock (DESIGN.md §15): ``obs.trace.span``
inside ``sched_policies.schedule`` and ``simulator.sweep``/``sweep_traces``,
and ``Tracer``'s spans mirrored as ``repro.orch.*`` host events.

Each test records a CPU ``jax.profiler`` trace and reads its host events
back; nothing here needs a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import pathlib
import warnings

import jax
import numpy as np
import pytest

from repro.core import dram, simulator, workload
from repro.core.sched import policies as sched_policies
from repro.core.timing import SchedConfig, paper_config
from repro.obs import trace as obs_trace
from repro.obs.trace import Tracer

FRFCFS = SchedConfig("frfcfs", queue_depth=8)
MECHS = ("base", "figcache_fast")
N_LEAVES = len(dram.Counters._fields)


def _cfgs():
    """2 mechanisms x (FCFS, FR-FCFS): four static groups of one config."""
    return [dataclasses.replace(paper_config(m), sched=sc)
            for m in MECHS for sc in (None, FRFCFS)]


def _specs():
    return [workload.preset("zipf_reuse", n_cores=2, n_channels=2,
                            per_channel=128 + 32 * i, seed=7 + i)
            for i in range(2)]


def _traced(tmp_path, fn):
    """Run ``fn`` under a CPU profiler trace; returns its result and the
    trace's program spans as (name, start, end, stats)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    return out, program_events(str(tmp_path))


def program_events(log_dir, prefix="repro."):
    """Host events whose name starts with ``prefix``, sorted by start."""
    f, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(f)
    out = []
    with warnings.catch_warnings():     # the stats' type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefix):
                        s = int(ev.start_ns)
                        out.append((ev.name, s, s + int(ev.duration_ns),
                                    dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def executions(log_dir):
    """Start times of the CPU backend's program executions."""
    f, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(f)
    return [int(ev.start_ns) for plane in pd.planes for line in plane.lines
            for ev in line.events if ev.name == "PjRtCpuExecutable::Execute"]


def _leaves(res):
    return [np.asarray(x) for row in res for r in row
            for x in (*jax.tree.leaves(r.counters), r.ipc, r.avg_lat_ns,
                      r.row_hit_rate, r.cache_hit_rate, r.exec_time_ns,
                      r.dram_energy_nj, r.system_energy_nj)]


@pytest.fixture(scope="module")
def traced_sweep(tmp_path_factory):
    """``sweep_traces`` on two device-generated workloads, untraced and
    then traced (both warm)."""
    specs = _specs()
    trs = [workload.generate(s) for s in specs]
    apps = [s.apps() for s in specs]
    cfgs = _cfgs()
    plain = simulator.sweep_traces(trs, cfgs, apps)
    traced, spans = _traced(tmp_path_factory.mktemp("sweep"),
                            lambda: simulator.sweep_traces(trs, cfgs, apps))
    return plain, traced, spans, trs


def test_spans_leave_sweep_results_bitwise_equal(traced_sweep):
    plain, traced, _, _ = traced_sweep
    a, b = _leaves(plain), _leaves(traced)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and np.array_equal(x, y)


def test_sweep_traces_spans_and_stats(traced_sweep):
    _, _, spans, trs = traced_sweep
    names = [n for n, *_ in spans]
    W, C, groups = 2, 2, 4
    t_max = max(tr.t_issue.shape[-1] for tr in trs)
    # one stack; one schedule per controller, over the whole stacked batch
    sched = [st for n, _, _, st in spans if n == "repro.sched.schedule"]
    assert sorted(st["policy"] for st in sched) == ["fcfs", "frfcfs"]
    assert all(st["lanes"] == W * C and st["requests"] == W * C * t_max
               for st in sched)
    stack = [st for n, _, _, st in spans if n == "repro.sweep.stack"]
    assert stack == [{"workloads": W, "trips": t_max}]
    disp = [st for n, _, _, st in spans if n == "repro.sweep.dispatch"]
    assert sorted(st["mechanism"] for st in disp) == sorted(MECHS * 2)
    assert all(st["configs"] == 1 and st["lanes"] == W * C for st in disp)
    post = [st for n, _, _, st in spans if n == "repro.sweep.post"]
    assert len(post) == groups * W
    # by hand: a group's first post span copies its 12 leaves to the host
    # at once; every cut after that is numpy indexing, no device program
    assert post == [{"configs": 1, "device_ops": 0, "d2h_copies": 12},
                    {"configs": 1, "device_ops": 0, "d2h_copies": 0}] \
        * groups
    assert len(names) == len(sched) + len(stack) + len(disp) + len(post)


def test_sweep_spans_are_disjoint_siblings(traced_sweep):
    _, _, spans, _ = traced_sweep
    for (n0, s0, e0, _), (n1, s1, e1, _) in zip(spans, spans[1:]):
        assert e0 <= s1, (n0, n1)


def _first_of_group(spans):
    """Post spans as (start, end, stats, first): ``first`` marks the one
    that follows its group's dispatch span."""
    out, prev = [], None
    for n, s, e, st in spans:
        if n == "repro.sweep.post":
            out.append((s, e, st, prev == "repro.sweep.dispatch"))
        prev = n
    return out


def test_post_device_ops_count_the_programs_run(tmp_path):
    """The ``device_ops`` stat equals the programs the CPU backend ran
    inside each post span, on multi- and single-channel inputs: none, as
    each group's counters reach the host in one copy, made in its first
    post span, and every cut after is numpy indexing."""
    spec = _specs()[0]
    multi = [workload.generate(spec)] * 2
    single = [jax.tree.map(lambda a: a[0], tr) for tr in multi]
    cfgs = [paper_config("base"), paper_config("lldram")]
    for trs in (multi, single):
        simulator.sweep_traces(trs, cfgs, [spec.apps()] * 2)   # warm
        d = tmp_path / str(trs[0].t_issue.ndim)
        _, spans = _traced(d, lambda trs=trs: simulator.sweep_traces(
            trs, cfgs, [spec.apps()] * 2))
        runs = executions(str(d))
        post = _first_of_group(spans)
        assert len(post) == 2 * 2
        assert [first for *_, first in post] == [True, False] * 2
        for s, e, st, first in post:
            assert st["device_ops"] == 0
            assert st["d2h_copies"] == (N_LEAVES if first else 0)
            assert sum(s <= t < e for t in runs) == st["device_ops"]


def test_sweep_and_identity_schedule_spans(tmp_path):
    """``sweep`` (one trace) has a dispatch and a post span per group; the
    FCFS identity path of ``schedule`` is a span too."""
    spec = _specs()[0]
    tr = workload.generate(spec)
    cfgs = [paper_config("base"), paper_config("figcache_fast"),
            paper_config("figcache_fast", cache_rows=32)]
    res, spans = _traced(tmp_path, lambda: (
        sched_policies.schedule(tr, None),
        simulator.sweep(tr, cfgs, spec.apps())))
    assert res[0] is tr
    names = [n for n, *_ in spans]
    assert names.count("repro.sched.schedule") == 2
    disp = [st for n, _, _, st in spans if n == "repro.sweep.dispatch"]
    assert sorted(st["configs"] for st in disp) == [1, 2]
    assert all(st["lanes"] == st["configs"] * 2 for st in disp)
    post = [st for n, _, _, st in spans if n == "repro.sweep.post"]
    assert sorted(st["configs"] for st in post) == [1, 2]
    assert all(st["device_ops"] == 0 and st["d2h_copies"] == N_LEAVES
               for st in post)


def test_span_outside_a_trace_is_a_plain_context():
    with obs_trace.span("repro.test", a=1, b="x") as s:
        assert s is not None


def _tracer_run(path):
    tracer = Tracer(str(path))
    with tracer.span("run", grid="g", shards=2):
        tracer.begin("shard", key="k0", attempt=0)
        with tracer.span("checkpoint.save", shard="k0", segment=1):
            tracer.event("checkpoint.fresh", shard="k0")
        tracer.end("shard", outcome="done")
    with pytest.raises(RuntimeError):
        with tracer.span("run", grid="g", shards=2):
            tracer.begin("shard", key="k1", attempt=0)
            raise RuntimeError("dies inside an open shard")
    tracer.close()
    return path.read_bytes()


def test_tracer_spans_on_the_profiler_clock(tmp_path):
    plain = _tracer_run(tmp_path / "plain.jsonl")
    traced, spans = _traced(tmp_path / "prof",
                            lambda: _tracer_run(tmp_path / "traced.jsonl"))
    assert traced == plain
    assert [n for n, *_ in spans] == [
        "repro.orch.run", "repro.orch.shard", "repro.orch.checkpoint.save",
        "repro.orch.run", "repro.orch.shard"]
    (_, rs, re_, rst), (_, ss, se, sst), (_, cs, ce, cst) = spans[:3]
    assert rst == {"grid": "g", "shards": 2}
    assert sst == {"key": "k0", "attempt": 0}
    assert cst == {"shard": "k0", "segment": 1}
    assert rs <= ss <= cs <= ce <= se <= re_
    # the raising span closes its program spans, not its JSONL record
    assert all(e > s for _, s, e, _ in spans)
    log = [ln for ln in pathlib.Path(tmp_path / "plain.jsonl")
           .read_text().splitlines()]
    assert sum('"ph":"B"' in ln for ln in log) \
        - sum('"ph":"E"' in ln for ln in log) == 2
