"""A benchmark cell: its configuration and traffic files, and one point.

Everything that belongs to one configuration, one traffic mix or one
point path is a file found by name (``configs/<config>.json``,
``traffic/<traffic>.json``, ``paths/<path>.py``); this module is the one
general reader of all three.  The unit of work is one campaign point:
synthesize every mix of the traffic from a seed, then simulate every
configuration on every mix, until every result is finished.  The traffic
file's ``path`` key (``sweep`` where it has none) names the program entry
point a point runs through: the module's ``point(camp, seed, index)``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<traffic>.json
    root: str = ROOT    # the checkout whose bench/ holds the cell's files

    @classmethod
    def load(cls, bench: dict, name: str, root: str = ROOT) -> "Cell":
        w = find_workload(bench, name)
        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
        return cls(name=name, chips=w["chips"],
                   config=load_json(root, cfg["file"]),
                   traffic=load_json(root, "bench", "traffic",
                                     w["traffic"] + ".json"), root=root)


def load_path(name: str, root: str = ROOT):
    """The point path module ``bench/paths/<name>.py`` under ``root``."""
    path = os.path.join(root, "bench", "paths", name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no point path {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(f"bench_path_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def point_seed(seed: int, index: int, mix: int) -> int:
    """The generator seed of one mix of one point: a 31-bit digest, so any
    run seed, however large, gives the device generator an int32."""
    h = hashlib.sha256(f"{seed}/{index}/{mix}".encode()).digest()
    return int.from_bytes(h[:4], "little") & 0x7FFFFFFF


class Campaign:
    """The program's objects for one cell: configurations, controller, the
    spec of each mix, and the point path module (``self.path``)."""

    def __init__(self, cell: Cell):
        from repro.core import workload
        from repro.core.timing import SchedConfig, paper_config
        self.cell = cell
        t = cell.traffic
        sched = SchedConfig(**t["controller"])
        self.cfgs = [dataclasses.replace(paper_config(**c), sched=sched)
                     for c in cell.config["configs"]]
        self.cores = [tuple(workload.CoreWorkload(**c) for c in m["cores"])
                      for m in t["mixes"]]
        self._workload = workload
        self.path = load_path(t.get("path", "sweep"), cell.root)

    def specs(self, seed: int, index: int):
        t = self.cell.traffic
        return [self._workload.WorkloadSpec(
            family=t["family"], cores=cores, n_channels=t["n_channels"],
            per_channel=t["per_channel"], seed=point_seed(seed, index, m))
            for m, cores in enumerate(self.cores)]


@dataclasses.dataclass
class Point:
    index: int
    traces: list            # device traces, one per mix
    results: list           # results[mix][cfg]: RunResult
    t_start: float
    t_synth: float          # end of synthesis
    t_end: float


def run_point(camp: Campaign, seed: int, index: int) -> Point:
    """One campaign point through the cell's point path."""
    return camp.path.point(camp, seed, index)


def real_requests(trace) -> int:
    from repro.core.dram import NOOP_ISSUE
    return int(np.sum(np.asarray(trace.t_issue) < NOOP_ISSUE))


def failures(point: Point, real: list) -> int:
    """Results whose counters are unhealthy or did not retire exactly the
    mix's real requests."""
    from repro.launch.orchestrator import counters_diagnosis
    bad = 0
    for w, row in enumerate(point.results):
        for r in row:
            done = int(np.sum(r.counters.reads) + np.sum(r.counters.writes))
            bad += counters_diagnosis(r.counters) is not None \
                or done != real[w]
    return bad
