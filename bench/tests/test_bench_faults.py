"""``correct`` comes out false for the control and for each fault the
benchmark's cells can have, at a size a test run holds (two mixes, 2
channels x 256 requests).  CPU only: the harness's look for a chip is
skipped and the rest of a run is driven with the timed path broken
underneath.  (The one-chip cells have no exchange between chips.)"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [p for p in (os.path.join(ROOT, "src"), ROOT)
                if p not in sys.path]

from bench import cell as C  # noqa: E402
from bench import check, control  # noqa: E402
from bench import run as R  # noqa: E402
from repro.core import dram, workload  # noqa: E402


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def tiny_cell(bench, name):
    cell = C.Cell.load(bench, name)
    cell.config["configs"] = cell.config["configs"][:3]
    cell.traffic.update(n_channels=2, per_channel=256,
                        mixes=cell.traffic["mixes"][:2])
    return cell


def run_once(bench, cell, capsys) -> dict:
    jax.clear_caches()
    args = R.parse(["--workload", cell.name, "--seed", str(2**34 + 9),
                    "--seconds", "0.01"])
    assert R.run(args, bench, cell, jax.devices()[:1]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    jax.clear_caches()
    return out


@pytest.mark.parametrize("name", ["mechs.fcfs", "grid.fcfs", "mechs.frfcfs"])
def test_control_is_not_correct(bench, name):
    got = control.readings(tiny_cell(bench, name), 2**33 + 1)
    assert got["trace_mismatch"] == 0
    assert got["counter_mismatch"] > 0
    assert got["result_gap"] > check.LIMITS["result_gap"]
    assert not check.verdict(got)


def _unchanged_state(monkeypatch):
    monkeypatch.setattr(dram, "make_step",
                        lambda static, geom=dram.GEOM, variant="fused":
                        lambda params, carry, req: (carry, None))


def _half_the_channels(monkeypatch):
    """Simulate the first half of the stacked lanes (mix by mix, channel
    by channel); the rest get their mean."""
    real = dram.run_sweep

    def half(trace, static, params_batch, variant="fused"):
        h = trace.t_issue.shape[0] // 2
        cnt = real(jax.tree.map(lambda a: a[:h], trace), static,
                   params_batch)
        return jax.tree.map(lambda a: jnp.concatenate(
            [a, jnp.broadcast_to(a.mean(1, keepdims=True).astype(a.dtype),
                                 a.shape)], 1), cnt)

    monkeypatch.setattr(dram, "run_sweep", half)


def _later_mixes_copied(monkeypatch):
    """Simulate the first half of the stacked lanes, the earlier mixes, and
    hand their counters out again for the later ones."""
    real = dram.run_sweep

    def copied(trace, static, params_batch, variant="fused"):
        h = trace.t_issue.shape[0] // 2
        cnt = real(jax.tree.map(lambda a: a[:h], trace), static,
                   params_batch)
        return jax.tree.map(lambda a: jnp.concatenate([a, a], 1), cnt)

    monkeypatch.setattr(dram, "run_sweep", copied)


def _counter_altered(monkeypatch):
    real = dram.run_sweep

    def altered(trace, static, params_batch, variant="fused"):
        cnt = real(trace, static, params_batch)
        return cnt._replace(lat_sum_ns=cnt.lat_sum_ns.at[0, 0, 0].add(1))

    monkeypatch.setattr(dram, "run_sweep", altered)


def _request_altered(monkeypatch):
    real = workload.generate_many

    def altered(specs, *a, **k):
        trs = real(specs, *a, **k)
        return [trs[0]._replace(row=trs[0].row.at[0, 0].add(1))] + trs[1:]

    monkeypatch.setattr(workload, "generate_many", altered)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_the_channels,
                                   _later_mixes_copied, _counter_altered,
                                   _request_altered])
def test_fault_is_not_correct(bench, capsys, monkeypatch, fault):
    cell = tiny_cell(bench, "grid.fcfs")
    fault(monkeypatch)
    out = run_once(bench, cell, capsys)
    assert out["correct"] is False
    assert not check.verdict({k: v["value"]
                              for k, v in out["checks"].items()})


def test_failed_result_is_not_correct(bench, capsys, monkeypatch):
    """A result the window counts as failed makes the run not correct,
    however well the checked point compares."""
    monkeypatch.setattr(C, "failures", lambda point, real: 1)
    out = run_once(bench, tiny_cell(bench, "mechs.fcfs"), capsys)
    assert out["correct"] is False
    assert out["checks"]["failed"]["value"] == out["failed"] > 0
    assert check.verdict({k: v["value"] for k, v in out["checks"].items()
                          if k != "failed"})


def test_sound_run_is_correct(bench, capsys):
    out = run_once(bench, tiny_cell(bench, "mechs.frfcfs"), capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert all(v["value"] == 0 for v in out["checks"].values())
