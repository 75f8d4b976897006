"""Resume-equivalence of the sharded sweep orchestrator (DESIGN.md §14).

The signature guarantee, one level above PR 7's chunk invariance: for every
fault plan in the injection matrix — kill at segment k in {first, interior,
last}, corrupt the latest checkpoint, drop a mesh device, straggler
re-issue, transient retry — a killed-and-resumed sweep produces counters
BITWISE identical to the uninterrupted run, and a poisoned config is
quarantined while the rest of the grid completes.

All faults are deterministic (``runtime/faults.py``: seeded schedules,
logical clock, injectable sleep) so these tests never touch wall-clock
randomness.  Plain pytest — runs on both CI dep configs.
"""
import numpy as np
import pytest

from repro.core import simulator, workload
from repro.core.timing import paper_config
from repro.launch import orchestrator as orch_mod
from repro.launch.mesh import make_sweep_mesh
from repro.runtime.faults import FaultEvent, FaultPlan, InjectedKill

CHUNK = 128


@pytest.fixture(scope="module")
def plan():
    return orch_mod.ci_grid(chunk_len=CHUNK)


@pytest.fixture(scope="module")
def oracle(plan, tmp_path_factory):
    """Uninterrupted orchestrated run — itself pinned against the
    monolithic ``sweep_traces`` oracle in the first test below."""
    d = str(tmp_path_factory.mktemp("oracle"))
    o = orch_mod.Orchestrator(plan, d, backoff_s=0.0)
    assert o.run() == {"done": len(plan.shards)}
    return o.counters_by_config()


def assert_counters_equal(got, exp, missing_ok=()):
    exp = {k: v for k, v in exp.items() if k not in missing_ok}
    assert set(got) == set(exp), (sorted(got), sorted(exp))
    for k, cnt in got.items():
        for name, a, b in zip(type(cnt)._fields, cnt, exp[k]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (k, name)


def test_uninterrupted_matches_sweep_traces_oracle(plan, oracle):
    # the orchestrated sharded run == the monolithic sweep engine, bitwise
    ref = simulator.sweep_traces(plan.specs, plan.cfgs, chunk_len=CHUNK)
    assert len(oracle) == len(plan.specs) * len(plan.cfgs)
    for (w, i), cnt in oracle.items():
        for name, a, b in zip(type(cnt)._fields, cnt, ref[w][i].counters):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (w, i, name)


def test_manifest_records_mesh_devices(plan, tmp_path):
    # each done shard names the devices its final carry sat on: the whole
    # ("params", "channel") mesh that make_sweep_mesh lays it over
    o = orch_mod.Orchestrator(plan, str(tmp_path), backoff_s=0.0)
    assert o.run() == {"done": len(plan.shards)}
    for shard in plan.shards:
        mesh = make_sweep_mesh(len(shard.cfg_idxs),
                               plan.specs[shard.w].n_channels)
        want = sorted(d.id for d in mesh.devices.flat)
        assert o.manifest["shards"][shard.key]["devices"] == want


@pytest.mark.parametrize("segment", [0, 1, 2],
                         ids=["first", "interior", "last"])
def test_kill_and_resume_bitwise(plan, oracle, tmp_path, segment):
    fp = FaultPlan([FaultEvent(kind="kill", shard=1, segment=segment,
                               mode="raise")])
    o = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                              backoff_s=0.0)
    with pytest.raises(InjectedKill):
        o.run()
    assert ("kill", 1, segment) in fp.log
    # resume in a "new process": fresh Orchestrator over the same run_dir
    o2 = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                               backoff_s=0.0)
    assert o2.run() == {"done": len(plan.shards)}
    assert_counters_equal(o2.counters_by_config(), oracle)


def test_corrupt_latest_checkpoint_falls_back(plan, oracle, tmp_path):
    # corrupt the shard's newest committed progress right after it commits,
    # then kill: the resume must fall back to the previous committed step
    # and still converge bitwise
    fp = FaultPlan([FaultEvent(kind="corrupt", shard=1, segment=1,
                               corrupt_mode="truncate_leaf"),
                    FaultEvent(kind="kill", shard=1, segment=2,
                               mode="raise")])
    o = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                              backoff_s=0.0)
    with pytest.raises(InjectedKill):
        o.run()
    o2 = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                               backoff_s=0.0)
    o2.run()
    assert_counters_equal(o2.counters_by_config(), oracle)


def test_drop_mesh_device_replans_and_matches(plan, oracle, tmp_path):
    fp = FaultPlan([FaultEvent(kind="device_loss", shard=2, segment=1)])
    o = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                              backoff_s=0.0)
    assert o.run() == {"done": len(plan.shards)}
    assert ("device_loss", 2, 1) in fp.log
    assert o._lost_devices == 1
    assert_counters_equal(o.counters_by_config(), oracle)


def test_transient_retries_with_deterministic_backoff(plan, oracle, tmp_path):
    fp = FaultPlan([FaultEvent(kind="transient", shard=0, segment=1)])
    o = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                              backoff_s=0.05)
    assert o.run() == {"done": len(plan.shards)}
    assert fp.clock.slept == [0.05]          # logical clock, not wall time
    key = plan.shards[0].key
    assert o.manifest["shards"][key]["attempts"] == 2
    assert_counters_equal(o.counters_by_config(), oracle)


def test_retry_exhaustion_quarantines_shard_only(plan, oracle, tmp_path):
    fp = FaultPlan([FaultEvent(kind="transient", shard=0, times=-1)])
    o = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                              backoff_s=0.0, max_retries=2)
    counts = o.run()
    assert counts == {"done": len(plan.shards) - 1, "quarantined": 1}
    dead = {(plan.shards[0].w, i) for i in plan.shards[0].cfg_idxs}
    assert set(o.quarantined()) == dead
    assert_counters_equal(o.counters_by_config(), oracle, missing_ok=dead)


def test_straggler_reissued_under_fresh_worker(plan, oracle, tmp_path):
    # slow-worker fault on a late shard (the fleet p50 needs earlier healthy
    # beats); the monitor's EMA deadline trips on the first slow beat and
    # the shard re-issues from its checkpoint under a new logical worker
    fp = FaultPlan([FaultEvent(kind="slow", shard=4, segment=0, factor=8.0)])
    o = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                              backoff_s=0.0)
    assert o.run() == {"done": len(plan.shards)}
    key = plan.shards[4].key
    assert o.manifest["shards"][key]["reissues"] == 1
    assert f"{key}#r1" in o.monitor.health
    assert_counters_equal(o.counters_by_config(), oracle)


def test_poisoned_config_quarantined_grid_completes(plan, oracle, tmp_path):
    fp = FaultPlan([FaultEvent(kind="poison", shard=1, cfg_pos=0, times=-1)])
    o = orch_mod.Orchestrator(plan, str(tmp_path), fault_plan=fp,
                              backoff_s=0.0)
    assert o.run() == {"done": len(plan.shards)}
    # shard 1 = workload 0, cfg positions (1, 2); pos 0 -> global cfg 1
    poisoned = (plan.shards[1].w, plan.shards[1].cfg_idxs[0])
    q = o.quarantined()
    assert poisoned in q and "negative" in q[poisoned]
    assert_counters_equal(o.counters_by_config(), oracle,
                          missing_ok={poisoned})
    # results() mirrors the quarantine as None, rest populated
    res = o.results()
    assert res[poisoned[0]][poisoned[1]] is None
    healthy = [(w, i) for w in range(len(plan.specs))
               for i in range(len(plan.cfgs)) if (w, i) != poisoned]
    assert all(res[w][i] is not None for w, i in healthy)


def test_resume_skips_done_shards(plan, tmp_path):
    o = orch_mod.Orchestrator(plan, str(tmp_path), backoff_s=0.0)
    o.run()
    attempts = {k: e["attempts"] for k, e in o.manifest["shards"].items()}
    o2 = orch_mod.Orchestrator(plan, str(tmp_path), backoff_s=0.0)
    o2.run()
    assert {k: e["attempts"] for k, e in o2.manifest["shards"].items()} \
        == attempts


def test_manifest_reconcile_repairs_half_states(plan, tmp_path):
    o = orch_mod.Orchestrator(plan, str(tmp_path), backoff_s=0.0)
    o.run()
    key0, key1 = plan.shards[0].key, plan.shards[1].key
    # (a) status says running but the result is committed -> done
    o.manifest["shards"][key0]["status"] = "running"
    # (b) status says done but the result dir vanished -> pending
    import shutil
    shutil.rmtree(o._result_dir(key1))
    orch_mod.write_manifest(o.manifest_path, o.manifest)
    o2 = orch_mod.Orchestrator(plan, str(tmp_path), backoff_s=0.0)
    assert o2.manifest["shards"][key0]["status"] == "done"
    assert o2.manifest["shards"][key1]["status"] == "pending"
    o2.run()
    assert o2.status() == {"done": len(plan.shards)}


def test_shard_keys_content_stable(plan):
    again = orch_mod.ci_grid(chunk_len=CHUNK)
    assert [s.key for s in again.shards] == [s.key for s in plan.shards]
    assert again.grid_hash == plan.grid_hash
    other = orch_mod.ci_grid(chunk_len=64)       # chunking is part of the key
    assert other.grid_hash != plan.grid_hash


def test_mismatched_grid_refused(plan, tmp_path):
    orch_mod.Orchestrator(plan, str(tmp_path), backoff_s=0.0)
    other = orch_mod.make_plan(
        [workload.preset("zipf_reuse", n_cores=2, n_channels=2,
                         per_channel=384, seed=99)],
        [paper_config("base")], chunk_len=CHUNK)
    with pytest.raises(ValueError, match="different grid"):
        orch_mod.Orchestrator(other, str(tmp_path))


def test_make_plan_rejects_raw_traces():
    with pytest.raises(TypeError, match="WorkloadSpec"):
        orch_mod.make_plan([np.zeros(4)], [paper_config("base")])


def test_shard_groups_match_simulator_dispatch(plan):
    # shards are exactly the simulator's compilation units: same grouping,
    # so orchestration adds zero compiled-program structures
    groups = simulator.static_groups(plan.cfgs)
    per_workload = sorted(idxs for (_s, _sc), idxs in groups.items())
    for w in range(len(plan.specs)):
        got = sorted(list(s.cfg_idxs) for s in plan.shards if s.w == w)
        assert got == per_workload
