"""The simulation sanitizer's own gate (DESIGN.md §12).

Three layers:
 * one seeded-violation fixture per rule/check, asserting the analyzer
   demonstrably CATCHES it (lint fixtures are tmp files; jaxpr fixtures
   are real traced programs; the contract fixture is a registered-and-
   removed over-budget contract);
 * zero-false-positive assertions over the shipped tree: the AST lint on
   ``src/repro/core`` + ``src/repro/kernels`` + ``benchmarks``, the jaxpr
   audit on every declared entry point, and the compile-contract pass
   (which is the 1-compile guarantee for the fig12/fig13/sweep_traces
   grids);
 * the bitwise pin for the ``lat_sum_ns`` saturation fix the auditor
   surfaced: golden counters on a deterministic workload plus the proof
   the clamp is inactive below the cap.
"""
import textwrap
from typing import NamedTuple

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import contracts, findings, jaxpr_audit, lint
from repro.core import dram, workload
from repro.core.timing import paper_config

# ---------------------------------------------------------------------------
# lint rule fixtures: each snippet must be caught, exactly once


def _lint_rules_on(tmp_path, src: str):
    p = tmp_path / "fixture.py"
    p.write_text(textwrap.dedent(src))
    rep = lint.lint_paths([str(p)], repo_root=str(tmp_path))
    return [f.rule for f in rep.findings]


def test_lint_catches_traced_param_branch(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        import jax
        from repro.core.timing import MechParams

        @jax.jit
        def f(p: MechParams, x):
            if p.n_slots > 4:
                return x
            assert p.insert_threshold > 0
            return x + 1
        """)
    assert rules.count("traced-param-branch") == 2


def test_lint_allows_is_none_dispatch(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        import jax
        from repro.core.timing import MechParams

        @jax.jit
        def f(p: MechParams, x):
            if p.n_slots is None:
                return x
            return x + 1
        """)
    assert "traced-param-branch" not in rules


def test_lint_catches_unmasked_padded_reduction(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        import jax.numpy as jnp

        def pick_victim(fts):
            return jnp.argmin(fts.benefit)
        """)
    assert "unmasked-padded-reduction" in rules


def test_lint_allows_masked_reduction(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        import jax.numpy as jnp

        def pick_victim(fts, active):
            return jnp.argmin(jnp.where(active, fts.benefit, 1 << 30))
        """)
    assert "unmasked-padded-reduction" not in rules


def test_lint_catches_numpy_in_scan_body(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        import numpy as np

        def make_step(static):
            def step(carry, x):
                inc = np.float32(1.0)
                return carry + inc, carry.item()
            return step
        """)
    assert "numpy-in-scan-body" in rules


def test_lint_catches_jit_in_function_body(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        import jax

        def run(xs):
            f = jax.jit(lambda x: x + 1)
            return [f(x) for x in xs]
        """)
    assert "jit-closure-cache" in rules


def test_lint_allows_memoized_jit_factory(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        import functools
        import jax

        @functools.lru_cache(maxsize=None)
        def compiled(n):
            return jax.jit(lambda x: x + n)
        """)
    assert "jit-closure-cache" not in rules


def test_lint_catches_vmem_blowout(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        from jax.experimental import pallas as pl

        def launch(x):
            spec = pl.BlockSpec((2048, 2048), lambda i: (i, 0))
            return spec
        """)
    assert "pallas-vmem-budget" in rules


def test_lint_skips_unresolvable_vmem_dims(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        from jax.experimental import pallas as pl

        def launch(x):
            n = x.shape[0]
            spec = pl.BlockSpec((n, 4096), lambda i: (i, 0))
            return spec
        """)
    assert "pallas-vmem-budget" not in rules


def test_lint_catches_bad_io_alias(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        from jax.experimental import pallas as pl

        def launch(kernel, a, b, shape):
            bad_key = pl.pallas_call(
                kernel, out_shape=shape,
                input_output_aliases={5: 0})(a, b)
            dup_out = pl.pallas_call(
                kernel, out_shape=shape,
                input_output_aliases={0: 0, 1: 0})(a, b)
            return bad_key, dup_out
        """)
    assert rules.count("pallas-io-alias") == 2


def test_lint_pragma_suppresses(tmp_path):
    rules = _lint_rules_on(tmp_path, """
        import jax

        def run(xs):
            # repro: allow(jit-closure-cache)
            f = jax.jit(lambda x: x + 1)
            return f(xs)
        """)
    assert rules == []


# ---------------------------------------------------------------------------
# jaxpr-audit fixtures: seeded violations in real traced programs


def _audit(fn, args, carry_names=(), carry_bounds=None, len_bound=1 << 20,
           trace=None):
    entry = jaxpr_audit.Entry(
        "fixture", trace or (lambda: jax.make_jaxpr(fn)(*args)),
        carry_names=tuple(carry_names), carry_bounds=carry_bounds or {},
        len_bound=len_bound)
    return [f.rule for f in jaxpr_audit.audit_entry(entry)]


def _sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_audit_catches_x64_leak():
    def trace():
        with jax.experimental.enable_x64():
            return jax.make_jaxpr(
                lambda x: x.astype(jnp.float64) * 2.0)(
                _sds((4,), jnp.float32))
    assert "x64-leak" in _audit(None, None, trace=trace)


def test_audit_catches_weak_output():
    # a python-scalar chain never anchored to a concrete dtype
    rules = _audit(lambda x: jnp.sin(1.0), [_sds((4,), jnp.float32)])
    assert "weak-type-leak" in rules


class _Acc(NamedTuple):
    acc: jax.Array


def _scan_fixture(body):
    def fn(x0):
        c, _ = jax.lax.scan(body, _Acc(acc=x0),
                            jnp.zeros((8,), jnp.int32))
        return c.acc
    return fn


def test_audit_catches_int32_accumulator_overflow():
    # +4096/step with a 2**20-step declared capacity: wraps int32
    fn = _scan_fixture(lambda c, x: (_Acc(acc=c.acc + 4096), None))
    rules = _audit(fn, [_sds(())], carry_names=("acc",))
    assert "int32-overflow" in rules


def test_audit_accepts_saturating_accumulator():
    cap = (1 << 30) - 1
    fn = _scan_fixture(
        lambda c, x: (_Acc(acc=jnp.minimum(c.acc + 4096, cap)), None))
    rules = _audit(fn, [_sds(())], carry_names=("acc",))
    assert rules == []


def test_audit_catches_undeclared_accumulator():
    # increment comes from the scanned xs: no derivable bound, no decl
    fn = _scan_fixture(lambda c, x: (_Acc(acc=c.acc + x), None))
    rules = _audit(fn, [_sds(())], carry_names=("acc",))
    assert "undeclared-accumulator" in rules


def test_audit_accepts_declared_step_bound():
    fn = _scan_fixture(lambda c, x: (_Acc(acc=c.acc + x), None))
    rules = _audit(
        fn, [_sds(())], carry_names=("acc",),
        carry_bounds={"acc": jaxpr_audit.CarryBound("xs < 64", step=64)})
    assert rules == []


def test_audit_catches_callback_in_scan():
    def body(c, x):
        y = jax.pure_callback(lambda v: v, _sds(()), c)
        return c + y - y, None

    def fn(x0):
        c, _ = jax.lax.scan(body, x0, jnp.zeros((4,), jnp.int32))
        return c
    assert "callback-in-scan" in _audit(fn, [_sds(())])


def test_audit_catches_while_in_scan():
    def body(c, x):
        c2 = jax.lax.while_loop(lambda v: v < 10, lambda v: v + 1, c)
        return c2, None

    def fn(x0):
        c, _ = jax.lax.scan(body, x0, jnp.zeros((4,), jnp.int32))
        return c
    assert "while-in-scan" in _audit(fn, [_sds(())])


def test_audit_catches_oversized_gather_in_scan():
    n = 1 << 18
    perm = jnp.arange(n - 1, -1, -1, dtype=jnp.int32)

    def body(c, x):
        return c[perm], None

    def fn(c0):
        c, _ = jax.lax.scan(body, c0, jnp.zeros((2,), jnp.int32))
        return c
    assert "oversized-gather" in _audit(fn, [_sds((n,))])


# ---------------------------------------------------------------------------
# compile-contract fixtures


def test_contract_violation_is_caught():
    bad = contracts.Contract("fixture.bad", "always over budget", 0,
                             ("nothing",), lambda: 1)
    contracts.REGISTRY["fixture.bad"] = bad
    try:
        fs = contracts.check_contract("fixture.bad")
        assert [f.rule for f in fs] == ["compile-contract"]
        with pytest.raises(AssertionError, match="fixture.bad"):
            contracts.assert_jit_budget("fixture.bad", 3)
    finally:
        del contracts.REGISTRY["fixture.bad"]


# ---------------------------------------------------------------------------
# zero false positives on the shipped tree


def test_lint_clean_on_shipped_tree():
    rep = lint.lint_paths(("src/repro/core", "src/repro/kernels",
                           "benchmarks"))
    assert rep.findings == [], "\n" + rep.render_text()
    assert len(rep.scanned) >= 10     # the walk actually found the tree


def test_jaxpr_audit_clean_on_entry_points():
    rep = jaxpr_audit.audit_all()
    assert rep.findings == [], "\n" + rep.render_text()
    assert len(rep.scanned) == len(jaxpr_audit.default_entries())


@pytest.fixture(scope="module")
def contract_report():
    """The reusable compile-contract gate: future entry points declare a
    contract in ``repro.analysis.contracts`` and are covered here with no
    further test changes."""
    return contracts.check_all()


def test_contracts_hold_on_shipped_tree(contract_report):
    assert contract_report.findings == [], \
        "\n" + contract_report.render_text()
    # the acceptance grids are all declared and were all checked
    for name in ("sweep.timings", "sweep.capacity", "sweep.segment",
                 "simulator.sweep_traces", "workload.generate_many"):
        assert name in contract_report.scanned


def test_sarif_and_json_render():
    rep = lint.lint_paths(("src/repro/analysis",))
    import json

    import repro.analysis as analysis
    json.loads(rep.to_json())
    sarif = json.loads(rep.to_sarif(analysis.rule_index()))
    assert sarif["version"] == "2.1.0"
    assert len(sarif["runs"][0]["tool"]["driver"]["rules"]) == \
        len(analysis.rule_index())


# ---------------------------------------------------------------------------
# the lat_sum_ns saturation fix: bitwise-pinned regression

# The plain reference model (bench/reference/dram.py) yields these same
# counters, field for field, on the same trace.
_GOLD = {
    "figcache_fast": dict(
        acts_slow=53, acts_fast=0, reads=185, writes=71, reloc_blocks=848,
        wb_blocks=0, row_hits=203, cache_hits=203, insertions=53,
        lat_sum_ns=[6076, 4831, 0, 0, 0, 0, 0, 0],
        req_cnt=[142, 114, 0, 0, 0, 0, 0, 0], t_end=34140),
    "base": dict(
        acts_slow=90, acts_fast=0, reads=185, writes=71, reloc_blocks=0,
        wb_blocks=0, row_hits=166, cache_hits=0, insertions=0,
        lat_sum_ns=[7546, 5677, 0, 0, 0, 0, 0, 0],
        req_cnt=[142, 114, 0, 0, 0, 0, 0, 0], t_end=34344),
}


@pytest.mark.parametrize("mech", sorted(_GOLD))
def test_lat_sum_clamp_is_bitwise_invisible(mech):
    """Golden counters on a deterministic workload: the saturating clamp
    the auditor demanded (dram.LAT_SUM_CAP) must not move ANY counter on
    in-contract traces — every per-core sum stays far below the cap, where
    ``min(x, cap) == x`` exactly."""
    spec = workload.preset("zipf_reuse", n_cores=2, n_channels=1,
                           per_channel=256, seed=11)
    tr = jax.tree.map(lambda a: a[0], workload.generate(spec))
    cnt = dram.run_channel(tr, paper_config(mech))
    import numpy as np
    for field, want in _GOLD[mech].items():
        got = np.asarray(getattr(cnt, field))
        assert got.tolist() == want, f"{mech}.{field}: {got.tolist()}"
    assert int(np.max(np.asarray(cnt.lat_sum_ns))) < dram.LAT_SUM_CAP


def test_lat_sum_cap_headroom():
    """cap + per-step bound == INT32_MAX: the pre-clamp add can never wrap
    (the arithmetic fact the auditor's clamp check relies on)."""
    assert dram.LAT_SUM_CAP + jaxpr_audit.T_MAX == (1 << 31) - 1
    cap = jnp.int32(dram.LAT_SUM_CAP)
    below = cap - jnp.int32(5)
    assert int(jnp.minimum(below + jnp.int32(4), cap)) == dram.LAT_SUM_CAP - 1
    assert int(jnp.minimum(below + jnp.int32(4096), cap)) == dram.LAT_SUM_CAP
