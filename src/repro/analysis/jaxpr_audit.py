"""Jaxpr auditor: abstract-trace the compiled entry points and walk them.

``jax.make_jaxpr`` over ShapeDtypeStruct arguments gives the exact program
XLA will see — no data, no device time — so every check here runs on the
*real* traced artifact, not on source text (the AST lint's job).  Four
checks per entry (DESIGN.md §12):

* **x64/weak-type creep** — any float64/int64/complex128 aval anywhere in
  the program is an error (the repo runs x64-disabled; a wide dtype means
  a host value leaked into the trace).  Weak *float* avals are flagged on
  entry outputs and scan carries only — weak scalars are ubiquitous and
  benign as intermediates, but a weak output or carry re-promotes on every
  downstream use.
* **int32 overflow on accumulated carries** — for the simulator scan, each
  int32 carry must be bounded for the declared trace-length ceiling
  (``TRACE_LEN_BOUND``).  Structural analysis derives per-step growth
  where it can (literal increments, bool->int converts, ``.at[].add``
  chains, saturating ``min``-clamps); ``CarryBound`` declarations supply
  what shape analysis cannot (e.g. a latency increment bounded only by
  simulated time).  An int32 carry that is neither derivable nor declared
  is itself an error: undeclared accumulators are how ``lat_sum_ns``-class
  overflows ship.
* **host callbacks / while_loops inside scan bodies** — a callback stalls
  the scan on the host every step; an unbounded ``while_loop`` defeats the
  static step-count the roadmap's whole-step Pallas scan requires.
* **oversized gather/scatter inside scan bodies** — a gather materializing
  more than ``GATHER_LIMIT`` elements per step is the signature of the
  dense formulation (whole-FTS per-step traffic) leaking into a fused
  path.

Entries are declared in ``ENTRIES`` — each names a public compiled entry
point, how to abstract-trace it, and the carry bounds contract for its
scan.  ``audit_all()`` is the pass the CLI and CI run.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import findings as F

INT32_MAX = (1 << 31) - 1

# Declared capacity contract: the largest request stream one simulator scan
# is promised to handle (the roadmap's cluster-sweep sizing; benchmarks use
# <= 2**16 today).  Carry bounds are checked against this, not against the
# representative trace length used for the abstract trace.
TRACE_LEN_BOUND = 1 << 20

# Declared simulated-time ceiling, ticks.  Workload generators emit arrival
# clocks < T_MAX and queue-drain times are bounded by it (contracts.py runs
# the generator contract; traces beyond this are out of contract).
T_MAX = 1 << 30

# A per-step gather/scatter materializing more elements than this inside a
# scan body indicates the dense formulation leaked into a fused path.
GATHER_LIMIT = 1 << 17

CHECKS = {
    "x64-leak": "float64/int64 aval in an x64-disabled program",
    "weak-type-leak": "weak float aval on an entry output or scan carry",
    "int32-overflow": "int32 scan carry can exceed 2**31-1 within the "
                      "declared trace-length bound",
    "undeclared-accumulator": "int32 scan carry with neither a derivable "
                              "step bound nor a CarryBound declaration",
    "callback-in-scan": "host callback inside a scan body",
    "while-in-scan": "while_loop inside a scan body",
    "oversized-gather": "per-step gather/scatter above the dense-fallback "
                        "threshold inside a scan body",
}


# ---------------------------------------------------------------------------
# carry-bound declarations

@dataclasses.dataclass(frozen=True)
class CarryBound:
    """Declared bound for one named scan carry.

    ``abs_max``: externally-justified absolute bound (time-like and
    id-space carries whose ceiling comes from the workload/geometry
    contract, not from per-step arithmetic).  ``step``: per-step growth
    bound used when structural derivation can't see one.  ``why`` is the
    reviewer-facing justification and is mandatory.
    """
    why: str
    abs_max: Optional[int] = None
    step: Optional[int] = None


_TIME = "bounded by the declared simulated-time ceiling T_MAX (workload "\
        "arrival clocks and queue-drain times stay under it by contract)"

# Bounds for the (BankState, Counters) carry of the simulator scan.  Keys
# are leaf names from the carry pytree (NamedTuple field names).
SIM_CARRY_BOUNDS: Dict[str, CarryBound] = {
    "open_row":  CarryBound("row-id space: n_rows + cache rows < 2**20",
                            abs_max=1 << 20),
    "busy":      CarryBound(_TIME, abs_max=T_MAX),
    "mshr_ring": CarryBound(_TIME, abs_max=T_MAX),
    "bus_free":  CarryBound(_TIME, abs_max=T_MAX),
    "t_end":     CarryBound(_TIME, abs_max=T_MAX),
    "mshr_idx":  CarryBound("ring cursor mod N_MSHR", abs_max=8),
    "tags":      CarryBound("segment-id space < 2**26", abs_max=1 << 26),
    "miss_tags": CarryBound("segment-id space < 2**26", abs_max=1 << 26),
    "benefit":   CarryBound("saturates at MechParams.benefit_max < 2**10",
                            abs_max=1 << 10),
    "last_use":  CarryBound("step stamp <= TRACE_LEN_BOUND",
                            abs_max=TRACE_LEN_BOUND + 1),
    "row_sum":   CarryBound("sum of <= max_segs benefits, each < 2**10",
                            abs_max=1 << 21),
    "miss_cnt":  CarryBound("consecutive-miss run <= TRACE_LEN_BOUND",
                            abs_max=TRACE_LEN_BOUND + 1),
    "evict_row": CarryBound("row-id space", abs_max=1 << 20),
    "n_valid":   CarryBound("valid count <= max_slots", abs_max=1 << 12),
    "free_list": CarryBound("slot index < max_slots", abs_max=1 << 12),
    # per-request latency includes queueing delay, so its only sound step
    # bound is simulated time itself; the accumulator must therefore clamp
    # (dram.LAT_SUM_CAP) and the structural check verifies that it does.
    "lat_sum_ns": CarryBound("per-step growth bounded by simulated time",
                             step=T_MAX),
    "reloc_blocks": CarryBound("per-step growth <= seg_blocks ceiling 256",
                               step=256),
    "wb_blocks": CarryBound("per-step growth <= seg_blocks ceiling 256",
                            step=256),
}

# The orchestrator's shard step (launch/orchestrator.py, DESIGN.md §14)
# wraps the simulator scan — same carry, same bounds — and adds two int32
# progress accumulators OUTSIDE the scan (per-segment host loop, one add per
# compiled step).  Their bounds are declared here so the contract is
# reviewable even though they never enter a scan carry:
#   seg_done  += 1 per segment           <= TRACE_LEN_BOUND segments
#   reqs_done += sum(real reqs in chunk) <= TRACE_LEN_BOUND * channels,
#               capped by the declared 2**27 stream-request ceiling.
ORCH_CARRY_BOUNDS: Dict[str, CarryBound] = {
    **SIM_CARRY_BOUNDS,
    "seg_done":  CarryBound("one increment per segment; segment count <= "
                            "TRACE_LEN_BOUND", abs_max=TRACE_LEN_BOUND),
    "reqs_done": CarryBound("real-request count across the shard's stream "
                            "< 2**27 by the sweep-plan contract",
                            abs_max=1 << 27),
}

# Telemetry extension of the segment carry (``dram._TelScan`` leaves,
# DESIGN.md §15; only ``StaticConfig.telemetry > 0`` programs carry them).
# The packed scalar lane reuses the ``lat_sum_ns`` saturation story: each
# per-step delta is bounded only by simulated time (the latency lanes), so
# the whole (11,) vector clamps at ``dram.LAT_SUM_CAP`` and the pre-clamp
# add stays within ``LAT_SUM_CAP + T_MAX == INT32_MAX`` on every segment.
# The ring-buffer rows hold copies of already-clamped window vectors, and
# the closed-window cursor ``n`` is bounded by the ring height W <= T + 2.
# §16 latency-distribution extension of the telemetry carry
# (``dram._TelScan.{hist, slo, buf_hist}`` + the packed window histogram
# lane).  Every histogram cell counts requests — one scatter-add of 0/1
# per serial step — so per-bucket counts are bounded by the scan capacity
# ``TRACE_LEN_BOUND``, never by simulated time; the same goes for the
# per-core over-SLO counts (at most one per request, compared exactly
# in-scan).  Ring rows are copies of the per-window histogram.
HIST_CARRY_BOUNDS: Dict[str, CarryBound] = {
    "hist_win": CarryBound(
        "per-window bucket counts: one request per serial step (resets "
        "each window, so <= TRACE_LEN_BOUND even unwindowed)", step=1),
    "hist": CarryBound(
        "cumulative per-(rw, core, bucket) request counts: +1 element "
        "per real request, <= TRACE_LEN_BOUND", step=1),
    "slo": CarryBound(
        "cumulative per-core over-SLO request count <= TRACE_LEN_BOUND",
        step=1),
    "buf_hist": CarryBound(
        "ring rows are copies of per-window bucket counts "
        "<= TRACE_LEN_BOUND", abs_max=TRACE_LEN_BOUND + 1),
}

TEL_CARRY_BOUNDS: Dict[str, CarryBound] = {
    **SIM_CARRY_BOUNDS,
    **HIST_CARRY_BOUNDS,
    "scalars": CarryBound(
        "per-window deltas bounded by window period x max issue width "
        "(one request per serial step); time lanes grow by at most "
        "simulated time per step and the vector clamps at dram.LAT_SUM_CAP",
        step=T_MAX),
    "bank_issues": CarryBound(
        "one request issued per serial scan step (resets each window, so "
        "<= TRACE_LEN_BOUND even unwindowed)", step=1),
    "buf_scalars": CarryBound(
        "ring rows are copies of the clamped window vector "
        "<= dram.LAT_SUM_CAP", abs_max=(1 << 30) - 1),
    "buf_banks": CarryBound(
        "ring rows are copies of per-window bank issue counts "
        "<= TRACE_LEN_BOUND", abs_max=TRACE_LEN_BOUND + 1),
    "n": CarryBound(
        "closed-window count <= ring height W <= T + 2 <= "
        "TRACE_LEN_BOUND + 2", abs_max=TRACE_LEN_BOUND + 2),
}


# ---------------------------------------------------------------------------
# jaxpr plumbing

def _subjaxprs(eqn):
    """(name, ClosedJaxpr-or-Jaxpr) pairs nested in one eqn's params."""
    for k, v in eqn.params.items():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for item in vs:
            if hasattr(item, "jaxpr") and hasattr(item, "consts"):
                yield k, item.jaxpr          # ClosedJaxpr
            elif hasattr(item, "eqns") and hasattr(item, "invars"):
                yield k, item                # raw Jaxpr


def _walk(jaxpr, path: str = "", scan_depth: int = 0):
    """Yield (eqn, path, scan_depth) over every eqn at every nesting level."""
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        here = f"{path}/{prim}" if path else prim
        yield eqn, here, scan_depth
        inner_depth = scan_depth + (1 if prim == "scan" else 0)
        for _k, sub in _subjaxprs(eqn):
            yield from _walk(sub, here, inner_depth)


def _aval_of(v):
    return getattr(v, "aval", None)


_WIDE = {"float64", "int64", "uint64", "complex128"}


def _is_literal(v) -> bool:
    return not hasattr(v, "count") and hasattr(v, "val")


# ---------------------------------------------------------------------------
# absolute-bound propagation (pure upper bounds, no carry relation)

_PASSTHROUGH = {"broadcast_in_dim", "reshape", "squeeze", "copy",
                "stop_gradient", "slice", "dynamic_slice", "gather",
                "expand_dims", "transpose"}


def _abs_bound(v, defs, depth: int = 0) -> Optional[int]:
    """Static upper bound for a (non-negative) integer value, or None."""
    if depth > 24:
        return None
    if _is_literal(v):
        try:
            return int(v.val)
        except (TypeError, ValueError):
            return None
    eqn = defs.get(v)
    if eqn is None:
        return None
    prim = eqn.primitive.name
    ops = eqn.invars
    if prim in _PASSTHROUGH:
        return _abs_bound(ops[0], defs, depth + 1)
    if prim == "convert_element_type":
        src = _aval_of(ops[0])
        if src is not None and str(src.dtype) == "bool":
            return 1
        return _abs_bound(ops[0], defs, depth + 1)
    if prim == "add":
        a = _abs_bound(ops[0], defs, depth + 1)
        b = _abs_bound(ops[1], defs, depth + 1)
        return None if a is None or b is None else a + b
    if prim == "mul":
        a = _abs_bound(ops[0], defs, depth + 1)
        b = _abs_bound(ops[1], defs, depth + 1)
        return None if a is None or b is None else a * b
    if prim in ("max",):
        a = _abs_bound(ops[0], defs, depth + 1)
        b = _abs_bound(ops[1], defs, depth + 1)
        return None if a is None or b is None else max(a, b)
    if prim in ("min",):
        known = [b for b in (_abs_bound(o, defs, depth + 1) for o in ops)
                 if b is not None]
        return min(known) if known else None
    if prim == "select_n":
        cases = [_abs_bound(o, defs, depth + 1) for o in ops[1:]]
        if any(c is None for c in cases):
            return None
        return max(cases)
    if prim == "rem":
        d = _abs_bound(ops[1], defs, depth + 1)
        return None if d is None else d - 1
    return None


# relative bound: value <= max(carry_in + growth, floor)
@dataclasses.dataclass(frozen=True)
class _Rel:
    rel: bool                 # references the carry slot?
    growth: Optional[int]     # per-step growth (None: unknown)
    floor: int                # absolute component


def _rel_bound(v, carry_in, defs, depth: int = 0) -> Optional[_Rel]:
    if depth > 24:
        return None
    if _is_literal(v):
        b = _abs_bound(v, defs)
        return None if b is None else _Rel(False, 0, b)
    if v is carry_in:
        return _Rel(True, 0, 0)
    eqn = defs.get(v)
    if eqn is None:                       # other invar (const / xs / carry)
        b = _abs_bound(v, defs)
        return None if b is None else _Rel(False, 0, b)
    prim = eqn.primitive.name
    ops = eqn.invars

    def sub(o):
        return _rel_bound(o, carry_in, defs, depth + 1)

    if prim in _PASSTHROUGH or prim == "convert_element_type":
        if prim == "convert_element_type":
            src = _aval_of(ops[0])
            if src is not None and str(src.dtype) == "bool":
                return _Rel(False, 0, 1)
        return sub(ops[0])
    if prim == "add":
        ra, rb = sub(ops[0]), sub(ops[1])
        if ra is None or rb is None:
            return None
        if ra.rel and rb.rel:
            return None                   # carry + carry: out of scope
        if rb.rel:
            ra, rb = rb, ra
        # ra may be rel: max(in+g, f) + f_b <= max(in+g+f_b, f+f_b)
        if rb.growth is None or ra.growth is None:
            g = None
        else:
            g = ra.growth + rb.floor if ra.rel else None
        if not ra.rel:                    # pure abs + pure abs
            return _Rel(False, 0, ra.floor + rb.floor)
        return _Rel(True, g, ra.floor + rb.floor)
    if prim in ("scatter-add", "scatter_add"):
        ro = sub(ops[0])
        if ro is None:
            return None
        upd = _abs_bound(ops[2], defs) if len(ops) >= 3 else None
        if not ro.rel:
            return None if upd is None else _Rel(False, 0, ro.floor + upd)
        g = None if (upd is None or ro.growth is None) else ro.growth + upd
        return _Rel(True, g, ro.floor + (upd or 0))
    if prim == "scatter":                 # .at[].set: replace, not grow
        ro = sub(ops[0])
        upd = _abs_bound(ops[2], defs) if len(ops) >= 3 else None
        if ro is None or upd is None:
            return None
        return _Rel(ro.rel, ro.growth if ro.rel else 0,
                    max(ro.floor, upd))
    if prim == "min":
        # saturating clamp: min(chain, K) caps the whole chain at K
        known = [b for b in (_abs_bound(o, defs) for o in ops)
                 if b is not None]
        if known:
            return _Rel(False, 0, min(known))
        return None
    if prim in ("max", "select_n"):
        cases = ops[1:] if prim == "select_n" else ops
        rels = [sub(o) for o in cases]
        if any(r is None for r in rels):
            return None
        rel = any(r.rel for r in rels)
        growths = [r.growth for r in rels if r.rel]
        g = None if any(x is None for x in growths) else \
            (max(growths) if growths else 0)
        return _Rel(rel, g if rel else 0, max(r.floor for r in rels))
    b = _abs_bound(v, defs)
    return None if b is None else _Rel(False, 0, b)


def _def_map(jaxpr) -> Dict:
    defs = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            defs[ov] = eqn
    return defs


# ---------------------------------------------------------------------------
# per-entry audit

@dataclasses.dataclass(frozen=True)
class Entry:
    """One audited entry point: how to trace it and its carry contract."""
    name: str
    trace: Callable[[], "jax.core.ClosedJaxpr"]
    carry_names: Tuple[str, ...] = ()        # flat names of the scan carry
    carry_bounds: Dict[str, CarryBound] = dataclasses.field(
        default_factory=dict)
    len_bound: int = TRACE_LEN_BOUND


def _leaf_name(path) -> str:
    """Last named component of a tree_flatten_with_path key path."""
    for k in reversed(path):
        name = getattr(k, "name", None)
        if name is not None:
            return str(name)
    return str(path[-1]) if path else "?"


def carry_leaf_names(carry_example) -> Tuple[str, ...]:
    leaves = jax.tree_util.tree_flatten_with_path(carry_example)[0]
    return tuple(_leaf_name(path) for path, _leaf in leaves)


def _audit_dtypes(closed, entry: str) -> List[F.Finding]:
    out = []
    seen_wide = set()
    for eqn, path, _d in _walk(closed.jaxpr, entry):
        for v in eqn.outvars:
            aval = _aval_of(v)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            dt = str(aval.dtype)
            if dt in _WIDE and (path, dt) not in seen_wide:
                seen_wide.add((path, dt))
                out.append(F.Finding(
                    rule="x64-leak", entry=entry,
                    message=f"{dt} value produced at {path}; the repo runs "
                            f"x64-disabled — a host int/float leaked into "
                            f"the trace"))
    for i, v in enumerate(closed.jaxpr.outvars):
        aval = _aval_of(v)
        if aval is not None and getattr(aval, "weak_type", False) \
                and "float" in str(getattr(aval, "dtype", "")):
            out.append(F.Finding(
                rule="weak-type-leak", entry=entry,
                message=f"output {i} is a weak {aval.dtype}; anchor it with "
                        f"an explicit dtype before returning"))
    return out


def _audit_scan_hygiene(closed, entry: str) -> List[F.Finding]:
    out = []
    for eqn, path, depth in _walk(closed.jaxpr, entry):
        prim = eqn.primitive.name
        if depth < 1:
            continue
        if "callback" in prim:
            out.append(F.Finding(
                rule="callback-in-scan", entry=entry,
                message=f"host callback `{prim}` at {path} runs once per "
                        f"scan step; hoist it out of the scanned region"))
        elif prim == "while":
            out.append(F.Finding(
                rule="while-in-scan", entry=entry,
                message=f"while_loop at {path} inside a scan body has no "
                        f"static trip count; use a bounded fori/scan"))
        elif prim in ("gather", "scatter", "scatter-add"):
            sizes = [int(getattr(_aval_of(v), "size", 0))
                     for v in list(eqn.outvars) + list(eqn.invars)
                     if _aval_of(v) is not None]
            biggest = max(sizes or [0])
            if biggest > GATHER_LIMIT:
                out.append(F.Finding(
                    rule="oversized-gather", entry=entry,
                    message=f"{prim} at {path} touches {biggest} elements "
                            f"per scan step (> {GATHER_LIMIT}); the dense "
                            f"formulation is leaking into a fused path"))
    return out


def _audit_carries(closed, entry: Entry) -> List[F.Finding]:
    out = []
    for eqn, path, _d in _walk(closed.jaxpr, entry.name):
        if eqn.primitive.name != "scan":
            continue
        body = eqn.params["jaxpr"].jaxpr
        nc = eqn.params["num_consts"]
        kc = eqn.params["num_carry"]
        if kc != len(entry.carry_names):
            continue                      # not the declared simulator carry
        defs = _def_map(body)
        for i, name in enumerate(entry.carry_names):
            in_v = body.invars[nc + i]
            out_v = body.outvars[i]
            aval = _aval_of(in_v)
            if aval is None or str(getattr(aval, "dtype", "")) != "int32":
                continue
            # weak-type check on carries (float carries only)
            decl = entry.carry_bounds.get(name)
            if decl is not None and decl.abs_max is not None:
                if decl.abs_max + (decl.step or 0) > INT32_MAX:
                    out.append(F.Finding(
                        rule="int32-overflow", entry=entry.name,
                        message=f"carry `{name}` declared abs bound "
                                f"{decl.abs_max} does not fit int32"))
                continue
            rel = None if _is_literal(out_v) else \
                _rel_bound(out_v, in_v, defs)
            if _is_literal(out_v):
                continue
            if rel is None:
                if decl is not None and decl.step is not None:
                    # structure opaque but a per-step growth is declared:
                    # worst-case accumulate from a zero base
                    rel = _Rel(True, decl.step, 0)
                else:
                    out.append(F.Finding(
                        rule="undeclared-accumulator", entry=entry.name,
                        message=f"carry `{name}` at {path}: cannot derive "
                                f"a step bound and no CarryBound is "
                                f"declared; declare one in jaxpr_audit "
                                f"(with a why) or restructure the update"))
                    continue
            if not rel.rel:
                # clamped/replaced: bound is the floor, plus one declared
                # step of pre-clamp headroom for the internal add
                slack = decl.step if decl is not None else 0
                if rel.floor + (slack or 0) > INT32_MAX:
                    out.append(F.Finding(
                        rule="int32-overflow", entry=entry.name,
                        message=f"carry `{name}` clamps at {rel.floor} but "
                                f"pre-clamp growth {slack} can wrap int32; "
                                f"lower the clamp"))
                continue
            growth = rel.growth
            if growth is None and decl is not None:
                growth = decl.step
            if growth is None:
                out.append(F.Finding(
                    rule="undeclared-accumulator", entry=entry.name,
                    message=f"carry `{name}` at {path} accumulates with an "
                            f"underivable per-step increment; declare a "
                            f"CarryBound(step=...) with a justification"))
                continue
            total = rel.floor + entry.len_bound * growth
            if total > INT32_MAX:
                out.append(F.Finding(
                    rule="int32-overflow", entry=entry.name,
                    message=f"carry `{name}` can reach ~{total:.3g} after "
                            f"{entry.len_bound} steps (step bound {growth})"
                            f" and wraps int32; clamp the accumulator "
                            f"(saturating min) or widen the contract"))
    return out


def audit_entry(entry: Entry) -> List[F.Finding]:
    # abstract tracing trips the repo's compile-count logs exactly like a
    # real compilation would; snapshot/restore so the audit never skews the
    # jit counters the contract pass (and tests) measure.
    from repro.core import dram, workload
    marks = (len(dram.JIT_TRACE_LOG), len(workload.GEN_TRACE_LOG))
    try:
        closed = entry.trace()
    except Exception as e:    # noqa: BLE001 - a broken entry IS a finding
        return [F.Finding(
            rule="x64-leak", entry=entry.name,
            message=f"entry failed to abstract-trace: {type(e).__name__}: "
                    f"{e}")]
    finally:
        del dram.JIT_TRACE_LOG[marks[0]:]
        del workload.GEN_TRACE_LOG[marks[1]:]
    out = _audit_dtypes(closed, entry.name)
    out += _audit_scan_hygiene(closed, entry.name)
    if entry.carry_names:
        out += _audit_carries(closed, entry)
    return out


# ---------------------------------------------------------------------------
# entry declarations for this repo

def _sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _abstract_trace(T: int, channels: int = 0):
    from repro.core.dram import Trace
    shp = (T,) if channels == 0 else (channels, T)
    fields = {}
    for fname, ftype in Trace.__annotations__.items():
        fields[fname] = _sds(shp, jnp.bool_ if "is_" in fname else jnp.int32)
    return Trace(**fields)


def _abstract_params(batch: int = 0):
    from repro.core.timing import MechParams
    shp = () if batch == 0 else (batch,)
    return MechParams(**{f: _sds(shp) for f in MechParams._fields})


def _sim_carry_names() -> Tuple[str, ...]:
    from repro.core import dram
    from repro.core.timing import paper_config
    static = paper_config("figcache_fast").static
    return carry_leaf_names((dram.init_state(static),
                             dram.init_counters()))


def _trace_run_sweep(channels: int = 0, dense: bool = False):
    """Abstract-trace ``run_sweep``'s body — ``simulate`` (or the dense
    reference ``simulate_dense``) vmapped over a params batch."""
    from repro.core import dram
    from repro.core.timing import paper_config
    static = paper_config("figcache_fast").static
    tr = _abstract_trace(256, channels)
    pb = _abstract_params(batch=4)
    fn = dram.simulate_dense if dense else dram.simulate
    return jax.make_jaxpr(
        lambda t, p: jax.vmap(lambda one: fn(t, static, one))(p))(tr, pb)


def _abstract_sim_state(static, channels: int = 0, batch: int = 0):
    from repro.core import dram
    st = dram.sim_init(static, channels=channels or None,
                       batch=batch or None)
    return jax.tree.map(lambda a: _sds(a.shape, a.dtype), st)


def _trace_run_segment(channels: int = 0, batch: int = 0, period: int = 0):
    """Abstract-trace the chunked segment step (``dram.run_segment`` /
    ``run_sweep_segment``, DESIGN.md §13), telemetry off (``period=0``)
    or on (DESIGN.md §15).

    Unlike ``run_sweep``, the ``SimState`` carry enters as an *input*: the
    scan resumes from whatever the previous segment left.  The declared
    ``SIM_CARRY_BOUNDS`` still apply because every bound is a per-segment
    *invariant* — an ``abs_max`` that holds on segment exit holds on the
    next segment's entry, and the ``lat_sum_ns`` saturation story composes
    across segments: the carried-in value is <= ``dram.LAT_SUM_CAP`` (the
    clamp is part of the step), so the pre-clamp add is bounded by
    ``LAT_SUM_CAP + T_MAX == INT32_MAX`` on EVERY segment, not just the
    first.  The carry audit checks exactly that (clamp floor + one
    declared step of pre-clamp headroom).  With telemetry on, the
    ``_TelScan`` extension is audited against ``TEL_CARRY_BOUNDS``: the
    packed scalar lane's clamp composes across segments exactly like
    ``lat_sum_ns``."""
    from repro.core import dram
    from repro.core.timing import paper_config
    static = dataclasses.replace(paper_config("figcache_fast"),
                                 telemetry=period).static
    tr = _abstract_trace(256, channels)
    st = _abstract_sim_state(static, channels, batch)
    if batch:
        pb = _abstract_params(batch=batch)
        return jax.make_jaxpr(
            lambda t, p, s: dram.sweep_resume(t, static, p, s))(tr, pb, st)
    p = _abstract_params()
    return jax.make_jaxpr(
        lambda t, pp, s: dram.resume(t, static, pp, s))(tr, p, st)


def _tel_carry_names() -> Tuple[str, ...]:
    """Flat leaf names of the telemetry segment carry: the simulator
    carry plus the ``dram._TelScan`` extension (derived from an actual
    pytree so a field rename cannot silently desynchronize the audit)."""
    from repro.core import dram
    tel = dram.init_telemetry()
    cur = dram._tel_pack(tel.win)
    scan = dram._TelScan(
        cur=cur,
        hist=tel.hist,
        slo=tel.slo,
        buf_scalars=jnp.zeros((1,) + cur.scalars.shape, jnp.int32),
        buf_banks=jnp.zeros((1,) + cur.bank_issues.shape, jnp.int32),
        buf_hist=jnp.zeros((1,) + cur.hist_win.shape, jnp.int32),
        n=jnp.int32(0))
    from repro.core.timing import paper_config
    static = paper_config("figcache_fast").static
    return carry_leaf_names((dram.init_state(static),
                             dram.init_counters(), scan))


def _trace_shard_step(channels: int = 2, batch: int = 4):
    """Abstract-trace the orchestrator's per-segment shard advance
    (``orchestrator.shard_step``: ``dram.sweep_resume`` + the two progress
    accumulators).  The embedded scan is the simulator carry, so
    ``SIM_CARRY_BOUNDS`` audit it; the accumulators sit outside the scan
    and are covered by the dtype checks plus the declared
    ``ORCH_CARRY_BOUNDS``."""
    from repro.core.timing import paper_config
    from repro.launch import orchestrator

    static = paper_config("figcache_fast").static
    tr = _abstract_trace(256, channels)
    pb = _abstract_params(batch=batch)
    prog = jax.eval_shape(
        lambda: orchestrator.init_progress(static, batch, channels))
    return jax.make_jaxpr(
        lambda t, p, s: orchestrator.shard_step(t, static, p, s))(tr, pb,
                                                                  prog)


def _workload_entry():
    """Trace the program ``workload.generate``/``generate_many`` compile:
    the un-jitted generator of one representative static structure."""
    from repro.core.timing import GEOM
    from repro.core.workload import preset
    from repro.core.workload.generators import _make_gen

    spec = preset("zipf_reuse", n_cores=2, n_channels=1, per_channel=1024)
    gen = _make_gen(spec.family, spec.n_cores, spec.n_channels,
                    spec.per_channel, GEOM)
    return jax.make_jaxpr(gen)(spec.params(), jnp.int32(0))


def _kernel_entry(which: str):
    from repro.kernels.figaro_reloc.ops import reloc_segments
    from repro.kernels.figcache_decode.ops import decode_attend
    from repro.kernels.flash_attention.ops import mha
    from repro.kernels.fts_lookup.ops import fts_lookup_op
    f32 = jnp.float32
    if which == "fts_lookup":
        return jax.make_jaxpr(functools.partial(
            fts_lookup_op, interpret=True))(
            _sds((16, 512)), _sds((16, 512)), _sds(()), _sds(()), _sds(()))
    if which == "reloc":
        return jax.make_jaxpr(functools.partial(
            reloc_segments, interpret=True))(
            _sds((64, 128), f32), _sds((32, 128), f32),
            _sds((8,)), _sds((8,)))
    if which == "decode":
        return jax.make_jaxpr(functools.partial(
            decode_attend, interpret=True))(
            _sds((2, 1, 4, 64), f32), _sds((2, 128, 4, 64), f32),
            _sds((2, 128, 4, 64), f32), _sds((2, 128), jnp.bool_))
    if which == "mha":
        return jax.make_jaxpr(functools.partial(mha, interpret=True))(
            _sds((2, 256, 4, 64), f32), _sds((2, 256, 4, 64), f32),
            _sds((2, 256, 4, 64), f32))
    raise ValueError(which)


def default_entries() -> List[Entry]:
    names = _sim_carry_names()
    tel_names = _tel_carry_names()
    return [
        Entry("dram.run_sweep",
              lambda: _trace_run_sweep(),
              carry_names=names, carry_bounds=SIM_CARRY_BOUNDS),
        Entry("dram.run_sweep[dense]",
              lambda: _trace_run_sweep(dense=True),
              carry_names=names, carry_bounds=SIM_CARRY_BOUNDS),
        Entry("simulator.sweep_traces[multi-channel]",
              lambda: _trace_run_sweep(channels=2),
              carry_names=names, carry_bounds=SIM_CARRY_BOUNDS),
        Entry("dram.run_segment",
              lambda: _trace_run_segment(),
              carry_names=names, carry_bounds=SIM_CARRY_BOUNDS),
        Entry("dram.run_sweep_segment[multi-channel]",
              lambda: _trace_run_segment(channels=2, batch=4),
              carry_names=names, carry_bounds=SIM_CARRY_BOUNDS),
        Entry("dram.run_segment[telemetry]",
              lambda: _trace_run_segment(period=64),
              carry_names=tel_names, carry_bounds=TEL_CARRY_BOUNDS),
        Entry("dram.run_sweep_segment[telemetry, multi-channel]",
              lambda: _trace_run_segment(channels=2, batch=4, period=64),
              carry_names=tel_names, carry_bounds=TEL_CARRY_BOUNDS),
        Entry("orchestrator.shard_step[sharded]",
              lambda: _trace_shard_step(channels=2, batch=4),
              carry_names=names, carry_bounds=ORCH_CARRY_BOUNDS),
        Entry("workload.generate_many", _workload_entry),
        Entry("kernels.fts_lookup_op",
              lambda: _kernel_entry("fts_lookup")),
        Entry("kernels.reloc_segments", lambda: _kernel_entry("reloc")),
        Entry("kernels.decode_attend", lambda: _kernel_entry("decode")),
        Entry("kernels.mha", lambda: _kernel_entry("mha")),
    ]


def audit_all(entries: Optional[List[Entry]] = None) -> F.Report:
    rep = F.Report(passes=["jaxpr-audit"])
    for entry in (entries if entries is not None else default_entries()):
        rep.scanned.append(entry.name)
        rep.extend(audit_entry(entry))
    return rep
