"""DRAM timing + geometry constants (paper Table 1 / §4.2).

All latencies are stored in integer *ticks* of 1/8 ns so the jitted simulator
runs on exact int32 arithmetic (float32 timestamps lose precision past ~16 ms).

A ``MechConfig`` (one evaluated system point) splits into two halves
(DESIGN.md §3):

 * ``StaticConfig`` — mechanism kind, replacement policy, and the *padded*
   FTS allocation (``max_slots``, ``max_segs_per_row``).  These set array
   *shapes* and trace-time branches, so they are jit static arguments: one
   compilation per distinct ``StaticConfig``.
 * ``MechParams`` — every remaining knob (timings in ticks, ``seg_blocks``,
   ``insert_threshold``, ``benefit_max``, and the *effective* FTS geometry
   ``n_slots``/``segs_per_row``) as an int32 pytree that is passed *traced*
   into the compiled scan, so configs differing only in params — including
   cache capacity and segment size — share one compilation and can be
   ``jax.vmap``-ed as a stacked batch (``core/dram.py:run_sweep``).

The padded maxima are bucketed (``DEFAULT_MAX_SLOTS`` etc., covering every
paper grid) so that whole capacity/segment-size sweeps collapse onto ONE
``StaticConfig``; exotic oversized configs round up to the next power of
two and get their own structure.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

TICKS_PER_NS = 8


def ns(x: float) -> int:
    return int(round(x * TICKS_PER_NS))


@dataclasses.dataclass(frozen=True)
class DRAMTimings:
    """DDR4-1600 (800 MHz bus) timings, ns — paper Table 1."""
    tCK: float = 1.25
    tRCD: float = 13.75
    tRP: float = 13.75
    tRAS: float = 35.0
    tCAS: float = 13.75
    tBL: float = 5.0          # 8-beat burst @ 1.6 GT/s
    tCCD: float = 6.25
    tRELOC: float = 1.0       # §4.2: 0.57 ns SPICE + 43 % guardband -> 1 ns
    # Fast-subarray reductions (LISA-VILLA SPICE model, §7)
    fast_tRCD_scale: float = 1.0 - 0.455
    fast_tRP_scale: float = 1.0 - 0.382
    fast_tRAS_scale: float = 1.0 - 0.629
    # LISA inter-subarray hop (row-buffer movement between adjacent subarrays)
    tLISA_HOP: float = 10.0

    # -- tick helpers ------------------------------------------------------
    @property
    def rcd(self): return ns(self.tRCD)
    @property
    def rp(self): return ns(self.tRP)
    @property
    def ras(self): return ns(self.tRAS)
    @property
    def cas(self): return ns(self.tCAS)
    @property
    def bl(self): return ns(self.tBL)
    @property
    def ccd(self): return ns(self.tCCD)
    @property
    def reloc(self): return ns(self.tRELOC)
    @property
    def rcd_fast(self): return ns(self.tRCD * self.fast_tRCD_scale)
    @property
    def rp_fast(self): return ns(self.tRP * self.fast_tRP_scale)
    @property
    def ras_fast(self): return ns(self.tRAS * self.fast_tRAS_scale)
    @property
    def lisa_hop(self): return ns(self.tLISA_HOP)

    def full_reloc_ns(self) -> float:
        """One isolated column relocation: ACT(src,tRAS) + RELOC + ACT(dst,
        counted as tRCD) + PRE (tRP).  Paper §4.2: 63.5 ns."""
        return self.tRAS + self.tRELOC + self.tRCD + self.tRP


DDR4 = DRAMTimings()


@dataclasses.dataclass(frozen=True)
class DRAMGeometry:
    """Per-channel geometry — paper Table 1 (4 GB/channel)."""
    n_banks: int = 16              # 4 bank groups x 4 banks
    n_rows: int = 32768            # per bank -> 16 * 32768 * 8 kB = 4 GB
    row_blocks: int = 128          # 8 kB row / 64 B cache block
    rows_per_subarray: int = 512   # -> 64 subarrays per bank
    n_cores: int = 8

    @property
    def n_subarrays(self) -> int:
        return self.n_rows // self.rows_per_subarray


GEOM = DRAMGeometry()


MECHANISMS = ("base", "lisa_villa", "figcache_slow", "figcache_fast",
              "figcache_ideal", "lldram")


@dataclasses.dataclass(frozen=True)
class SchedConfig:
    """Memory-controller scheduling discipline (DESIGN.md §10).

    The paper evaluates FIGCache under an FR-FCFS controller (§7); the seed
    harness had none ("the trace order is the schedule").  A ``SchedConfig``
    names a controller: ``core/sched/policies.py`` realizes it as a
    *trace-preprocessing* pass (a per-channel service-order permutation) that
    runs on the device before the compiled scan, so the scheduling knobs never
    enter the scan and a policy grid reuses ONE compilation — the scheduled
    traces all share the original trace's shape.  It lives here next to
    ``StaticConfig`` / ``MechParams`` because it is the third leg of a
    ``MechConfig``: hashable, tiny, and a grouping key of
    ``simulator.sweep`` (configs differing only in ``sched`` replay
    differently-ordered copies of the same trace through the same scan).

    Knobs:
      * ``policy`` — ``"fcfs"`` (service = arrival order, the seed
        behavior) or ``"frfcfs"`` (row-hit-first within the transaction
        queue window, the paper's §7 controller).
      * ``queue_depth`` — the controller's lookahead window: only the next
        ``queue_depth`` pending requests are candidates for reordering.
      * ``starve_cap`` — FR-FCFS fairness: after the oldest pending request
        has been bypassed by ``starve_cap`` row hits it is scheduled
        unconditionally.  ``starve_cap=0`` degenerates to FCFS.
      * ``arrival_window_ns`` — the queue holds *arrived* requests: a
        request may bypass the oldest pending one only if it was issued
        within this many ns of it.  Without the bound a request-count
        window would let the scheduler see arbitrarily far into the
        issue-future and starve present requests behind it; the default
        is service-latency scale (~tRC), i.e. "arrived while the oldest
        request is being served".
      * ``write_drain`` / ``drain_batch`` — posted writes: writes are held
        in a write queue while reads proceed, and the queue drains as a
        batch (sorted by (bank, row) for row-buffer locality) once it
        reaches ``drain_batch`` entries (§7's write-drain batching).
    """
    policy: str = "fcfs"
    queue_depth: int = 32
    starve_cap: int = 16
    arrival_window_ns: int = 50
    write_drain: bool = False
    drain_batch: int = 16

    def __post_init__(self):
        assert self.policy in ("fcfs", "frfcfs"), self.policy
        assert self.queue_depth >= 1 and self.starve_cap >= 0
        assert self.arrival_window_ns >= 0 and self.drain_batch >= 1

    @property
    def is_identity(self) -> bool:
        """True when scheduling cannot change the service order (the
        fast path: ``sched.schedule`` returns the trace untouched)."""
        return self.policy == "fcfs" and not self.write_drain


SCHED_FCFS = SchedConfig()


# Padded FTS allocation buckets (DESIGN.md §3/§9).  A two-rung ladder:
#   SMALL_*  — covers every default §8 configuration (512 slots = 64 cache
#              rows x 8 segs; lisa_villa's 512 rows x 1 seg; spr <= 8), so
#              single-config runs do not pay 1024-wide reductions for a
#              512-slot config;
#   DEFAULT_* — the sweep-grid ceiling: seg_blocks=8 -> 64 x 16 = 1024
#              slots, segs_per_row up to 128 // 8 = 16 (fig 13's grid).
# ``shared_static`` buckets a whole config GRID to one shared structure
# (the tightest rung covering its maximum), which is what keeps capacity
# (fig 12) and segment-size (fig 13) sweeps compiling exactly once; configs
# that exceed a bucket round up to the next power of two and get their own
# static structure.
SMALL_MAX_SLOTS = 512
SMALL_MAX_SEGS_PER_ROW = 8
DEFAULT_MAX_SLOTS = 1024
DEFAULT_MAX_SEGS_PER_ROW = 16


def _pad_bucket(n: int, floor: int) -> int:
    if n <= floor:
        return floor
    p = floor
    while p < n:
        p <<= 1
    return p


@dataclasses.dataclass(frozen=True)
class StaticConfig:
    """The shape-/branch-determining half of a ``MechConfig``.

    Hashable and tiny: used as a jit static argument and as the grouping key
    of ``simulator.sweep``.  Two configs with equal ``StaticConfig`` share one
    compiled scan.  ``max_slots``/``max_segs_per_row`` are the *padded* FTS
    allocation (the effective ``n_slots``/``segs_per_row`` travel traced in
    ``MechParams``); both are normalized to 1 for cache-less mechanisms so
    the FTS arrays collapse to placeholders.
    """
    mechanism: str
    max_slots: int
    max_segs_per_row: int
    policy: str
    # route the tag compare + victim argmin through the fused Pallas
    # ``kernels/fts_lookup`` op (DESIGN.md §9); a trace-time branch, so it
    # lives in the static half.  Off-TPU it falls back to the pure-JAX ref.
    fts_kernel: bool = False
    # in-scan telemetry window period in REAL requests (DESIGN.md §15);
    # 0 disables.  Static because enabling it extends the scan carry with
    # the ``dram.TelemetryWindows`` accumulators and adds per-step frame
    # outputs — a different program structure.  Disabled (the default) is
    # bitwise-identical to the pre-telemetry scan.
    telemetry: int = 0

    @property
    def has_cache(self) -> bool:
        return self.mechanism in ("lisa_villa", "figcache_slow",
                                  "figcache_fast", "figcache_ideal")

    @property
    def fast_cache(self) -> bool:
        return self.mechanism in ("lisa_villa", "figcache_fast",
                                  "figcache_ideal")

    @property
    def free_reloc(self) -> bool:
        return self.mechanism == "figcache_ideal"


class MechParams(NamedTuple):
    """Dynamic (traced) half of a ``MechConfig``: int32 scalars, stackable.

    Leaves carry DRAM timings in ticks plus the mechanism knobs — including
    the *effective* FTS geometry ``n_slots``/``segs_per_row``, which only
    select the live prefix of the padded arrays (``StaticConfig.max_slots``)
    and therefore need not be jit-static.  A batch of ``MechParams`` with a
    leading axis is what ``dram.run_sweep`` vmaps over.
    """
    rcd: jax.Array
    rp: jax.Array
    cas: jax.Array
    bl: jax.Array
    ccd: jax.Array
    rcd_fast: jax.Array
    rp_fast: jax.Array
    reloc: jax.Array
    lisa_hop: jax.Array
    seg_blocks: jax.Array
    insert_threshold: jax.Array
    benefit_max: jax.Array
    n_slots: jax.Array
    segs_per_row: jax.Array
    # per-request latency SLO threshold in ns (<= 0 disables the in-scan
    # over-SLO count; DESIGN.md §16).  Traced so an SLO grid batches
    # through one compiled scan; telemetry-off programs never read it.
    slo_ns: jax.Array


@dataclasses.dataclass(frozen=True)
class MechConfig:
    """One evaluated system configuration (paper §8)."""
    mechanism: str = "figcache_fast"
    seg_blocks: int = 16           # row segment = 16 blocks = 1/8 row
    cache_rows: int = 64           # rows in the in-DRAM cache region (per bank)
    policy: str = "row_benefit"    # row_benefit|segment_benefit|lru|random
    insert_threshold: int = 1      # consecutive misses before insertion
    benefit_bits: int = 5
    fts_kernel: bool = False       # fuse lookup+victim via kernels/fts_lookup
    telemetry: int = 0             # in-scan window period in real requests;
                                   # 0 = off (DESIGN.md §15)
    slo_ns: int = 0                # per-request latency SLO threshold (ns);
                                   # <= 0 = no over-SLO accounting (§16).
                                   # Traced (rides MechParams), only read by
                                   # telemetry-enabled scans.
    # which memory controller serves the trace (DESIGN.md §10): a host-side
    # trace-preprocessing knob — it never enters the compiled scan, so any
    # sched grid shares the scan compilations of its mech/policy grid
    sched: SchedConfig = SCHED_FCFS

    def __post_init__(self):
        assert self.mechanism in MECHANISMS, self.mechanism

    @property
    def has_cache(self) -> bool:
        return self.mechanism in ("lisa_villa", "figcache_slow",
                                  "figcache_fast", "figcache_ideal")

    @property
    def fast_cache(self) -> bool:
        """Cache rows live in fast subarrays (reduced timings)?"""
        return self.mechanism in ("lisa_villa", "figcache_fast",
                                  "figcache_ideal")

    @property
    def segs_per_row(self) -> int:
        return GEOM.row_blocks // self.seg_blocks

    @property
    def n_slots(self) -> int:
        return self.cache_rows * self.segs_per_row

    @property
    def free_reloc(self) -> bool:
        return self.mechanism == "figcache_ideal"

    @property
    def static(self) -> StaticConfig:
        """Padded static structure for a config evaluated ON ITS OWN: the
        tightest bucket rung covering this config (a default 512-slot
        config no longer pays the 1024-slot sweep ceiling).  Grids that mix
        shapes must share one structure via ``shared_static``."""
        if not self.has_cache:
            return StaticConfig(self.mechanism, 1, 1, self.policy,
                                self.fts_kernel, self.telemetry)
        return StaticConfig(
            mechanism=self.mechanism,
            max_slots=_pad_bucket(self.n_slots, SMALL_MAX_SLOTS),
            max_segs_per_row=_pad_bucket(self.segs_per_row,
                                         SMALL_MAX_SEGS_PER_ROW),
            policy=self.policy,
            fts_kernel=self.fts_kernel,
            telemetry=self.telemetry,
        )

    @property
    def exact_static(self) -> StaticConfig:
        """Unpadded static structure (``max == actual``): the per-config
        reference that benchmarks/tests compare the padded path against."""
        return StaticConfig(
            mechanism=self.mechanism,
            max_slots=self.n_slots if self.has_cache else 1,
            max_segs_per_row=self.segs_per_row if self.has_cache else 1,
            policy=self.policy,
            fts_kernel=self.fts_kernel,
            telemetry=self.telemetry,
        )

    def params(self, t: DRAMTimings = DDR4) -> MechParams:
        i32 = jnp.int32
        return MechParams(
            rcd=i32(t.rcd), rp=i32(t.rp), cas=i32(t.cas), bl=i32(t.bl),
            ccd=i32(t.ccd), rcd_fast=i32(t.rcd_fast), rp_fast=i32(t.rp_fast),
            reloc=i32(t.reloc), lisa_hop=i32(t.lisa_hop),
            seg_blocks=i32(self.seg_blocks),
            insert_threshold=i32(self.insert_threshold),
            benefit_max=i32((1 << self.benefit_bits) - 1),
            n_slots=i32(self.n_slots if self.has_cache else 1),
            segs_per_row=i32(self.segs_per_row if self.has_cache else 1),
            slo_ns=i32(self.slo_ns),
        )


def static_group_key(cfg: MechConfig):
    """The non-shape half of a static structure.  Configs sharing this key
    can always share ONE compiled scan via ``shared_static`` — capacity and
    segment-size variation never splits a group."""
    return (cfg.mechanism, cfg.policy, cfg.fts_kernel, cfg.has_cache,
            cfg.telemetry)


def shared_static(cfgs) -> StaticConfig:
    """One static structure covering a whole config grid: the tightest
    bucket rung holding the grid's maximum ``n_slots`` / ``segs_per_row``.
    All configs must agree on ``static_group_key`` (mechanism / policy /
    fts_kernel) — that is the grouping ``simulator.sweep`` performs."""
    cfgs = list(cfgs)
    key = static_group_key(cfgs[0])
    assert all(static_group_key(c) == key for c in cfgs), \
        "a shared static needs one mechanism/policy/fts_kernel"
    c0 = cfgs[0]
    if not c0.has_cache:
        return StaticConfig(c0.mechanism, 1, 1, c0.policy, c0.fts_kernel,
                            c0.telemetry)
    return StaticConfig(
        mechanism=c0.mechanism,
        max_slots=_pad_bucket(max(c.n_slots for c in cfgs),
                              SMALL_MAX_SLOTS),
        max_segs_per_row=_pad_bucket(max(c.segs_per_row for c in cfgs),
                                     SMALL_MAX_SEGS_PER_ROW),
        policy=c0.policy,
        fts_kernel=c0.fts_kernel,
        telemetry=c0.telemetry,
    )


def paper_config(mechanism: str, **kw) -> MechConfig:
    """The exact §8 configurations."""
    if mechanism == "lisa_villa":
        # whole-row caching, 512 cache rows (16 fast subarrays x 32 rows)
        kw.setdefault("seg_blocks", GEOM.row_blocks)
        kw.setdefault("cache_rows", 512)
    return MechConfig(mechanism=mechanism, **kw)
