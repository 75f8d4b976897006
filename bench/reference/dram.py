"""Plain reference of the DRAM timing model and the FIGCache tag store.

One channel, one request at a time, in plain Python integers: the model of
arXiv:2009.08437 sec. 7-8 as the configuration files state it.  Per bank:
the open row, the time the bank is busy until, and (for the caching
mechanisms) a fully associative tag store of ``cache_rows`` rows of
``128 / seg_blocks`` segments, filled on every miss, evicting by RowBenefit
(the cache row with the lowest summed benefit, then that row's segments
lowest-benefit first).  Per core: a ring of the completion times of its
last 8 requests (a core stalls until the request 8 ago has completed).
Per channel: the data bus, which serializes every 64 B burst.

It imports nothing of the program.  ``break_bus`` drops the data-bus
serialization: the control that has to come out as not correct.
"""
from __future__ import annotations

NOOP_ISSUE = 1 << 30
LAT_SUM_CAP = (1 << 30) - 1
COUNTERS = ("acts_slow", "acts_fast", "reads", "writes", "reloc_blocks",
            "wb_blocks", "row_hits", "cache_hits", "insertions",
            "lat_sum_ns", "req_cnt", "t_end")


def ticks(ns: float) -> int:
    return int(round(ns * 8))


class Mech:
    """One configuration's mechanism, with its numbers in ticks."""

    def __init__(self, cfg: dict, system: dict):
        d, g, cache = system["dram"], system["geometry"], system["cache"]
        if cache["policy"] != "row_benefit" or cache["insert_threshold"] != 1:
            raise ValueError("the reference models RowBenefit eviction "
                             "with insertion on every miss only")
        self.name = cfg["mechanism"]
        lisa = self.name == "lisa_villa"
        self.seg_blocks = cfg.get(
            "seg_blocks", g["row_blocks"] if lisa else cache["seg_blocks"])
        self.cache_rows = cfg.get(
            "cache_rows", 512 if lisa else cache["cache_rows"])
        self.has_cache = self.name in ("lisa_villa", "figcache_slow",
                                       "figcache_fast", "figcache_ideal")
        self.fast_cache = self.name in ("lisa_villa", "figcache_fast",
                                        "figcache_ideal")
        self.spr = g["row_blocks"] // self.seg_blocks
        self.n_rows = g["n_rows"]
        self.n_banks = g["n_banks"]
        self.n_cores = g["n_cores"]
        self.rows_per_sub = g["rows_per_subarray"]
        self.n_mshr = system["n_mshr"]
        self.benefit_max = (1 << system["benefit_bits"]) - 1
        self.rcd, self.rp = ticks(d["tRCD"]), ticks(d["tRP"])
        self.cas, self.bl, self.ccd = (ticks(d["tCAS"]), ticks(d["tBL"]),
                                       ticks(d["tCCD"]))
        self.rcd_fast = ticks(d["tRCD"] * d["fast_tRCD_scale"])
        self.rp_fast = ticks(d["tRP"] * d["fast_tRP_scale"])
        self.reloc = ticks(d["tRELOC"])
        self.hop = ticks(d["tLISA_HOP"])


class TagStore:
    """One bank's cache: slots fill lowest index first and never empty."""

    def __init__(self, n_rows: int, spr: int):
        self.spr = spr
        self.n_slots = n_rows * spr
        self.n_rows = n_rows
        self.where = {}                    # segment -> slot
        self.tag, self.dirty, self.benefit = [], [], []
        self.row_sum = [0] * n_rows
        self.evict_row, self.evict_left = -1, []

    def victim(self) -> int:
        """RowBenefit: keep evicting the marked row's segments, lowest
        benefit first (lowest index on ties); pick a new row (lowest summed
        benefit, lowest index on ties) once all its segments went."""
        if self.evict_row < 0 or not self.evict_left:
            rs = self.row_sum
            self.evict_row = rs.index(min(rs))
            self.evict_left = list(range(self.spr))
        base = self.evict_row * self.spr
        b = self.benefit
        j = min(self.evict_left, key=lambda j: (b[base + j], j))
        self.evict_left.remove(j)
        return base + j


def _lisa_hops(row: int, rows_per_sub: int) -> int:
    m = (row // rows_per_sub) % 4
    return min(m, 4 - m)


def simulate_channel(m: Mech, t_issue, bank, row, col, is_write, core,
                     break_bus: bool = False) -> dict:
    """Counters of one channel's requests, served in the given order."""
    open_row = [-1] * m.n_banks
    busy = [0] * m.n_banks
    ring = [[0] * m.n_mshr for _ in range(m.n_cores)]
    ring_at = [0] * m.n_cores
    bus = 0
    stores = [TagStore(m.cache_rows, m.spr) for _ in range(m.n_banks)] \
        if m.has_cache else None
    c = dict.fromkeys(COUNTERS, 0)
    lat_sum = [0] * m.n_cores
    req_cnt = [0] * m.n_cores
    slow_cache = m.name == "figcache_slow"
    reserved = m.n_rows // m.rows_per_sub - 1
    for t, b, r, k, w, cr in zip(t_issue, bank, row, col, is_write, core):
        if t >= NOOP_ISSUE:
            continue
        hit = False
        target = r
        ins = False
        cost = moved = wb = 0
        if m.has_cache:
            st = stores[b]
            seg = r * m.spr + k // m.seg_blocks
            cacheable = not (slow_cache and r // m.rows_per_sub == reserved)
            slot = st.where.get(seg) if cacheable else None
            if slot is not None:
                hit = True
                target = m.n_rows + slot // m.spr
                b0 = st.benefit[slot]
                b1 = min(b0 + 1, m.benefit_max)
                st.benefit[slot] = b1
                st.row_sum[slot // m.spr] += b1 - b0
                st.dirty[slot] = st.dirty[slot] or w
            elif cacheable:
                ins = True
                ev_dirty, ev_tag = False, 0
                if len(st.tag) < st.n_slots:
                    slot = len(st.tag)
                    st.tag.append(seg)
                    st.dirty.append(w)
                    st.benefit.append(1)
                    st.row_sum[slot // m.spr] += 1
                else:
                    slot = st.victim()
                    ev_tag, ev_dirty = st.tag[slot], st.dirty[slot]
                    del st.where[ev_tag]
                    st.row_sum[slot // m.spr] += 1 - st.benefit[slot]
                    st.tag[slot], st.dirty[slot] = seg, w
                    st.benefit[slot] = 1
                st.where[seg] = slot
                if m.name == "figcache_ideal":
                    cost = 0
                elif m.name == "lisa_villa":
                    cost = _lisa_hops(r, m.rows_per_sub) * m.hop + m.rcd_fast
                    if ev_dirty:
                        cost += _lisa_hops(ev_tag, m.rows_per_sub) * m.hop \
                            + m.rcd
                else:
                    cost = m.seg_blocks * m.reloc
                    if ev_dirty:
                        cost += m.seg_blocks * m.reloc + m.rcd
                moved = m.seg_blocks
                wb = m.seg_blocks if ev_dirty else 0
        fast = (hit and m.fast_cache) or m.name == "lldram"
        o = open_row[b]
        row_hit = o == target
        if row_hit:
            pre = 0
        else:
            pre = (m.rcd_fast if fast else m.rcd) + (
                0 if o < 0 else (m.rp_fast if fast else m.rp))
        i = ring_at[cr]
        t_ready = max(t, ring[cr][i])
        t0 = max(t_ready, busy[b])
        done = t0 + pre + m.cas
        if not break_bus:
            done = max(done, bus)
        done += m.bl
        end = t0 + pre + m.ccd
        open_row[b] = m.n_rows + slot // m.spr if ins else target
        busy[b] = end + cost
        ring[cr][i] = done
        ring_at[cr] = (i + 1) % m.n_mshr
        bus = done
        if not row_hit:
            c["acts_fast" if fast else "acts_slow"] += 1
        c["writes" if w else "reads"] += 1
        c["reloc_blocks"] += moved
        c["wb_blocks"] += wb
        c["row_hits"] += row_hit
        c["cache_hits"] += hit
        c["insertions"] += ins
        lat_sum[cr] = min(lat_sum[cr] + (done - t_ready) // 8, LAT_SUM_CAP)
        req_cnt[cr] += 1
        c["t_end"] = max(c["t_end"], done, end + cost)
    c["lat_sum_ns"], c["req_cnt"] = lat_sum, req_cnt
    return c
