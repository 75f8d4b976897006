"""Plain reference of the campaign's derived numbers (arXiv:2009.08437 sec. 7).

Per-core IPC from the simulated latency by the MLP-weighted CPI model,
execution time as the slowest core's, and DRAM and system energy from the
counters, with the constants the repository's model states.  Imports
nothing of the program.
"""
from __future__ import annotations

CPU_GHZ = 3.2
CPI_EXEC = 0.4
MLP_INTENSIVE, MLP_NON = 2.2, 1.4
INTENSIVE = ("zeusmp", "leslie3d", "mcf", "GemsFDTD", "libquantum",
             "bwaves", "lbm", "com", "tigr", "mum")
E_ACT_PRE, E_ACT_PRE_FAST, E_RD, E_WR, E_RELOC = 13.5, 8.0, 12.0, 13.0, 1.0
P_BG, E_CPU_INSTR, P_CPU_STATIC, E_OFFCHIP = 0.40, 0.60, 2.5, 2.0

NUMBERS = ("ipc", "avg_lat_ns", "row_hit_rate", "cache_hit_rate",
           "exec_time_ns", "dram_energy_nj", "system_energy_nj")


def results(channels, cores, has_cache: bool) -> dict:
    """Derived numbers of one mix under one configuration, from the
    counters of each of its channels."""
    n_ch = len(channels)
    tot = lambda k: float(sum(c[k] for c in channels))
    per_core = lambda k: [float(sum(c[k][j] for c in channels))
                          for j in range(len(channels[0][k]))]
    lat, req = per_core("lat_sum_ns"), per_core("req_cnt")
    avg = [l / r if r > 0 else 0.0 for l, r in zip(lat, req)]
    ipc, busy, instr_tot = [], [], 0.0
    for j, core in enumerate(cores):
        r = req[j]
        mlp = MLP_INTENSIVE if core["name"] in INTENSIVE else MLP_NON
        instr = r * 1000.0 / core["mpki"]
        cycles = instr * CPI_EXEC + r * (avg[j] * CPU_GHZ) / mlp
        ipc.append(instr / cycles if r > 0 else 1.0 / CPI_EXEC)
        busy.append(cycles / CPU_GHZ if r > 0 else 0.0)
        instr_tot += instr
    exec_ns = max(busy)
    reqs = tot("reads") + tot("writes")
    dyn = (tot("acts_slow") * E_ACT_PRE + tot("acts_fast") * E_ACT_PRE_FAST
           + tot("insertions") * E_ACT_PRE_FAST + tot("reads") * E_RD
           + tot("writes") * E_WR
           + (tot("reloc_blocks") + tot("wb_blocks")) * E_RELOC)
    dram = dyn + exec_ns * P_BG * n_ch
    cpu = instr_tot * E_CPU_INSTR + exec_ns * P_CPU_STATIC * len(cores)
    div = reqs if reqs else 1.0
    return {"ipc": ipc, "avg_lat_ns": avg[:len(cores)],
            "row_hit_rate": tot("row_hits") / div,
            "cache_hit_rate": tot("cache_hits") / div if has_cache else 0.0,
            "exec_time_ns": exec_ns, "dram_energy_nj": dram,
            "system_energy_nj": dram + cpu + reqs * E_OFFCHIP}
