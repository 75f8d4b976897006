"""Plain reference of the memory controller's service order.

FCFS serves requests as they arrive.  FR-FCFS (arXiv:2009.08437 sec. 7)
looks at the next ``queue_depth`` requests and serves the oldest one that
hits the row last scheduled to its bank, provided it arrived within
``arrival_window_ns`` of the oldest waiting request; after ``starve_cap``
such bypasses the oldest is served regardless.  With write drain, writes
wait in a queue while reads pass, and leave as a batch sorted by (bank,
row) once ``drain_batch`` of them wait (and at the end).  No-op padding
keeps its place at the end.  Imports nothing of the program.
"""
from __future__ import annotations

NOOP_ISSUE = 1 << 30
TICKS_PER_NS = 8


def service_order(t, bank, row, is_write, ctl: dict, n_banks: int = 16):
    """Indices of one channel's requests in the order they are served."""
    real = [i for i, x in enumerate(t) if x < NOOP_ISSUE]
    pad = [i for i, x in enumerate(t) if x >= NOOP_ISSUE]
    order = real
    if ctl.get("write_drain"):
        order, waiting = [], []
        for i in real:
            if not is_write[i]:
                order.append(i)
                continue
            waiting.append(i)
            if len(waiting) >= ctl["drain_batch"]:
                order += sorted(waiting, key=lambda j: (bank[j], row[j]))
                waiting = []
        order += sorted(waiting, key=lambda j: (bank[j], row[j]))
    if ctl["policy"] == "frfcfs":
        order = _frfcfs(order, t, bank, row, ctl, n_banks)
    return order + pad


def _frfcfs(order, t, bank, row, ctl, n_banks):
    depth, cap = ctl["queue_depth"], ctl["starve_cap"]
    reach = ctl["arrival_window_ns"] * TICKS_PER_NS
    queue, rest = order[:depth], iter(order[depth:])
    last = [-1] * n_banks
    out, skipped = [], 0
    while queue:
        k = 0
        if skipped < cap:
            limit = t[queue[0]] + reach
            for j, i in enumerate(queue):
                if t[i] <= limit and row[i] == last[bank[i]]:
                    k = j
                    break
        i = queue.pop(k)
        skipped = skipped + 1 if k else 0
        out.append(i)
        last[bank[i]] = row[i]
        nxt = next(rest, None)
        if nxt is not None:
            queue.append(nxt)
    return out
