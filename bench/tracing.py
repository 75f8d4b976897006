"""The profiler trace of a window, reduced to what the metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes (``/device:TPU:<n>``) hold the programs the chip
ran (line ``XLA Modules``); the host plane holds the benchmark's own spans
(``bench.*``, written with ``jax.profiler.TraceAnnotation``).  The
benchmark compiles without per-operation trace points (``run.py``), so a
program execution is the finest device event: busy time is the union of
program executions.  The reduction keeps intervals in nanoseconds and is
checked on a small trace kept under ``bench/testdata/``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

Interval = Tuple[int, int]          # [start, end) in ns

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Reduced:
    """One traced window.  ``modules[d]`` lists device ``d``'s program
    executions as (name, start, end); ``spans`` the benchmark's host spans
    as (name, start, end)."""
    window: Interval
    modules: Dict[str, List[Tuple[str, int, int]]]
    spans: List[Tuple[str, int, int]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def devices(self) -> List[str]:
        return sorted(self.modules)

    def busy(self, device: str) -> List[Interval]:
        """Union of the intervals in which a program ran on ``device``,
        clipped to the window."""
        return union(clip([(s, e) for _, s, e in self.modules[device]],
                          self.window))

    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(length(self.busy(d)) for d in self.devices) \
            / len(self.devices) * 1e-9

    def module_s(self, match) -> float:
        """Seconds of the programs whose name ``match`` accepts, summed
        over their executions in the window, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(length(clip([(s, e) for n, s, e in self.modules[d]
                               if match(n)], self.window))
                  for d in self.devices)
        return tot / len(self.devices) * 1e-9

    def spans_named(self, name: str) -> List[Interval]:
        return [(s, e) for n, s, e in self.spans if n == name]


def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        elif e > s:
            out.append((s, e))
    return out


def clip(iv: List[Interval], win: Interval) -> List[Interval]:
    return [(max(s, win[0]), min(e, win[1])) for s, e in iv
            if e > win[0] and s < win[1]]


def length(iv: List[Interval]) -> int:
    return sum(e - s for s, e in iv)


def gaps(busy: List[Interval], win: Interval) -> List[Interval]:
    """The idle intervals of the window between busy ones."""
    out, t = [], win[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if win[1] > t:
        out.append((t, win[1]))
    return out


def subtract(iv: List[Interval], busy: List[Interval]) -> int:
    """Length of ``iv`` (disjoint) not covered by ``busy`` (disjoint)."""
    tot = 0
    for s, e in iv:
        tot += (e - s) - length(clip(busy, (s, e)))
    return tot


def load(log_dir: str):
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return jax.profiler.ProfileData.from_file(files[-1])


def reduce(pd, window_span: str = "bench.window") -> Reduced:
    """Device programs and benchmark spans of one trace; the window is the
    ``window_span`` host span."""
    modules: Dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            modules[plane.name] = [ev for line in plane.lines
                                   if line.name == MODULE_LINE
                                   for ev in _events(line)]
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
    win = [(s, e) for n, s, e in spans if n == window_span]
    if not win:
        raise ValueError(f"the trace holds no {window_span!r} span")
    return Reduced(window=win[0], modules=modules,
                   spans=[x for x in spans if x[0] != window_span])


def _events(line):
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device programs that took most time (seconds summed over the
    window, averaged over the devices) and the longest idle gaps of the
    first device, each named by the innermost benchmark span open at its
    start."""
    per: Dict[str, int] = {}
    for d in red.devices:
        for n, s, e in red.modules[d]:
            iv = clip([(s, e)], red.window)
            if iv:
                per[n] = per.get(n, 0) + length(iv)
    n_dev = max(1, len(red.devices))
    device_ops = sorted(([n, t / n_dev * 1e-9] for n, t in per.items()),
                        key=lambda x: -x[1])[:top]
    idle = []
    if red.devices:
        for s, e in gaps(red.busy(red.devices[0]), red.window):
            idle.append([innermost(red.spans, s), (e - s) * 1e-9])
    idle.sort(key=lambda x: -x[1])
    return {"device_ops": device_ops, "idle_gaps": idle[:top]}


def innermost(spans, t: int) -> str:
    """The shortest benchmark span open at ``t`` (``window`` if none)."""
    best = None
    for n, s, e in spans:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (n, e - s)
    return best[0] if best else "window"
