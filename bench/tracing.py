"""The profiler trace of a window, reduced to what the metrics read.

``jax.profiler`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device planes (``/device:TPU:<n>``) hold the programs the chip
ran (line ``XLA Modules``); the host plane holds the benchmark's own spans
(``bench.*``, written with ``jax.profiler.TraceAnnotation``) and the
program's (``repro.*``, ``obs.trace.span``), each with its stats.  The
benchmark compiles without per-operation trace points (``run.py``), so a
program execution is the finest device event: busy time is the union of
program executions.  The reduction keeps intervals in nanoseconds and is
checked on a small trace kept under ``bench/testdata/``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import warnings
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]          # [start, end) in ns

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
PROG_PREFIX = "repro."


@dataclasses.dataclass
class Reduced:
    """One traced window.  ``modules[d]`` lists device ``d``'s program
    executions as (name, start, end); ``spans`` the benchmark's host spans
    as (name, start, end); ``prog_spans`` the program's as (name, start,
    end, stats)."""
    window: Interval
    modules: Dict[str, List[Tuple[str, int, int]]]
    spans: List[Tuple[str, int, int]]
    prog_spans: List[Tuple[str, int, int, dict]] = dataclasses.field(
        default_factory=list)

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

    def prog_named(self, name: str) -> List[Interval]:
        return [(s, e) for n, s, e, _ in self.prog_spans if n == name]


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


def idle_ms_per_point(red: Reduced, spans: List[Interval],
                      n_points: int) -> Optional[float]:
    """Milliseconds per point inside the host ``spans`` in which no
    operation runs on a device, averaged over the devices; ``None`` where
    there is nothing to read."""
    if not red.devices or n_points <= 0:
        return None
    spans = union(clip(spans, red.window))
    if not spans:
        return None
    idle = sum(subtract(spans, red.busy(d)) for d in red.devices)
    return idle / len(red.devices) * 1e-6 / n_points


def load(log_dir: str):
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no trace under {log_dir}")
    return jax.profiler.ProfileData.from_file(files[-1])


def reduce(pd, window_span: str = "bench.window") -> Reduced:
    """Device programs, benchmark spans and program spans of one trace;
    the window is the ``window_span`` host span."""
    modules: Dict[str, list] = {}
    spans: list = []
    prog: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            modules[plane.name] = [_interval(ev) for line in plane.lines
                                   if line.name == MODULE_LINE
                                   for ev in line.events]
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(_interval(ev))
                    elif ev.name.startswith(PROG_PREFIX):
                        prog.append(_interval(ev) + (_stats(ev),))
    win = [(s, e) for n, s, e in spans if n == window_span]
    if not win:
        raise ValueError(f"the trace holds no {window_span!r} span")
    return Reduced(window=win[0], modules=modules,
                   spans=[x for x in spans if x[0] != window_span],
                   prog_spans=prog)


def _interval(ev):
    return (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))


def _stats(ev) -> dict:
    with warnings.catch_warnings():     # the stats' type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device programs that took most time (seconds summed over the
    window, averaged over the devices) and the longest idle gaps of the
    first device, each named by the span that holds most of it
    (``holder``)."""
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
        longest = sorted(gaps(red.busy(red.devices[0]), red.window),
                         key=lambda g: g[0] - g[1])[:top]
        spans = red.spans + [x[:3] for x in red.prog_spans]
        idle = [[holder(spans, g), (g[1] - g[0]) * 1e-9] for g in longest]
    return {"device_ops": device_ops, "idle_gaps": idle}


def holder(spans, gap: Interval) -> str:
    """The span that holds most of ``gap``: each instant of the gap goes
    to the shortest span open at it, its innermost, or to ``window``
    where none is open; the name given most time wins."""
    s0, e0 = gap
    inside = [(n, max(s, s0), min(e, e0), e - s) for n, s, e in spans
              if s < e0 and e > s0]
    cuts = sorted({s0, e0, *(t for _, s, e, _ in inside for t in (s, e))})
    held: Dict[str, int] = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(ln, n) for n, s, e, ln in inside if s <= a and b <= e]
        name = min(open_)[1] if open_ else "window"
        held[name] = held.get(name, 0) + b - a
    return max(sorted(held), key=held.get)
