"""Host-side collection of in-scan telemetry windows (DESIGN.md §15).

The telemetry-enabled scans emit the segment's CLOSED windows as a
fixed-shape ``dram.TelemetryFrame`` (``W = min(T, T // period + 2)`` rows
per segment, trailing rows ``valid=False`` filler — fixed shapes keep the
scan a single compilation).  ``WindowCollector`` is the host-side half: it
absorbs each segment's frames (``add``), takes the final partial window
off the carried ``SimState.tel`` cursor (``close``), and serves masked,
concatenated per-window series.  Because windows are indexed by the
real-request count, a collector fed chunked segments produces the exact
byte-identical series as one fed the monolithic scan's frames —
``tests/test_obs.py`` pins chunk sizes {1, 7, 64k}.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core import dram
from repro.obs import latency

__all__ = ["WindowCollector", "window_table", "series_csv"]

# derived per-window rates (floats; everything else is the raw int32 delta)
_DERIVED = ("hit_rate", "row_hit_rate", "write_frac", "avg_lat_ns",
            "slo_rate", "p50_ns", "p99_ns")


class WindowCollector:
    """Accumulate telemetry frames from a (possibly chunked) replay.

    Use with the streaming drivers::

        col = WindowCollector()
        streaming.simulate_stream(segments, cfg, telemetry=col)
        s = col.series()          # {"win_idx": ..., "w_cache_hits": ...,
                                  #  "hit_rate": ..., ...}

    or feed the frames ``dram.run_segment`` returns directly (``add`` per
    segment, ``close(state)`` once at the end).  For batched/multi-channel
    runs the frames carry lead axes (P, [C,]); pass the lead index to
    ``series`` to select one stream, e.g. ``series(index=(p, c))``.
    """

    _fields = dram.TelemetryWindows._fields

    def __init__(self) -> None:
        # frames are kept as handed over (device arrays) and only pulled
        # to host at series() time: collection must not force a per-chunk
        # device sync, or it would serialize the streaming drivers' async
        # dispatch pipeline (and inflate the measured telemetry tax)
        self._chunks: List["dram.TelemetryFrame"] = []
        self._final: Optional["dram.TelemetryState"] = None
        self._closed = False

    def add(self, frames: "dram.TelemetryFrame") -> None:
        """Absorb one segment's frames (any lead axes, scan axis last)."""
        assert not self._closed, "collector already closed"
        self._chunks.append(frames)

    def close(self, state: "dram.SimState") -> None:
        """Take the final (possibly partial) window — and the cumulative
        §16 latency-distribution planes — from the scan carry."""
        assert not self._closed, "collector already closed"
        self._final = state.tel
        self._closed = True

    def block(self) -> None:
        """Wait for every collected frame (benchmark timing fences)."""
        import jax
        jax.block_until_ready((self._chunks, self._final))

    @property
    def n_segments(self) -> int:
        return len(self._chunks)

    def series(self, index: Tuple[int, ...] = ()) -> Dict[str, np.ndarray]:
        """Per-window series for ONE stream, oldest window first.

        ``index`` selects the lead (params/channel) axes; what remains
        must be the scan axis.  Returns every ``TelemetryWindows`` field
        as a 1-D int64 array over windows (``w_bank_issues`` is
        ``(n_windows, n_banks)``, ``w_hist`` ``(n_windows,
        HIST_BUCKETS)``) plus the derived float rates ``hit_rate`` /
        ``row_hit_rate`` / ``write_frac`` / ``avg_lat_ns`` / ``slo_rate``
        and the per-window tail estimates ``p50_ns`` / ``p99_ns``.
        The final partial window is included iff it saw any requests.

        Zero-request windows are guarded explicitly: count rates emit
        0.0 and the latency-valued series (``avg_lat_ns``, percentiles)
        emit NaN — never a division artifact or a runtime warning.
        """
        cols: Dict[str, List[np.ndarray]] = {f: [] for f in self._fields}
        for frames in self._chunks:
            v = np.asarray(frames.valid)[index]
            assert v.ndim == 1, (
                "index must select all lead axes; got shape %r" % (v.shape,))
            m = v.astype(bool)
            for f in self._fields:
                cols[f].append(np.asarray(getattr(frames.win, f))[index][m])
        if self._final is not None and \
                int(np.asarray(self._final.win.w_reqs)[index]) > 0:
            for f in self._fields:
                cols[f].append(
                    np.asarray(getattr(self._final.win, f))[index][None])
        empty = {"w_bank_issues": dram.GEOM.n_banks,
                 "w_hist": dram.HIST_BUCKETS}
        out = {f: (np.concatenate(cols[f]).astype(np.int64) if cols[f]
                   else np.zeros((0,) + ((empty[f],) if f in empty else ()),
                                 np.int64)) for f in self._fields}
        idx = out["win_idx"]
        assert np.all(np.diff(idx) > 0), \
            "window ordinals must be strictly increasing"
        nz = out["w_reqs"] > 0
        reqs = np.where(nz, out["w_reqs"], 1).astype(np.float64)
        rate = lambda num: np.where(nz, num / reqs, 0.0)
        out["hit_rate"] = rate(out["w_cache_hits"])
        out["row_hit_rate"] = rate(out["w_row_hits"])
        out["write_frac"] = rate(out["w_writes"])
        out["slo_rate"] = rate(out["w_slo"])
        out["avg_lat_ns"] = np.where(nz, out["w_lat_ns"] / reqs, np.nan)
        out.update(latency.tail_series(out, qs=(0.5, 0.99)))
        return out

    def cumulative(self, index: Tuple[int, ...] = ()) -> Dict[str, np.ndarray]:
        """The run-cumulative §16 planes of one stream (``close`` first).

        ``hist`` is the ``(2, n_cores, HIST_BUCKETS)`` read/write bucket
        counts, ``slo`` the per-core over-SLO request counts — feed them
        to ``obs.latency`` (``percentiles``, ``core_tails``, ``cdf``)."""
        assert self._closed and self._final is not None, \
            "cumulative planes live on the final carry; close() first"
        return {"hist": np.asarray(self._final.hist)[index].astype(np.int64),
                "slo": np.asarray(self._final.slo)[index].astype(np.int64)}


def window_table(series: Dict[str, np.ndarray], max_rows: int = 24) -> str:
    """Render a compact fixed-width per-window table (quickstart, CLI).

    Long series are subsampled evenly to ``max_rows`` so the table stays
    terminal-sized; the window ordinal column keeps the timeline honest.
    """
    n = len(series["win_idx"])
    if n == 0:
        return "(no closed telemetry windows)"
    rows = np.arange(n) if n <= max_rows else np.unique(
        np.linspace(0, n - 1, max_rows).astype(int))
    head = f"{'win':>6} {'reqs':>6} {'hit%':>6} {'rowhit%':>8} " \
           f"{'ins':>5} {'reloc':>6} {'lat(ns)':>8} {'p50':>7} {'p99':>7}"
    lines = [head, "-" * len(head)]
    for i in rows:
        lines.append(
            f"{series['win_idx'][i]:>6d} {series['w_reqs'][i]:>6d} "
            f"{100 * series['hit_rate'][i]:>6.1f} "
            f"{100 * series['row_hit_rate'][i]:>8.1f} "
            f"{series['w_ins'][i]:>5d} {series['w_reloc_blocks'][i]:>6d} "
            f"{series['avg_lat_ns'][i]:>8.1f} "
            f"{series['p50_ns'][i]:>7.1f} {series['p99_ns'][i]:>7.1f}")
    return "\n".join(lines)


def series_csv(series: Dict[str, np.ndarray]) -> str:
    """The full series as CSV (scalar columns only — no bank breakdown)."""
    keys = [f for f in series if series[f].ndim == 1]
    lines = [",".join(keys)]
    for i in range(len(series["win_idx"])):
        lines.append(",".join(
            f"{series[k][i]:.6g}" if series[k].dtype.kind == "f"
            else str(int(series[k][i])) for k in keys))
    return "\n".join(lines) + "\n"
