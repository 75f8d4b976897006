"""Flight-recorder observability (DESIGN.md §15).

Two layers over the simulator and its orchestration:

 * ``obs.telemetry`` — host-side collection of the in-scan telemetry
   window frames that ``dram.run_segment`` / ``run_sweep_segment`` return
   when ``StaticConfig.telemetry`` is set: ``WindowCollector`` masks the
   per-step frames down to closed windows and serves per-window time series
   (hit rates, relocation bursts, bus/MSHR stalls, per-bank issue mix).
 * ``obs.trace`` — program spans (``span``, on the profiler's clock:
   ``repro.sched.schedule``, ``repro.sweep.*``, ``repro.orch.*``) and a
   structured JSONL span/event log for the orchestrator (shard
   lifecycle, checkpoint save/restore/fallback, retries, straggler
   re-issue, device loss, quarantine), timestamped off the deterministic
   ``runtime.faults.LogicalClock``, plus a Chrome trace-event exporter
   (load the output in Perfetto / chrome://tracing).

``python -m repro.obs`` measures the telemetry tax on the fig12 capacity
grid, pins chunked-vs-monolithic window series bitwise, renders the
``phase_mix`` re-warming time series, and writes ``BENCH_obs.json``.
"""
from repro.obs.telemetry import WindowCollector, window_table
from repro.obs.trace import (Tracer, chrome_trace, chrome_from_jsonl,
                             span, telemetry_counter_events)
from repro.obs import latency

__all__ = ["WindowCollector", "window_table", "Tracer", "chrome_trace",
           "chrome_from_jsonl", "span", "telemetry_counter_events",
           "latency"]
