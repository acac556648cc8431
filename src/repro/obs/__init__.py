"""Observability plane: span tracing, trace capture/replay, calibrated cost
models, and the cross-PR perf trajectory.

The paper's headline numbers are *measured* claims; this package is what
lets the reproduction measure honestly:

* ``tracing``   — low-overhead span recorder: per-request lifecycle spans
                  (queue / admission / prefill / transfer / decode /
                  prefix_fetch) on both the scheduler clock and the wall
                  clock, nested step spans on the profiler's clock,
                  compile requests charged to the open span, JSONL export,
                  and ``attach_tracer`` / ``detach_tracer`` to wire a
                  recorder into a live ``PDCluster`` or ``ClusterSim``.
* ``calibrate`` — fits ``TransportProfile`` / ``HardwareProfile``
                  coefficients from measured kernel timings and asserts a
                  sim-vs-real predicted-TTFT error bound (CI gate).
* ``replay``    — deterministically re-runs a captured trace's arrival
                  process and request shapes through ``ClusterSim`` under
                  any routing policy.
* ``history``   — schema-versioned ``BENCH_<area>.json`` records appended by
                  every gated benchmark; ``tools/bench_history.py --check``
                  compares against committed baselines so the perf
                  trajectory exists across PRs.

See ``docs/observability.md`` for the span taxonomy, trace format and the
calibration workflow.
"""
from repro.obs.tracing import (Span, SpanRecorder, Trace, attach_tracer,
                               detach_tracer, read_trace, write_trace)

__all__ = ["Span", "SpanRecorder", "Trace", "attach_tracer", "detach_tracer",
           "read_trace", "write_trace"]
