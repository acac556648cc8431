"""One run of one cell: set up, measure a window, check, print one line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``) is everything before the window: weights made on the
device from the seed, the cluster and its pools, the decode step's buckets
compiled or loaded from the persistent cache, and the mix's lead-in.
The window then runs for ``--seconds``. With ``--trace 1`` the profiler
records the window and the per-layer metrics are printed; with ``--trace 0``
the end-to-end metrics are. After the window the program's state is freed
and the float32 reference checks a sample of what was served.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``busy_s`` and
``window_s`` when traced), ``breakdown`` when traced, and last ``checks``:
each number compared, with its limit. The same comparisons are the last
lines of standard error.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from harness import check, spec, traffic
from harness import trace as tracing
from harness import weights as W
from harness.serve import LoadGenerator, Recorder, instrument, warm_up
from harness.window import Run

# A checkout's first run compiles every prefill and transfer shape its lead-in
# meets; later runs load them from the persistent cache.
LEAD_IN_MAX_S = 900.0
FINISH_WAIT_S = 60.0


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else the fixed ``<checkout>/.jax_cache``. Every program
    is kept, however fast it compiled, so that a run finds what earlier runs
    in the same checkout compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(spec.CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class Cluster:
    """The system under test, built for one cell and seed."""

    def __init__(self, cell: spec.Cell, seed: int):
        import jax
        from repro.serving.api import FlowKVClient

        self.cell = cell
        self.shape = spec.model_shape(cell.config)
        self.serving = cell.config["serving"]
        cfg = spec.family(self.shape["family"]).program_config(cell.config, cell.config_name)
        self.weights = W.make(self.shape, seed)
        jax.block_until_ready(self.weights)
        self.client = FlowKVClient(
            cfg, self.weights, num_prefill=1, num_decode=1,
            num_blocks=int(self.serving["pool_blocks"]),
            max_batch_tokens=int(self.serving["max_batch_tokens"]),
            transfer_schedule="flowkv")
        self.recorder = Recorder()
        instrument(self.client, self.recorder)
        mix = cell.traffic
        pool_tokens = int(self.serving["pool_blocks"]) * self.shape["block_size"]
        self.clients = traffic.clients(mix, pool_tokens) if mix["loop"] == "closed" else 0
        self.max_batch = self.clients or int(self.serving["max_decode_batch"])

    def warm_up(self) -> Dict[str, int]:
        return warm_up(self.client, self.cell.traffic, self.max_batch)

    def load(self, seed: int) -> LoadGenerator:
        return LoadGenerator(self.client, self.cell.traffic, seed, self.shape["vocab"],
                      self.clients)


def served(records: List[Any]) -> List[Dict[str, List[int]]]:
    return [{"prompt": list(r.request.prompt_tokens),
             "served": list(r.request.output_tokens)} for r in records]


def compare(cell: spec.Cell, weights, shape, sampled: List[Dict[str, List[int]]],
            fp8: bool = False) -> Dict[str, Optional[float]]:
    """Widest gaps over the sample (served, and the control's with ``fp8``)."""
    ref = spec.family(shape["family"])
    per = [check.gaps(ref, weights, shape, s["prompt"], s["served"], fp8) for s in sampled]
    out = {"served": check.widest([p["served"] for p in per])}
    if fp8:
        out["control"] = check.widest([p["control"] for p in per])
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices, peaks: Dict[str, Any], say=print, t_start: Optional[float] = None,
             fault=None) -> Dict[str, Any]:
    """Set up, measure, check; returns the result object (not yet printed).

    ``fault`` (tests only) is called with the built ``Cluster`` before the
    window, to break the timed path underneath.
    """
    import jax
    t_start = time.monotonic() if t_start is None else t_start
    say(f"compile cache: {enable_compile_cache()}")
    sut = Cluster(cell, seed)
    warm = sut.warm_up()
    say(f"warm-up: {json.dumps(warm)}; clients {sut.clients}; "
        f"pool blocks {sut.serving['pool_blocks']} per engine")
    if fault is not None:
        fault(sut)
    drv = sut.load(seed)
    drv.lead_in(LEAD_IN_MAX_S)
    setup_s = time.monotonic() - t_start
    say(f"set-up: {setup_s} s")
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        tracing.start(log_dir)
    w0, w1 = drv.run(seconds)
    reduced = tracing.stop_and_reduce(log_dir) if trace else None
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    drv.until_finished(w0, FINISH_WAIT_S)
    limits = cell.limits
    picked = check.sample(drv.records, w0, seed, int(limits["sample"]["tokens"]),
                          int(limits["sample"]["max_requests"]))
    sampled = served(picked)
    run = Run(w0, w1, drv.records, sut.recorder.spans, sut.recorder.compiles,
              sut.shape, peaks, setup_s, reduced, drv.lateness)
    metrics = read_metrics(cell, run, trace)
    attempted = sum(1 for r in drv.records if r.sent <= w1)
    failed = sum(1 for r in drv.records if r.sent <= w1 and r.request.state.value
                 in ("rejected", "cancelled", "failed"))
    rec = sut.recorder
    say(f"compile requests in the window: {sum(1 for t, _ in rec.compiles if run.inside(t))}, "
        f"{sum(1 for t in rec.misses if run.inside(t))} of them missed the persistent cache")
    if drv.lateness:
        late = sorted(drv.lateness)
        say(f"generator lateness: median {late[len(late) // 2]} s, max {late[-1]} s "
            f"over {len(late)} sends")
    weights, shape = sut.weights, sut.shape
    sut.recorder.close()
    del run, drv, sut, picked
    gc.collect()
    widest = compare(cell, weights, shape, sampled)["served"]
    limit = float(limits["max_logit_gap"]["limit"])
    correct = check.judge(widest, limit, failed)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.idle_by_host()}
    say(f"checked {len(sampled)} requests, "
        f"{sum(len(s['served']) for s in sampled)} served tokens")
    result["checks"] = {
        "max_logit_gap": {"value": widest, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
    }
    return result


def read_metrics(cell: spec.Cell, run: Run, trace: bool) -> Dict[str, Dict[str, Any]]:
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.load_module("metrics", m["name"]).read(run)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv: Optional[List[str]] = None) -> int:
    t_start = time.monotonic()
    args = parse(argv)
    cell = spec.load_cell(args.workload)
    sys.path.insert(0, str(spec.CHECKOUT / "src"))
    import jax
    from peaks import peaks_for

    devices = jax.devices()
    dev = devices[0]
    tag = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"{tag} {cell.name} needs {cell.chips} TPU chip(s); no result",
              file=sys.stderr)
        return 2
    try:
        peaks = peaks_for(dev.device_kind)
    except KeyError as e:
        print(f"{tag} {e}; no result", file=sys.stderr)
        return 2
    say = lambda msg: print(f"{tag} {msg}", file=sys.stderr, flush=True)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices[:cell.chips],
                      peaks, say=say, t_start=t_start)
    for name, c in result["checks"].items():
        say(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
