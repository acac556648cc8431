"""Device trace of the window, and its reduction to numbers.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads it
with nothing but JAX. The reduction keeps, inside the host annotation
``bench.window``:

* every operation on a device plane's ``XLA Ops`` line (its HLO name, start
  and duration, and for a custom call, which is how a Pallas kernel appears,
  the whole instruction, whose operand shapes identify the kernel), from
  which busy time is the union of their intervals (a
  loop's operation and the operations of its body nest, so the union counts
  them once; the per-name totals of ``top_ops`` count both);
* the benchmark's own host annotations (``bench.*``), so that each idle gap
  of the device can be laid to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"


@dataclasses.dataclass
class Op:
    name: str            # the HLO instruction up to its layout: "%reshape.10 = bf16[900,28,2,32768]"
    start: int           # ns
    dur: int             # ns
    device: int
    text: str = ""       # the whole HLO instruction, for custom calls (kernels) only

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Reduced:
    t0: int                               # window start, ns
    t1: int                               # window end, ns
    ops: List[Op]
    devices: int
    host: List[Tuple[str, int, int]]      # (annotation, start, end), ns

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices."""
        if self.devices == 0:
            return 0.0
        total = 0
        for d in range(self.devices):
            total += union_ns([(o.start, o.end) for o in self.ops if o.device == d],
                              self.t0, self.t1)
        return total / self.devices * 1e-9

    def kernels(self, pattern: "re.Pattern[str]") -> List[Tuple[Op, "re.Match[str]"]]:
        """Custom calls whose instruction ``pattern`` matches, with the match."""
        out = []
        for o in self.ops:
            m = pattern.match(o.text) if o.text else None
            if m:
                out.append((o, m))
        return out

    def top_ops(self, n: int = 10) -> List[List[object]]:
        by: Dict[str, int] = {}
        for o in self.ops:
            by[o.name] = by.get(o.name, 0) + o.dur
        return [[k, v * 1e-9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_host(self, n: int = 10) -> List[List[object]]:
        """Idle device seconds of device 0, by the innermost ``bench.*`` host
        annotation open at the middle of each gap (``host`` when none)."""
        busy = merged([(o.start, o.end) for o in self.ops if o.device == 0],
                      self.t0, self.t1)
        gaps, t = [], self.t0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < self.t1:
            gaps.append((t, self.t1))
        by: Dict[str, int] = {}
        for a, b in gaps:
            mid = (a + b) // 2
            open_ = [h for h in self.host if h[1] <= mid <= h[2] and h[0] != WINDOW]
            label = min(open_, key=lambda h: h[2] - h[1])[0] if open_ else "host"
            by[label] = by.get(label, 0) + (b - a)
        return [[k, v * 1e-9] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def merged(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Intervals clipped to [lo, hi] and merged where they overlap."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def union_ns(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> int:
    return sum(b - a for a, b in merged(intervals, lo, hi))


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop_and_reduce(log_dir: str) -> Optional[Reduced]:
    """Stop the profiler, reduce its trace, and delete the files."""
    import jax
    jax.profiler.stop_trace()
    try:
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            return None
        return reduce(jax.profiler.ProfileData.from_file(paths[-1]))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def reduce(profile) -> Optional[Reduced]:
    """Reduce a ``ProfileData`` (or anything shaped like one) to the window."""
    host: List[Tuple[str, int, int]] = []
    device_ops: List[Tuple[int, object]] = []
    devices = 0
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            idx = devices
            devices += 1
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops += [(idx, ev) for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        host.append((ev.name, s, s + int(ev.duration_ns)))
    windows = [h for h in host if h[0] == WINDOW]
    if not windows:
        return None
    _, t0, t1 = windows[-1]
    ops = []
    for idx, ev in device_ops:
        s, d = int(ev.start_ns), int(ev.duration_ns)
        if s + d <= t0 or s >= t1:
            continue
        text = ev.name if " custom-call(" in ev.name else ""
        ops.append(Op(ev.name.split("{", 1)[0][:160], s, d, idx, text))
    host = [h for h in host if h[2] > t0 and h[1] < t1]
    return Reduced(t0, t1, ops, devices, host)
