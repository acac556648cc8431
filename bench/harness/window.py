"""What a run measured, as the metric readers see it.

``Run`` holds the window's bounds, every request record, the spans of the
wrapped layer boundaries, the compile events and, in a traced run, the
reduced device trace. Each ``metrics/<name>.py`` reads one number from it.
"""
from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from harness import spec


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (numpy's linear interpolation); None if empty."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


@dataclasses.dataclass
class Run:
    w0: float
    w1: float
    records: List[Any]
    spans: Dict[str, List[Any]]
    compiles: List[Any]
    shape: Dict[str, Any]              # harness.spec.model_shape of the config
    peaks: Dict[str, Any]
    setup_s: float
    trace: Optional[Any] = None        # harness.trace.Reduced, traced runs only
    lateness: List[float] = dataclasses.field(default_factory=list)

    @property
    def model(self) -> ModuleType:
        """The configuration's family module (``reference/<family>.py``): its
        FLOP counts and paged-attention calls."""
        return spec.family(self.shape["family"])

    @property
    def seconds(self) -> float:
        return self.w1 - self.w0

    def inside(self, t: Optional[float]) -> bool:
        return t is not None and self.w0 <= t <= self.w1

    def spans_in(self, kind: str) -> List[Any]:
        """Spans of ``kind`` that ended inside the window."""
        return [s for s in self.spans[kind] if self.inside(s.end)]

    def ttfts(self) -> List[float]:
        """First token minus due time (open loop) or send time (closed), of
        every request whose first token came inside the window."""
        return [r.deliveries[0][0] - r.due for r in self.records
                if r.deliveries and self.inside(r.deliveries[0][0])]

    def gaps(self) -> List[float]:
        """Gaps between successive deliveries of one request, for every
        delivery inside the window (tokens that arrive together make one)."""
        out = []
        for r in self.records:
            times = [t for t, _ in r.deliveries]
            out += [b - a for a, b in zip(times, times[1:]) if self.inside(b)]
        return out

    def tokens_out(self) -> int:
        return sum(n for r in self.records for t, n in r.deliveries if self.inside(t))
