"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it finished is drawn
from the seed, the longest of them always in it. The float32 reference runs
once over each prompt with its served tokens, teacher-forced, and reads at
every served position the gap by which the served token's reference logit
lies below the reference's best. The widest gap over the sample is the number
compared; its limit is in ``limits/<workload>.json``. A run is correct when
that gap is within its limit and no request failed (``judge``).

With ``fp8=True`` the same positions are also read for the control: the
token that the reference computed in float8 puts first.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


def sample(records: List[Any], since: float, seed: int, tokens: int,
           max_requests: int) -> List[Any]:
    """Requests finished after ``since``: the longest, then others in a
    seeded order until ``tokens`` served tokens or ``max_requests``."""
    done = [r for r in records if r.finished is not None and r.finished >= since]
    if not done:
        return []
    total = lambda r: r.request.prompt_len + r.request.num_output
    longest = max(done, key=total)
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out = [longest]
    for i in order:
        if len(out) >= max_requests or sum(r.request.num_output for r in out) >= tokens:
            break
        out.append(rest[i])
    return out


def gaps(reference, weights, shape: Dict[str, Any], prompt: List[int],
         served: List[int], fp8: bool = False) -> Dict[str, np.ndarray]:
    """Per served position: the served token's gap below the reference's best
    logit and, with ``fp8``, the gap of the control's first choice."""
    tokens = np.asarray(prompt + served[:-1], np.int32)
    ref = reference.logits(weights, shape, tokens, len(prompt) - 1)
    best = ref.max(-1)
    idx = np.arange(len(served))
    out = {"served": best - ref[idx, np.asarray(served)]}
    if fp8:
        low = reference.logits(weights, shape, tokens, len(prompt) - 1, fp8=True)
        out["control"] = best - ref[idx, low.argmax(-1)]
    return out


def widest(values: List[np.ndarray]) -> Optional[float]:
    values = [v for v in values if len(v)]
    return float(max(v.max() for v in values)) if values else None


def judge(widest_gap: Optional[float], limit: float, failed: int) -> bool:
    """Correct: a gap was read, it is within ``limit``, and nothing failed.
    The control's gap is judged by the same rule, in the program's place."""
    return widest_gap is not None and widest_gap <= limit and failed == 0
