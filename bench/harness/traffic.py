"""The one traffic generator: every mix is a ``traffic/<name>.json`` it reads.

A mix fixes a replay set: the prompt and output lengths, the component each
request comes from and, for an open loop, its arrival time, all drawn once
from the mix's own ``population_seed``. Every run of a cell therefore sends
the same sizes at the same times, whatever its ``--seed``; the seed draws the
prompt tokens (and, elsewhere, the weights). So two runs differ only by what
the system does, not by what it was given.

Keys of a mix file:

* ``loop``: ``"open"`` (requests sent at their arrival times, timed from
  when they were due) or ``"closed"`` (``clients`` callers, each sending its
  next request when the previous one finished, timed from the send);
* ``components``: ``[{"name", "weight", "prompt": dist, "output": dist}]``,
  a dist being ``{"normal": [mean, std]}`` or ``{"lognormal": [median, sigma]}``;
* ``prompt_clip`` / ``output_clip``: ``[lo, hi]`` token bounds; lengths are
  drawn, rounded to whole tokens and clipped, never rounded to a grid, so the
  program sees the ragged prompt lengths that users send;
* ``requests``: the size of the replay set; ``population_seed``;
* open loop: ``rate_rps`` (Poisson arrivals) and ``lead_in_s`` (the schedule
  runs that long before the window opens);
* closed loop: ``clients``: ``{"pool_quantile": q, "min": m}`` — as many
  callers as the decode pool holds requests at the q-quantile total length.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    index: int
    component: str
    prompt_len: int
    output_len: int
    due_s: Optional[float]      # open loop: seconds after the schedule starts


def _draw(dist: Dict[str, List[float]], n: int, rng: np.random.Generator) -> np.ndarray:
    (kind, (a, b)), = dist.items()
    if kind == "normal":
        return rng.normal(a, b, n)
    if kind == "lognormal":
        return a * np.exp(rng.normal(0.0, b, n))
    raise ValueError(f"unknown distribution {kind!r}")


def clip(x: float, lo: int, hi: int) -> int:
    """``x`` rounded to a whole number of tokens and clipped to [lo, hi]."""
    return int(min(max(round(x), lo), hi))


def plan(mix: Dict[str, Any]) -> List[Planned]:
    """The mix's replay set, in sending order."""
    rng = np.random.default_rng(int(mix["population_seed"]))
    n = int(mix["requests"])
    comps = mix["components"]
    weights = np.asarray([c["weight"] for c in comps], float)
    which = rng.choice(len(comps), size=n, p=weights / weights.sum())
    plo, phi = mix["prompt_clip"]
    olo, ohi = mix["output_clip"]
    prompts = np.empty(n)
    outputs = np.empty(n)
    for i, c in enumerate(comps):
        sel = which == i
        prompts[sel] = _draw(c["prompt"], int(sel.sum()), rng)
        outputs[sel] = _draw(c["output"], int(sel.sum()), rng)
    due = None
    if mix["loop"] == "open":
        due = np.cumsum(rng.exponential(1.0 / float(mix["rate_rps"]), n))
    return [Planned(i, comps[which[i]]["name"],
                    clip(prompts[i], plo, phi), clip(outputs[i], olo, ohi),
                    None if due is None else float(due[i]))
            for i in range(n)]


def longest_total(mix: Dict[str, Any]) -> int:
    """The most tokens one request of the mix can hold: prompt and output."""
    return int(mix["prompt_clip"][1]) + int(mix["output_clip"][1])


def clients(mix: Dict[str, Any], pool_tokens: int) -> int:
    """Closed loop: callers the decode pool holds at the rule's quantile."""
    rule = mix["clients"]
    totals = [p.prompt_len + p.output_len for p in plan(mix)]
    at_q = float(np.quantile(totals, float(rule["pool_quantile"])))
    return max(int(rule["min"]), int(pool_tokens // at_q))


def prompt_tokens(seed: int, p: Planned, vocab: int) -> List[int]:
    """Prompt token ids of one planned request, from the run's seed."""
    rng = np.random.default_rng([seed, 1, p.index])
    return rng.integers(0, vocab, p.prompt_len).tolist()
