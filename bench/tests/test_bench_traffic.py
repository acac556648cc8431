"""The traffic generator: one replay set per mix, whatever the seed; prompt
tokens per seed; clips and medians held, lengths never rounded to a grid."""
import json
import os

import numpy as np
import pytest

from harness import traffic as T

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "traffic")


# An open-loop mix: the Azure conversation trace's medians as Splitwise
# (arXiv:2311.18677) reports them, Poisson arrivals.
CONVERSATION = {
    "loop": "open", "rate_rps": 0.5, "lead_in_s": 10.0,
    "components": [{"name": "azure_conversation", "weight": 1,
                    "prompt": {"lognormal": [1020, 0.8]}, "output": {"lognormal": [129, 0.8]}}],
    "prompt_clip": [16, 3584], "output_clip": [8, 511],
    "requests": 2000, "population_seed": 20231130}


def mix(name):
    if name == "conversation":
        return dict(CONVERSATION)
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["longbench", "conversation"])
def test_replay_set_is_fixed_and_clipped(name):
    m = mix(name)
    a, b = T.plan(m), T.plan(m)
    assert a == b
    lo, hi = m["prompt_clip"]
    olo, ohi = m["output_clip"]
    for p in a:
        assert lo <= p.prompt_len <= hi and isinstance(p.prompt_len, int)
        assert olo <= p.output_len <= ohi
        assert p.prompt_len + p.output_len <= T.longest_total(m)
    # ragged lengths: no grid the program could be warmed for
    assert len({p.prompt_len for p in a}) > len(a) // 2


def test_tokens_follow_the_seed():
    m = mix("conversation")
    p = T.plan(m)[3]
    big = 2**31 + 7
    assert T.prompt_tokens(big, p, 1000) == T.prompt_tokens(big, p, 1000)
    assert T.prompt_tokens(big, p, 1000) != T.prompt_tokens(big + 1, p, 1000)
    toks = T.prompt_tokens(5, p, 1000)
    assert len(toks) == p.prompt_len and 0 <= min(toks) and max(toks) < 1000


def test_conversation_medians_and_arrivals():
    m = mix("conversation")
    plan = T.plan(m)
    prompts = np.array([p.prompt_len for p in plan])
    outputs = np.array([p.output_len for p in plan])
    assert abs(np.median(prompts) - 1020) <= 40
    assert abs(np.median(outputs) - 129) <= 3
    dues = np.array([p.due_s for p in plan])
    assert np.all(np.diff(dues) > 0)
    rate = len(dues) / dues[-1]
    assert rate == pytest.approx(m["rate_rps"], rel=0.1)


def test_longbench_components_and_clients():
    m = mix("longbench")
    plan = T.plan(m)
    by = {}
    for p in plan:
        by.setdefault(p.component, []).append(p.prompt_len)
    assert set(by) == {"gov_report", "qmsum", "multi_news"}
    assert abs(np.mean(by["qmsum"]) - 10614) < 600
    assert abs(np.mean(by["multi_news"]) - 2113) < 600
    assert all(p.due_s is None for p in plan)
    totals = [p.prompt_len + p.output_len for p in plan]
    q = np.quantile(totals, 0.9)
    assert T.clients(m, int(3 * q) + 1) == 3
    assert T.clients(m, 100) == m["clients"]["min"]


def test_lengths_are_clipped_and_whole():
    assert T.clip(1020.4, 16, 3584) == 1020
    assert T.clip(1020.6, 16, 3584) == 1021
    assert T.clip(-5.0, 16, 3584) == 16
    assert T.clip(9000.0, 16, 3584) == 3584
    assert T.longest_total({"prompt_clip": [512, 16384], "output_clip": [16, 1024]}) == 17408
