"""Window statistics: percentiles, time to first token from the due time
(open loop) or the send (closed loop), delivery gaps, tokens per window."""
from types import SimpleNamespace as NS

import pytest

from harness.spec import load_module
from harness.window import Run, percentile


def rec(due, sent, deliveries):
    return NS(due=due, sent=sent, deliveries=deliveries)


def run_of(records, w0=10.0, w1=20.0):
    return Run(w0, w1, records, {"prefill": [], "decode": [], "transfer": []}, [],
               {}, {}, 42.0)


def test_percentile_is_numpys_linear():
    assert percentile([1, 2, 3, 4], 50) == 2.5
    assert percentile([5.0], 95) == 5.0
    assert percentile([], 50) is None
    assert percentile(list(range(101)), 95) == pytest.approx(95.0)


def test_ttft_counts_from_the_due_time():
    # open loop: due at 9.0, sent late at 9.5, first token at 11.0 -> 2.0 s
    # closed loop records carry due == sent
    r = run_of([rec(9.0, 9.5, [(11.0, 1)]), rec(12.0, 12.0, [(12.5, 2)]),
                rec(1.0, 1.0, [(5.0, 1)])])           # first token before the window
    assert sorted(r.ttfts()) == [pytest.approx(0.5), pytest.approx(2.0)]
    assert load_module("metrics", "ttft_p50_s").read(r) == pytest.approx(1.25)


def test_gaps_and_tokens_inside_the_window():
    r = run_of([rec(0.0, 0.0, [(9.0, 2), (10.5, 1), (11.0, 1), (21.0, 1)]),
                rec(10.0, 10.0, [(12.0, 1), (15.0, 1)])])
    # gaps ending inside [10, 20]: 1.5, 0.5 and 3.0; the one ending at 21 is out
    assert sorted(r.gaps()) == [pytest.approx(0.5), pytest.approx(1.5), pytest.approx(3.0)]
    assert r.tokens_out() == 4
    assert load_module("metrics", "output_tokens_per_s").read(r) == pytest.approx(0.4)
    assert load_module("metrics", "itl_p50_s").read(r) == pytest.approx(1.5)
    assert load_module("metrics", "setup_s").read(r) == 42.0


def test_span_metrics():
    span = lambda s, e, **a: NS(start=s, end=e, attrs=a)
    spans = {"prefill": [span(11, 12, work=[(0, 1000), (1000, 1000)]),
                         span(1, 2, work=[(0, 5)])],
             "decode": [span(12, 12.05, lens=[10, 20]), span(13, 13.03, lens=[30])],
             "transfer": [span(14, 14.2, blocks=3)]}
    r = Run(10.0, 20.0, [], spans, [(11.0, 0.2), (5.0, 1.0)], {}, {}, 0.0)
    read = lambda name: load_module("metrics", name).read(r)
    assert read("prefill_ms_per_ktok") == pytest.approx(500.0)
    assert read("decode_batch_mean") == pytest.approx(1.5)
    assert read("decode_step_ms") == pytest.approx(40.0)
    assert read("transfer_ms_p50") == pytest.approx(200.0)
    assert read("compiles_in_window") == 1.0
