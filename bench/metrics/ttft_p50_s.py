"""Median time to first token of the requests whose first token came in the
window: from when each was due (open loop) or sent (closed loop)."""
from harness.window import percentile


def read(run):
    return percentile(run.ttfts(), 50)
