"""95th percentile of the same times to first token as ``ttft_p50_s``."""
from harness.window import percentile


def read(run):
    return percentile(run.ttfts(), 95)
