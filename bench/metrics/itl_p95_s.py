"""95th percentile of the gaps ``itl_p50_s`` reads: every stall of the
serving loop lands here."""
from harness.window import percentile


def read(run):
    return percentile(run.gaps(), 95)
