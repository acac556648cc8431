"""Median gap between successive token deliveries of one request, over every
delivery in the window and every request."""
from harness.window import percentile


def read(run):
    return percentile(run.gaps(), 50)
