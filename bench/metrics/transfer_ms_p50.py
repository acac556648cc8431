"""Median wall milliseconds of one P->D transfer (``PDCluster._transfer``,
its checksum included) that ended in the window."""
from harness.window import percentile


def read(run):
    ms = [(s.end - s.start) * 1e3 for s in run.spans_in("transfer")]
    return percentile(ms, 50)
