"""Median wait from arrival at the cluster to the start of prefill
(``Request.arrival_wall`` to ``prefill_start_wall``), of the requests whose
prefill started in the window."""
from harness.window import percentile


def read(run):
    waits = [r.request.prefill_start_wall - r.request.arrival_wall
             for r in run.records
             if run.inside(r.request.prefill_start_wall) and r.request.arrival_wall is not None]
    return percentile(waits, 50)
