"""Milliseconds of ``NodeEngine.run_prefill`` per thousand prompt tokens it
forwarded in the window (the engine's wall time, pool written back)."""


def read(run):
    spans = run.spans_in("prefill")
    tokens = sum(c for s in spans for _, c in s.attrs["work"])
    if not tokens:
        return None
    return sum(s.end - s.start for s in spans) * 1e3 / (tokens / 1e3)
