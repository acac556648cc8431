"""Mean number of requests in a decode step, over the window's steps."""


def read(run):
    steps = run.spans_in("decode")
    return sum(len(s.attrs["lens"]) for s in steps) / len(steps) if steps else None
