"""Mean wall milliseconds of one ``NodeEngine.run_decode`` step in the
window, from dispatch to the pool written and the tokens read back."""


def read(run):
    steps = run.spans_in("decode")
    return sum(s.end - s.start for s in steps) * 1e3 / len(steps) if steps else None
