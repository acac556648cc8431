"""Output tokens delivered in the window over the window's seconds."""


def read(run):
    return run.tokens_out() / run.seconds
