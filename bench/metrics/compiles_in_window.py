"""Backend compile requests inside the window, loads from the persistent
compilation cache included (``jax.monitoring`` compile events)."""


def read(run):
    return float(sum(1 for t, _ in run.compiles if run.inside(t)))
