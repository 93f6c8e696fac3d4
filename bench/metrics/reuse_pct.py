"""Patch cache: mean share of patch-block evaluations whose cached output was
reused, over the window's steps (`Metrics.compute_savings`). A count of
reuse, not of time saved: a block runs whole unless every patch in the batch
is reused."""


def read(run):
    s = [t.savings for t in run.ticks if t.savings is not None]
    return 100.0 * sum(s) / len(s) if s else None
