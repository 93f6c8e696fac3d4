"""Whole-step share of the chip's bf16 peak: the plain model's operations for
every real request-step in the window (`bench/flops.py`; padding requests
and work the patch cache skipped are not counted as work done twice, and
the model's operations are counted once), over the summed step time times
the peak."""
from bench import flops


def read(run):
    m = run.cfg["model"]
    res = run.cfg["resolutions"]
    work = sum(n * flops.step_flops(m, *res[i])
               for t in run.ticks for i, n in enumerate(t.composition))
    busy = sum(t.dt for t in run.ticks)
    if not work or busy <= 0:
        return None
    return 100.0 * work / (busy * run.peaks["flops_bf16"])
