"""Shared arithmetic of the kernels' roofline shares."""
from bench import flops


def least_seconds(run, kernel, count):
    """(summed least time of every recorded call, {bound: calls});
    count(shape) -> (operations, bytes) of one call."""
    total, bounds = 0.0, {}
    for shape in (run.kernel_calls or {}).get(kernel, ()):
        t, bound = flops.roofline_seconds(*count(shape), run.peaks)
        total += t
        bounds[bound] = bounds.get(bound, 0) + 1
    return total, bounds


def share(run, kernel, count):
    if not run.trace:
        return None
    spent = run.trace["kernel_s"].get(kernel, 0.0)
    least, _ = least_seconds(run, kernel, count)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
