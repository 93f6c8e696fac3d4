"""`groupnorm_stitch`'s share of its roofline: for every call in the traced
window, the least time the chip could take, summed, over the kernel's summed
device time in the trace (`_roofline.share`).

The count is defined here. A call on patches (P, s, s, C) has to read each
patch once, write the haloed (P, s+2, s+2, C) tiles once, and read each
patch's per-channel mean and rstd and the affine scale and bias; its
operations are four per output element (subtract, two multiplies, add). At
half an operation a byte the kernel is bound by memory.

`KERNEL` is the name the trace reduction looks for in device operations;
`instrument()` names the function whose calls the harness records in traced
runs, and how to read a call's shape from its arguments."""
from bench.metrics import _roofline

KERNEL = "groupnorm_stitch"


def count(shape, itemsize=4):
    """(operations, bytes) of one call on patches of `shape`."""
    P, s, _, C = shape
    out = P * (s + 2) * (s + 2) * C
    nbytes = itemsize * (P * s * s * C + out) + 4 * (2 * P * C + 2 * C)
    return 4 * out, nbytes


def instrument():
    from repro.kernels import ops
    return ops, "groupnorm_stitch_kernel", lambda a: tuple(a[0].shape)


def read(run):
    return _roofline.share(run, KERNEL, count)
