"""`patch_attention`'s share of its roofline: for every call in the traced
window, the least time the chip could take (operations over the bf16 peak or
bytes over HBM bandwidth, whichever is larger; `bench/flops.py`), summed,
over the kernel's summed device time in the trace. The bound that applies is
printed with the run's diagnostics.

`KERNEL` is the name the trace reduction looks for in device operations;
`instrument()` names the function whose calls the harness records in traced
runs, and how to read a call's shape from its arguments."""
from bench import flops
from bench.metrics import _roofline

KERNEL = "patch_attention"
count = flops.patch_attention


def instrument():
    from repro.kernels import ops
    return ops, "grouped_attention_kernel", lambda a: tuple(a[0].shape)


def read(run):
    return _roofline.share(run, KERNEL, count)
