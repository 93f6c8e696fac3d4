"""Operations and bytes from shapes: for one denoising step of one request of
the plain model, and for each call of the `patch_attention` kernel.

A step counts the multiply-adds of every matrix product and convolution, at
two operations each, of the model without patches: the prompt's keys and
values once per block and image. Elementwise work (norms, activations,
softmax) is left out; it is a few percent of the total.
"""
from __future__ import annotations

from bench.models import kind


def step_flops(m: dict, h: int, w: int) -> int:
    """One backbone evaluation of one (h, w) latent; m is a model section.
    Counted by its kind's module (`bench/models/<kind>.py`)."""
    return kind(m["kind"]).step_flops(m, h, w)


def patch_attention(shape, itemsize=4):
    """(flops, bytes) of one call on q of shape (B, H, S, D): scores and
    weighted sum; q, k, v read and the output written once."""
    b, h, s, d = shape
    return 4 * b * h * s * s * d, itemsize * 4 * b * h * s * d


def roofline_seconds(flops, nbytes, peaks):
    """(least time, which bound) on the chip."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
