"""Operations and bytes from shapes: for one denoising step of one request of
the plain model, and for each call of the `patch_attention` kernel.

A step counts the multiply-adds of every matrix product and convolution, at
two operations each, of the model without patches: the prompt's keys and
values once per block and image. Elementwise work (norms, activations,
softmax) is left out; it is a few percent of the total.
"""
from __future__ import annotations


def _conv(h, w, cin, cout, k):
    return 2 * h * w * cin * cout * k * k


def _res(h, w, cin, cout, t_dim):
    f = _conv(h, w, cin, cout, 3) + _conv(h, w, cout, cout, 3)
    f += 2 * t_dim * 2 * cout
    if cin != cout:
        f += _conv(h, w, cin, cout, 1)
    return f


def _attn(s, c, n_text, d_text):
    f = 2 * s * c * c * 6                # wq wk wv wo, xq xo
    f += 2 * n_text * d_text * c * 2     # prompt keys and values
    f += 4 * s * s * c                   # self-attention scores and sum
    f += 4 * s * n_text * c              # cross-attention
    f += 2 * s * c * 4 * c * 2           # feed-forward
    return f


def step_flops(m: dict, h: int, w: int) -> int:
    """One backbone evaluation of one (h, w) latent; m is a model section."""
    t = m["t_dim"]
    f = 2 * 2 * t * t
    C0, W = m["latent_channels"], m["width"]
    nt, dt = m["n_text"], m["d_text"]
    if m["kind"] == "dit":
        s = h * w
        f += 2 * s * C0 * W * 2 + 2 * t * 3 * W
        return f + m["dit_depth"] * _attn(s, W, nt, dt)
    levels = m["levels"]
    chans = [W * 2 ** lvl for lvl in range(levels)]
    f += _conv(h, w, C0, W, 3)
    for lvl in range(levels):
        c, hh, ww = chans[lvl], h >> lvl, w >> lvl
        for _ in range(m["blocks_per_level"]):
            f += _res(hh, ww, c, c, t)
            if lvl in m["attn_levels"]:
                f += _attn(hh * ww, c, nt, dt)
        if lvl + 1 < levels:
            f += _conv(hh // 2, ww // 2, c, chans[lvl + 1], 3)
    cm, hm, wm = chans[-1], h >> (levels - 1), w >> (levels - 1)
    f += 2 * _res(hm, wm, cm, cm, t) + _attn(hm * wm, cm, nt, dt)
    for lvl in reversed(range(levels)):
        c, hh, ww = chans[lvl], h >> lvl, w >> lvl
        if lvl + 1 < levels:
            f += _conv(hh, ww, chans[lvl + 1], c, 3)
        for i in range(m["blocks_per_level"]):
            f += _res(hh, ww, 2 * c if i == 0 else c, c, t)
            if lvl in m["attn_levels"]:
                f += _attn(hh * ww, c, nt, dt)
    return f + _conv(h, w, W, C0, 3)


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
