"""The program's `unet` kind: `levels` levels of ResBlocks at width
`width * 2**level`, an attention block after each ResBlock of the levels in
`attn_levels`, a stride-2 convolution down and nearest-neighbour upsampling
with a convolution up, and one skip per up level, concatenated before its
first ResBlock. Sampled by DDIM."""
from __future__ import annotations

import jax.numpy as jnp

from bench.models import _layers as L

SAMPLER = "ddim"


def _chans(m):
    return [m["width"] * 2 ** lvl for lvl in range(m["levels"])]


def weight_spec(m):
    spec = []
    t, W, C0 = m["t_dim"], m["width"], m["latent_channels"]
    L.time_mlp_spec(spec, t)
    levels = m["levels"]
    chans = _chans(m)
    L.conv_spec(spec, "stem", 3, C0, W)
    for lvl in range(levels):
        c = chans[lvl]
        for i in range(m["blocks_per_level"]):
            L.res_spec(spec, f"down{lvl}_res{i}", c, c, t)
            if lvl in m["attn_levels"]:
                L.attn_spec(spec, f"down{lvl}_attn{i}", c, m["d_text"])
        if lvl + 1 < levels:
            L.conv_spec(spec, f"down{lvl}_ds", 3, c, chans[lvl + 1])
    cm = chans[-1]
    L.res_spec(spec, "mid_res1", cm, cm, t)
    L.attn_spec(spec, "mid_attn", cm, m["d_text"])
    L.res_spec(spec, "mid_res2", cm, cm, t)
    for lvl in reversed(range(levels)):
        c = chans[lvl]
        if lvl + 1 < levels:
            L.conv_spec(spec, f"up{lvl}_us", 3, chans[lvl + 1], c)
        for i in range(m["blocks_per_level"]):
            L.res_spec(spec, f"up{lvl}_res{i}", 2 * c if i == 0 else c, c, t)
            if lvl in m["attn_levels"]:
                L.attn_spec(spec, f"up{lvl}_attn{i}", c, m["d_text"])
    L.norm_spec(spec, "out_norm", W)
    L.conv_spec(spec, "out_conv", 3, W, C0)
    return spec


def forward(cfg, P, x, t, text, run):
    temb = L.time_mlp(cfg, P, t, x.dtype)
    levels, blocks = cfg["levels"], cfg["blocks_per_level"]
    attn = set(cfg["attn_levels"])
    x = run("stem", lambda z: L.conv(z, P["stem"]), x)
    skips = []
    for lvl in range(levels):
        for i in range(blocks):
            name = f"down{lvl}_res{i}"
            x = run(name, lambda z, n=name: L.res_block(cfg, P[n], z, temb), x)
            if lvl in attn:
                name = f"down{lvl}_attn{i}"
                x = run(name, lambda z, n=name: L.attn_block(cfg, P[n], z,
                                                             text), x)
        skips.append(x)
        if lvl + 1 < levels:
            name = f"down{lvl}_ds"
            # stride-2 SAME on an even size pads one row/col at the far end
            x = run(name, lambda z, n=name: L.conv(
                z, P[n], 2, ((0, 1), (0, 1))), x)
    x = run("mid_res1", lambda z: L.res_block(cfg, P["mid_res1"], z, temb), x)
    x = run("mid_attn", lambda z: L.attn_block(cfg, P["mid_attn"], z, text), x)
    x = run("mid_res2", lambda z: L.res_block(cfg, P["mid_res2"], z, temb), x)
    for lvl in reversed(range(levels)):
        if lvl + 1 < levels:
            name = f"up{lvl}_us"
            x = run(name, lambda z, n=name: L.conv(
                jnp.repeat(jnp.repeat(z, 2, axis=1), 2, axis=2), P[n]), x)
        for i in range(blocks):
            if i == 0:
                x = jnp.concatenate([x, skips[lvl]], axis=-1)
            name = f"up{lvl}_res{i}"
            x = run(name, lambda z, n=name: L.res_block(cfg, P[n], z, temb), x)
            if lvl in attn:
                name = f"up{lvl}_attn{i}"
                x = run(name, lambda z, n=name: L.attn_block(cfg, P[n], z,
                                                             text), x)
    return run("out", lambda z: L.conv(
        L.silu(L.group_norm(z, P["out_norm"], cfg["groups"])),
        P["out_conv"]), x)


def step_flops(m, h, w):
    t = m["t_dim"]
    f = L.time_mlp_flops(t)
    C0, W = m["latent_channels"], m["width"]
    nt, dt = m["n_text"], m["d_text"]
    levels = m["levels"]
    chans = _chans(m)
    f += L.conv_flops(h, w, C0, W, 3)
    for lvl in range(levels):
        c, hh, ww = chans[lvl], h >> lvl, w >> lvl
        for _ in range(m["blocks_per_level"]):
            f += L.res_flops(hh, ww, c, c, t)
            if lvl in m["attn_levels"]:
                f += L.attn_flops(hh * ww, c, nt, dt)
        if lvl + 1 < levels:
            f += L.conv_flops(hh // 2, ww // 2, c, chans[lvl + 1], 3)
    cm, hm, wm = chans[-1], h >> (levels - 1), w >> (levels - 1)
    f += 2 * L.res_flops(hm, wm, cm, cm, t) + L.attn_flops(hm * wm, cm, nt, dt)
    for lvl in reversed(range(levels)):
        c, hh, ww = chans[lvl], h >> lvl, w >> lvl
        if lvl + 1 < levels:
            f += L.conv_flops(hh, ww, chans[lvl + 1], c, 3)
        for i in range(m["blocks_per_level"]):
            f += L.res_flops(hh, ww, 2 * c if i == 0 else c, c, t)
            if lvl in m["attn_levels"]:
                f += L.attn_flops(hh * ww, c, nt, dt)
    return f + L.conv_flops(h, w, W, C0, 3)


def kernel_shapes(cfg, patches_total):
    """GroupNorm-stitch on every level's patches at both widths a ResBlock
    sees; attention per resolution at the attention levels."""
    from bench.harness import gcd_patch
    m = cfg["model"]
    res = cfg["resolutions"]
    p = gcd_patch(res)
    gn, attn = set(), set()
    for lvl in range(m["levels"]):
        c = m["width"] * 2 ** lvl
        for cc in {c, 2 * c}:
            gn.add((patches_total, p >> lvl, p >> lvl, cc))
        if lvl in m["attn_levels"]:
            for h, w in res:
                attn.add((4, m["n_heads"], (h >> lvl) * (w >> lvl),
                          c // m["n_heads"]))
    return {"groupnorm_stitch": gn, "patch_attention": attn}
