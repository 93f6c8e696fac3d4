"""Latent diffusion backbones with native patched execution.

Two families mirroring the paper's evaluation models:
- ``unet`` (SDXL-analogue): ResBlocks (GroupNorm->SiLU->Conv3x3, timestep
  scale-shift) + transformer blocks (image-level self-attn via CSP groups,
  per-request cross-attn to text, FF), one down/up level with skip.
  Convolutions consume stitched halos; GroupNorm uses exact CSP stats
  (or the paper's per-patch mode).
- ``dit`` (SD3-analogue): pure transformer over 1x1-pixel tokens with
  adaLN timestep modulation — no convolution, so patched execution is
  bitwise-equal to unpatched (the paper's "SD3 inf PSNR" row).

Every block is registered with a *kind* so the serving engine knows its
patch semantics: "pixel" blocks are per-patch independent (maskable under
patch-level cache reuse), "context" blocks need full-image context
(cache-filled inputs, paper §5.1).

Requests inside one batch may sit at different denoising steps (paper
Fig. 1): the timestep embedding is per-request and broadcast per patch via
``csp.patch_req``.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.csp import CSP
from repro.core import patched_ops
from repro.models.layers import ParamBuilder


@dataclass(frozen=True)
class DiffusionConfig:
    name: str = "unet-lite"
    kind: str = "unet"            # unet | dit
    latent_channels: int = 4
    width: int = 64               # base channel count
    levels: int = 2               # unet: resolution levels (1 down/up pair per extra)
    blocks_per_level: int = 2
    attn_levels: Tuple[int, ...] = (1,)   # levels with transformer blocks
    dit_depth: int = 8            # dit: number of blocks
    n_heads: int = 4
    groups: int = 8               # GroupNorm groups
    d_text: int = 64              # text-embedding width (stub encoder)
    n_text: int = 8               # text tokens per prompt
    t_dim: int = 128              # timestep embedding
    steps: int = 50               # default denoising steps
    exact_stats: bool = True      # exact CSP GroupNorm vs paper per-patch
    use_kernels: bool = True      # fused Pallas groupnorm+stitch path
    dtype: str = "float32"


SDXL_LITE = DiffusionConfig(name="sdxl-lite", kind="unet")
SD3_LITE = DiffusionConfig(name="sd3-lite", kind="dit", dit_depth=8, width=64)


def timestep_embedding(t: jax.Array, dim: int) -> jax.Array:
    """(R,) -> (R, dim) sinusoidal."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _conv_init(b: ParamBuilder, path: str, kh, kw, cin, cout):
    b.make(f"{path}/w", (kh, kw, cin, cout), (None, None, None, "ff"),
           scale=1.0 / math.sqrt(kh * kw * cin))
    b.make(f"{path}/b", (cout,), ("ff",), init="zeros")


def _gn_init(b: ParamBuilder, path: str, c):
    b.make(f"{path}/scale", (c,), (None,), init="ones")
    b.make(f"{path}/bias", (c,), (None,), init="zeros")


def _res_block_init(b: ParamBuilder, path: str, cin, cout, t_dim):
    _gn_init(b, f"{path}/gn1", cin)
    _conv_init(b, f"{path}/conv1", 3, 3, cin, cout)
    b.make(f"{path}/temb_w", (t_dim, 2 * cout), (None, "ff"))
    b.make(f"{path}/temb_b", (2 * cout,), ("ff",), init="zeros")
    _gn_init(b, f"{path}/gn2", cout)
    _conv_init(b, f"{path}/conv2", 3, 3, cout, cout)
    if cin != cout:
        _conv_init(b, f"{path}/skip", 1, 1, cin, cout)


def _attn_block_init(b: ParamBuilder, path: str, c, d_text):
    _gn_init(b, f"{path}/gn", c)
    for n in ("wq", "wk", "wv", "wo"):
        b.make(f"{path}/{n}", (c, c), (None, "ff"))
    b.make(f"{path}/xq", (c, c), (None, "ff"))
    b.make(f"{path}/xk", (d_text, c), (None, "ff"))
    b.make(f"{path}/xv", (d_text, c), (None, "ff"))
    b.make(f"{path}/xo", (c, c), (None, "ff"))
    _gn_init(b, f"{path}/gn_ff", c)
    b.make(f"{path}/ff1", (c, 4 * c), (None, "ff"))
    b.make(f"{path}/ff2", (4 * c, c), ("ff", None))


def init_diffusion(cfg: DiffusionConfig, key: jax.Array):
    b = ParamBuilder(key, jnp.dtype(cfg.dtype))
    C0 = cfg.latent_channels
    W = cfg.width
    b.make("temb_w1", (cfg.t_dim, cfg.t_dim), (None, None))
    b.make("temb_b1", (cfg.t_dim,), (None,), init="zeros")
    b.make("temb_w2", (cfg.t_dim, cfg.t_dim), (None, None))
    b.make("temb_b2", (cfg.t_dim,), (None,), init="zeros")

    if cfg.kind == "dit":
        b.make("tok_in", (C0, W), (None, None))
        b.make("tok_in_b", (W,), (None,), init="zeros")
        b.make("adaln_w", (cfg.t_dim, 3 * W), (None, None), scale=0.02)
        b.make("adaln_b", (3 * W,), (None,), init="zeros")
        for i in range(cfg.dit_depth):
            _attn_block_init(b, f"blk{i}", W, cfg.d_text)
        _gn_init(b, "out_norm", W)
        b.make("tok_out", (W, C0), (None, None), scale=0.02)
        b.make("tok_out_b", (C0,), (None,), init="zeros")
        return b.params

    # unet
    _conv_init(b, "stem", 3, 3, C0, W)
    chans = [W * (2 ** lvl) for lvl in range(cfg.levels)]
    for lvl in range(cfg.levels):
        cin = chans[lvl]
        for i in range(cfg.blocks_per_level):
            _res_block_init(b, f"down{lvl}_res{i}", cin, cin, cfg.t_dim)
            if lvl in cfg.attn_levels:
                _attn_block_init(b, f"down{lvl}_attn{i}", cin, cfg.d_text)
        if lvl + 1 < cfg.levels:
            _conv_init(b, f"down{lvl}_ds", 3, 3, cin, chans[lvl + 1])
    cm = chans[-1]
    _res_block_init(b, "mid_res1", cm, cm, cfg.t_dim)
    _attn_block_init(b, "mid_attn", cm, cfg.d_text)
    _res_block_init(b, "mid_res2", cm, cm, cfg.t_dim)
    for lvl in reversed(range(cfg.levels)):
        cin = chans[lvl]
        if lvl + 1 < cfg.levels:
            _conv_init(b, f"up{lvl}_us", 3, 3, chans[lvl + 1], cin)
        for i in range(cfg.blocks_per_level):
            # concat skip -> 2*cin input
            _res_block_init(b, f"up{lvl}_res{i}", 2 * cin if i == 0 else cin,
                            cin, cfg.t_dim)
            if lvl in cfg.attn_levels:
                _attn_block_init(b, f"up{lvl}_attn{i}", cin, cfg.d_text)
    _gn_init(b, "out_norm", W)
    _conv_init(b, "out_conv", 3, 3, W, C0)
    return b.params


# ---------------------------------------------------------------------------
# Patched block implementations
# ---------------------------------------------------------------------------

def _gn_stitch(cfg: DiffusionConfig, csp: CSP, x: jax.Array, gp) -> jax.Array:
    """GroupNorm + halo, fused kernel when enabled; returns (P,p+2,p+2,C)."""
    if cfg.use_kernels:
        from repro.kernels.ops import fused_groupnorm_stitch
        return fused_groupnorm_stitch(csp, x, gp["scale"], gp["bias"],
                                      cfg.groups, exact=cfg.exact_stats)
    from repro.core.stitcher import gather_halo
    n = patched_ops.patched_groupnorm(csp, x, gp["scale"], gp["bias"],
                                      cfg.groups, exact=cfg.exact_stats)
    return gather_halo(n, csp.neighbors)


def _res_block(cfg, csp: CSP, p, x: jax.Array, temb_p: jax.Array) -> jax.Array:
    """x: (P, s, s, Cin); temb_p: (P, t_dim)."""
    h = _gn_stitch(cfg, csp, x, p["gn1"])
    h = jax.nn.silu(h)
    h = patched_ops.patched_conv(csp, None, p["conv1"]["w"], p["conv1"]["b"],
                                 haloed=h)
    ss = jax.nn.silu(temb_p) @ p["temb_w"] + p["temb_b"]         # (P, 2C)
    scale, shift = jnp.split(ss, 2, axis=-1)
    h = h * (1 + scale[:, None, None, :]) + shift[:, None, None, :]
    h = _gn_stitch(cfg, csp, h, p["gn2"])
    h = jax.nn.silu(h)
    h = patched_ops.patched_conv(csp, None, p["conv2"]["w"], p["conv2"]["b"],
                                 haloed=h)
    if "skip" in p:
        x = patched_ops.patched_conv(csp, x, p["skip"]["w"], p["skip"]["b"])
    return x + h


def _cross_attn(csp: CSP, p, x: jax.Array, text: jax.Array,
                n_heads: int) -> jax.Array:
    """Pixel-wise cross-attention to the request's text tokens.
    x: (P, s, s, C); text: (R, T, d_text)."""
    P, s, _, C = x.shape
    hd = C // n_heads
    tx = text[jnp.asarray(csp.patch_req)]                        # (P, T, dt)
    q = (x.reshape(P, s * s, C) @ p["xq"]).reshape(P, s * s, n_heads, hd)
    k = jnp.einsum("ptd,dc->ptc", tx, p["xk"]).reshape(P, -1, n_heads, hd)
    v = jnp.einsum("ptd,dc->ptc", tx, p["xv"]).reshape(P, -1, n_heads, hd)
    sgn = jnp.einsum("pqhd,pkhd->phqk", q.astype(jnp.float32),
                     k.astype(jnp.float32)) * hd ** -0.5
    o = jnp.einsum("phqk,pkhd->pqhd", jax.nn.softmax(sgn, -1),
                   v.astype(jnp.float32))
    o = o.reshape(P, s * s, C).astype(x.dtype) @ p["xo"]
    return x + o.reshape(P, s, s, C)


def _self_attn(cfg, csp: CSP, p, x: jax.Array) -> jax.Array:
    """Image-level self-attention via CSP resolution groups."""
    C = x.shape[-1]
    if cfg.use_kernels:
        from repro.kernels.ops import grouped_attention_kernel
        hd = C // cfg.n_heads

        def attn(imgs, _):
            n, H, Wd, _ = imgs.shape
            t = imgs.reshape(n, H * Wd, C)

            def heads(w):                       # -> (n, heads, tokens, hd)
                return (t @ w).reshape(n, H * Wd, cfg.n_heads,
                                       hd).transpose(0, 2, 1, 3)

            o = grouped_attention_kernel(heads(p["wq"]), heads(p["wk"]),
                                         heads(p["wv"]))
            o = o.transpose(0, 2, 1, 3).reshape(n, H * Wd, C) @ p["wo"]
            return o.reshape(n, H, Wd, C)

        return x + patched_ops.per_image_apply(csp, x, attn)
    return x + patched_ops.grouped_self_attention(
        csp, x, p["wq"], p["wk"], p["wv"], p["wo"], cfg.n_heads)


def _attn_block(cfg, csp: CSP, p, x: jax.Array, text: jax.Array) -> jax.Array:
    P, s, _, C = x.shape
    h = patched_ops.patched_groupnorm(csp, x, p["gn"]["scale"], p["gn"]["bias"],
                                      cfg.groups, exact=cfg.exact_stats)
    h = _self_attn(cfg, csp, p, h)
    h = _cross_attn(csp, p, h, text, cfg.n_heads)
    hn = patched_ops.patched_groupnorm(csp, h, p["gn_ff"]["scale"],
                                       p["gn_ff"]["bias"], cfg.groups,
                                       exact=cfg.exact_stats)
    ff = jax.nn.gelu(hn.reshape(P, s * s, C) @ p["ff1"]) @ p["ff2"]
    return h + ff.reshape(P, s, s, C)


def _downsample(csp: CSP, p, x: jax.Array) -> jax.Array:
    """Stride-2 3x3 conv with halo: (P, s, s, C) -> (P, s/2, s/2, C').

    Matches image-level SAME stride-2 conv (XLA pads right/bottom only for
    even sizes): windows start on even global rows, so only the right/bottom
    halo participates — drop the left/top halo row+col.
    """
    from repro.core.stitcher import gather_halo
    h = gather_halo(x, csp.neighbors)[:, 1:, 1:, :]
    return jax.lax.conv_general_dilated(
        h, p["w"], (2, 2), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]


def _upsample(csp: CSP, p, x: jax.Array) -> jax.Array:
    """Nearest x2 then 3x3 conv (halo at the upsampled scale)."""
    P, s, _, C = x.shape
    up = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return patched_ops.patched_conv(csp, up, p["w"], p["b"])


def csp_at_level(csp: CSP, level: int) -> CSP:
    """Same grid/neighbors, halved spatial dims per level."""
    if level == 0:
        return csp
    f = 2 ** level
    return dataclasses.replace(csp, patch=csp.patch // f, res=csp.res // f,
                               group_res=csp.group_res // f)


# ---------------------------------------------------------------------------
# Block plan + forward
# ---------------------------------------------------------------------------

def block_plan(cfg: DiffusionConfig) -> List[Tuple[str, str, int]]:
    """[(name, kind, level)]; kind: 'pixel' | 'context'. The engine's cache
    manager keys caches by block name and treats kinds differently (§5.1)."""
    if cfg.kind == "dit":
        plan = [("tok_in", "pixel", 0)]
        plan += [(f"blk{i}", "context", 0) for i in range(cfg.dit_depth)]
        plan += [("tok_out", "pixel", 0)]
        return plan
    plan = [("stem", "context", 0)]
    for lvl in range(cfg.levels):
        for i in range(cfg.blocks_per_level):
            plan.append((f"down{lvl}_res{i}", "context", lvl))
            if lvl in cfg.attn_levels:
                plan.append((f"down{lvl}_attn{i}", "context", lvl))
        if lvl + 1 < cfg.levels:
            plan.append((f"down{lvl}_ds", "context", lvl))
    plan += [("mid_res1", "context", cfg.levels - 1),
             ("mid_attn", "context", cfg.levels - 1),
             ("mid_res2", "context", cfg.levels - 1)]
    for lvl in reversed(range(cfg.levels)):
        if lvl + 1 < cfg.levels:
            plan.append((f"up{lvl}_us", "context", lvl))
        for i in range(cfg.blocks_per_level):
            plan.append((f"up{lvl}_res{i}", "context", lvl))
            if lvl in cfg.attn_levels:
                plan.append((f"up{lvl}_attn{i}", "context", lvl))
    plan += [("out", "context", 0)]
    return plan


def _plain_block(name, kind, fn, x):
    """A block without a hook; its dispatch is the engine's ``ps.block``
    span (``repro.core.serving.SPANS``)."""
    with jax.profiler.TraceAnnotation("ps.block"):
        return fn(x)


def denoise_patched(cfg: DiffusionConfig, params, csp: CSP, patches: jax.Array,
                    t_req: jax.Array, text: jax.Array,
                    block_hook: Optional[Callable] = None) -> jax.Array:
    """One model evaluation on a CSP patch batch.

    t_req: (R,) timestep per request (mixed steps in one batch, Fig. 1);
    text: (R, n_text, d_text). block_hook(name, kind, fn, x) -> x lets the
    cache manager interpose per block (None = plain execution).
    """
    temb = timestep_embedding(t_req, cfg.t_dim)
    temb = jax.nn.silu(temb @ params["temb_w1"] + params["temb_b1"])
    temb = temb @ params["temb_w2"] + params["temb_b2"]          # (R, t_dim)
    temb_p = temb[jnp.asarray(csp.patch_req)]                    # (P, t_dim)

    run = block_hook or _plain_block

    if cfg.kind == "dit":
        x = run("tok_in", "pixel",
                lambda xx: xx @ params["tok_in"] + params["tok_in_b"], patches)
        mod = jax.nn.silu(temb) @ params["adaln_w"] + params["adaln_b"]
        sc, sh, gate = jnp.split(mod[jnp.asarray(csp.patch_req)], 3, axis=-1)
        for i in range(cfg.dit_depth):
            name = f"blk{i}"
            p = params[name]

            def blk(xx, p=p):
                h = xx * (1 + sc[:, None, None, :]) + sh[:, None, None, :]
                h = _attn_block(cfg, csp, p, h, text)
                return xx + gate[:, None, None, :] * (h - xx)

            x = run(name, "context", blk, x)
        x = patched_ops.patched_groupnorm(
            csp, x, params["out_norm"]["scale"], params["out_norm"]["bias"],
            cfg.groups, exact=cfg.exact_stats)
        return run("tok_out", "pixel",
                   lambda xx: xx @ params["tok_out"] + params["tok_out_b"], x)

    # unet
    x = run("stem", "context",
            lambda xx: patched_ops.patched_conv(csp, xx, params["stem"]["w"],
                                                params["stem"]["b"]), patches)
    skips = []
    level_csp = [csp_at_level(csp, lvl) for lvl in range(cfg.levels)]
    for lvl in range(cfg.levels):
        cl = level_csp[lvl]
        for i in range(cfg.blocks_per_level):
            x = run(f"down{lvl}_res{i}", "context",
                    lambda xx, lvl=lvl, i=i: _res_block(
                        cfg, level_csp[lvl], params[f"down{lvl}_res{i}"], xx, temb_p), x)
            if lvl in cfg.attn_levels:
                x = run(f"down{lvl}_attn{i}", "context",
                        lambda xx, lvl=lvl, i=i: _attn_block(
                            cfg, level_csp[lvl], params[f"down{lvl}_attn{i}"], xx,
                            text), x)
        skips.append(x)
        if lvl + 1 < cfg.levels:
            x = run(f"down{lvl}_ds", "context",
                    lambda xx, lvl=lvl: _downsample(level_csp[lvl],
                                                params[f"down{lvl}_ds"], xx), x)
    lm = cfg.levels - 1
    x = run("mid_res1", "context",
            lambda xx: _res_block(cfg, level_csp[lm], params["mid_res1"], xx,
                                  temb_p), x)
    x = run("mid_attn", "context",
            lambda xx: _attn_block(cfg, level_csp[lm], params["mid_attn"], xx,
                                   text), x)
    x = run("mid_res2", "context",
            lambda xx: _res_block(cfg, level_csp[lm], params["mid_res2"], xx,
                                  temb_p), x)
    for lvl in reversed(range(cfg.levels)):
        if lvl + 1 < cfg.levels:
            x = run(f"up{lvl}_us", "context",
                    lambda xx, lvl=lvl: _upsample(level_csp[lvl],
                                              params[f"up{lvl}_us"], xx), x)
        for i in range(cfg.blocks_per_level):
            if i == 0:
                x = jnp.concatenate([x, skips[lvl]], axis=-1)
            x = run(f"up{lvl}_res{i}", "context",
                    lambda xx, lvl=lvl, i=i: _res_block(
                        cfg, level_csp[lvl], params[f"up{lvl}_res{i}"], xx, temb_p), x)
            if lvl in cfg.attn_levels:
                x = run(f"up{lvl}_attn{i}", "context",
                        lambda xx, lvl=lvl, i=i: _attn_block(
                            cfg, level_csp[lvl], params[f"up{lvl}_attn{i}"], xx,
                            text), x)

    def out_fn(xx):
        h = _gn_stitch(cfg, csp, xx, params["out_norm"])
        h = jax.nn.silu(h)
        return jax.lax.conv_general_dilated(
            h, params["out_conv"]["w"], (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["out_conv"]["b"]

    return run("out", "context", out_fn, x)


def denoise_image(cfg: DiffusionConfig, params, imgs: jax.Array,
                  t: jax.Array, text: jax.Array) -> jax.Array:
    """Unpatched oracle: same-resolution batch (N, H, W, C) through a
    single-request-per-image CSP (each image = its own request)."""
    N, H, W, _ = imgs.shape
    csp, patches = _batch_csp(imgs)
    out = denoise_patched(cfg, params, csp, patches, t, text)
    from repro.core.patching import merge
    return jnp.stack(merge(csp, out), axis=0)


def _batch_csp(imgs: jax.Array):
    """Whole images as single-patch requests => unpatched semantics."""
    from repro.core.patching import split
    return split([imgs[i] for i in range(imgs.shape[0])],
                 patch=int(imgs.shape[1]))
