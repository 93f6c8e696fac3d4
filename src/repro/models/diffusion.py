"""Latent diffusion backbones with native patched execution.

Three kinds, mirroring the paper's evaluation models:
- ``unet``: a generic UNet at ``width * 2**level`` channels. Each level has
  ``blocks_per_level`` ResBlocks (GroupNorm->SiLU->Conv3x3, timestep
  scale-shift), each followed at ``attn_levels`` by one transformer block
  (image-level self-attn via CSP groups, per-request cross-attn to text, a
  GELU feed-forward under GroupNorm); one skip per up level, taken by its
  first ResBlock.
- ``sdxl``: SDXL-base's ``UNet2DConditionModel`` block: ResBlocks with an
  additive time projection, Transformer2D stacks of ``tf_depth[level]``
  LayerNorm/GEGLU layers with heads of ``head_dim`` between ``proj_in`` and
  ``proj_out``, a padding-1 stride-2 Downsample, ``blocks_per_level + 1`` up
  ResBlocks per level, each taking one of the skips, and the time MLP widened
  to ``temb_dim`` plus the added conditioning from the pooled text and the
  request's size ids.
- ``dit`` (SD3-analogue): pure transformer over 1x1-pixel tokens with
  adaLN timestep modulation — no convolution, so patched execution is
  bitwise-equal to unpatched (the paper's "SD3 inf PSNR" row).

In both UNets convolutions consume stitched halos; GroupNorm uses exact CSP
stats (or the paper's per-patch mode).

Every block is registered with a *kind* so the serving engine knows its
patch semantics: "pixel" blocks are per-patch independent (maskable under
patch-level cache reuse), "context" blocks need full-image context
(cache-filled inputs, paper §5.1).

Requests inside one batch may sit at different denoising steps (paper
Fig. 1): the timestep embedding is per-request and broadcast per patch via
``csp.patch_req``.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import patched_ops, patching
from repro.core.csp import CSP, build_csp
from repro.core.stitcher import gather_halo
from repro.kernels import ops as kernel_ops
from repro.models.layers import ParamBuilder, layernorm


@dataclass(frozen=True)
class DiffusionConfig:
    name: str = "unet-lite"
    kind: str = "unet"            # unet | sdxl | dit
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
    # sdxl only: transformer layers per stack at each level (0: no stack),
    # the mid block taking the last level's; each head's width; the time
    # MLP's output width; the added conditioning's pooled-text width and
    # sinusoid width per size id
    tf_depth: Tuple[int, ...] = ()
    head_dim: int = 64
    temb_dim: int = 0
    d_pooled: int = 0
    d_size_id: int = 0
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


def _linear_init(b: ParamBuilder, path: str, cin, cout, bias=True):
    b.make(path, (cin, cout), (None, "ff"))
    if bias:
        b.make(f"{path}_b", (cout,), ("ff",), init="zeros")


def _res_add_init(b: ParamBuilder, path: str, cin, cout, temb_dim):
    """SDXL's ResnetBlock2D: its time projection is added, one vector."""
    _gn_init(b, f"{path}/gn1", cin)
    _conv_init(b, f"{path}/conv1", 3, 3, cin, cout)
    b.make(f"{path}/temb_w", (temb_dim, cout), (None, "ff"))
    b.make(f"{path}/temb_b", (cout,), ("ff",), init="zeros")
    _gn_init(b, f"{path}/gn2", cout)
    _conv_init(b, f"{path}/conv2", 3, 3, cout, cout)
    if cin != cout:
        _conv_init(b, f"{path}/skip", 1, 1, cin, cout)


def _tf_init(b: ParamBuilder, path: str, c, depth, d_text):
    """SDXL's Transformer2DModel: GroupNorm, proj_in, ``depth`` layers
    ``l0, l1, ...`` of self-attention, cross-attention and GEGLU, each under
    a LayerNorm, and proj_out."""
    _gn_init(b, f"{path}/gn", c)
    _linear_init(b, f"{path}/proj_in", c, c)
    for j in range(depth):
        q = f"{path}/l{j}"
        _gn_init(b, f"{q}/ln1", c)
        for n in ("wq", "wk", "wv"):
            _linear_init(b, f"{q}/{n}", c, c, bias=False)
        _linear_init(b, f"{q}/wo", c, c)
        _gn_init(b, f"{q}/ln2", c)
        _linear_init(b, f"{q}/xq", c, c, bias=False)
        _linear_init(b, f"{q}/xk", d_text, c, bias=False)
        _linear_init(b, f"{q}/xv", d_text, c, bias=False)
        _linear_init(b, f"{q}/xo", c, c)
        _gn_init(b, f"{q}/ln3", c)
        _linear_init(b, f"{q}/ff1", c, 8 * c)
        _linear_init(b, f"{q}/ff2", 4 * c, c)
    _linear_init(b, f"{path}/proj_out", c, c)


def _sdxl_init(b: ParamBuilder, cfg: DiffusionConfig):
    T = cfg.temb_dim
    for path, cin in (("temb", cfg.t_dim),
                      ("add", cfg.d_pooled + 6 * cfg.d_size_id)):
        b.make(f"{path}_w1", (cin, T), (None, None))
        b.make(f"{path}_b1", (T,), (None,), init="zeros")
        b.make(f"{path}_w2", (T, T), (None, None))
        b.make(f"{path}_b2", (T,), (None,), init="zeros")
    for name, level, cin, cout in sdxl_blocks(cfg):
        if "_res" in name:
            _res_add_init(b, name, cin, cout, T)
        elif "_tf" in name:
            _tf_init(b, name, cout, cfg.tf_depth[level], cfg.d_text)
        elif name == "out":
            _gn_init(b, "out_norm", cin)
            _conv_init(b, "out_conv", 3, 3, cin, cout)
        else:                                   # conv_in, Down/Upsample
            _conv_init(b, name, 3, 3, cin, cout)
    return b.params


def init_diffusion(cfg: DiffusionConfig, key: jax.Array):
    b = ParamBuilder(key, jnp.dtype(cfg.dtype))
    C0 = cfg.latent_channels
    W = cfg.width
    if cfg.kind == "sdxl":
        return _sdxl_init(b, cfg)
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
# Compiled segments
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Composition:
    """The static structure of a CSP batch, the key its programs compile
    under: the patch side and the requests' resolutions in CSP order (groups,
    offsets and neighbors follow from them), seen at a UNet ``level``."""
    patch: int
    res: Tuple[Tuple[int, int], ...]
    level: int = 0

    @staticmethod
    def of(csp: CSP) -> "Composition":
        return Composition(int(csp.patch),
                           tuple((int(h), int(w)) for h, w in csp.res))

    def view(self, ix) -> CSP:
        """The composition's CSP inside a trace: its layout is static, the
        index arrays the math reads are the traced ``ix``. A UNet level has
        the same grid and neighbors at 1/2**level the spatial size."""
        f = 2 ** self.level
        csp = build_csp([(h // f, w // f) for h, w in self.res],
                        patch=self.patch // f)
        return dataclasses.replace(csp, patch_req=ix["patch_req"],
                                   res=ix["res"] // f,
                                   neighbors=ix["neighbors"])


def csp_arrays(csp: CSP) -> dict:
    """The index arrays of ``csp`` that the segments read, on the device."""
    return jax.device_put({k: np.asarray(getattr(csp, k), np.int32)
                           for k in ("patch_req", "res", "neighbors")})


# Runs of each segment's Python body, i.e. its traces (compiles); the engine
# reads the total into ``serving.Metrics.block_traces``. Process-wide, as
# jit's own cache is.
SEGMENT_TRACES: collections.Counter = collections.Counter()


def segment_traces() -> int:
    return sum(SEGMENT_TRACES.values())


def _segment(fn):
    """``fn(cfg, csp, *args)`` as one compiled program per (config,
    composition), called as ``seg(cfg, comp, ix, *args)``. The config and
    the composition are the static key; the CSP's index arrays and every
    other argument, block weights included, are traced, so that blocks of
    one shape share one program."""
    def body(cfg, comp, ix, *args):
        SEGMENT_TRACES[fn.__name__] += 1
        return fn(cfg, comp.view(ix), *args)

    body.__name__ = body.__qualname__ = fn.__name__
    return jax.jit(body, static_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Patched block implementations
# ---------------------------------------------------------------------------
# A block with a Pallas kernel runs as compiled segments with each kernel
# call between them on the host, where the benchmark counts the calls; a
# block without one is one segment.

def _norm(cfg, csp: CSP, gp, x: jax.Array) -> jax.Array:
    return patched_ops.patched_groupnorm(csp, x, gp["scale"], gp["bias"],
                                         cfg.groups, exact=cfg.exact_stats)


def _gn_halo(cfg, csp: CSP, gp, x: jax.Array) -> jax.Array:
    """GroupNorm + halo on the jnp path: (P, p+2, p+2, C)."""
    return gather_halo(_norm(cfg, csp, gp, x), csp.neighbors)


def _stats(cfg, csp: CSP, x: jax.Array):
    """Per-patch (mean, rstd) for the fused GroupNorm + halo kernel."""
    return kernel_ops.groupnorm_stats(csp, x, cfg.groups,
                                      exact=cfg.exact_stats)


def _gn_stitch(ix, gp, x: jax.Array, stats) -> jax.Array:
    """The fused GroupNorm + halo kernel: (P, p+2, p+2, C)."""
    return kernel_ops.groupnorm_stitch_kernel(x, ix["neighbors"], *stats,
                                              gp["scale"], gp["bias"])


def _conv_valid(p, h: jax.Array) -> jax.Array:
    return jax.lax.conv_general_dilated(
        h, p["w"], (1, 1), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]


def _res_in(csp: CSP, p, h: jax.Array, temb_p: jax.Array,
            additive: bool = False) -> jax.Array:
    """From the first GroupNorm's haloed output to the second's input. The
    time projection scales and shifts, or with ``additive`` (SDXL) is
    added."""
    h = patched_ops.patched_conv(csp, None, p["conv1"]["w"], p["conv1"]["b"],
                                 haloed=jax.nn.silu(h))
    ss = jax.nn.silu(temb_p) @ p["temb_w"] + p["temb_b"]     # (P, 2C) or (P, C)
    if additive:
        return h + ss[:, None, None, :]
    scale, shift = jnp.split(ss, 2, axis=-1)
    return h * (1 + scale[:, None, None, :]) + shift[:, None, None, :]


def _res_out(csp: CSP, p, x: jax.Array, h: jax.Array) -> jax.Array:
    """From the second GroupNorm's haloed output to the block's output."""
    h = patched_ops.patched_conv(csp, None, p["conv2"]["w"], p["conv2"]["b"],
                                 haloed=jax.nn.silu(h))
    if "skip" in p:
        x = patched_ops.patched_conv(csp, x, p["skip"]["w"], p["skip"]["b"])
    return x + h


@_segment
def _res_block(cfg, csp: CSP, p, x: jax.Array,
               temb_p: jax.Array) -> jax.Array:
    """x: (P, s, s, Cin); temb_p: (P, t_dim). The jnp path, whole."""
    h = _res_in(csp, p, _gn_halo(cfg, csp, p["gn1"], x), temb_p)
    return _res_out(csp, p, x, _gn_halo(cfg, csp, p["gn2"], h))


@_segment
def _res_mid(cfg, csp: CSP, p, h: jax.Array, temb_p: jax.Array):
    h = _res_in(csp, p, h, temb_p)
    return h, _stats(cfg, csp, h)


@_segment
def _res_add_block(cfg, csp: CSP, p, x: jax.Array,
                   temb_p: jax.Array) -> jax.Array:
    """SDXL's ResBlock on the jnp path, whole."""
    h = _res_in(csp, p, _gn_halo(cfg, csp, p["gn1"], x), temb_p,
                additive=True)
    return _res_out(csp, p, x, _gn_halo(cfg, csp, p["gn2"], h))


@_segment
def _res_add_in(cfg, csp: CSP, p, h: jax.Array, temb_p: jax.Array):
    h = _res_in(csp, p, h, temb_p, additive=True)
    return h, _stats(cfg, csp, h)


@_segment
def _res_post(cfg, csp: CSP, p, x: jax.Array, h: jax.Array) -> jax.Array:
    return _res_out(csp, p, x, h)


_gn_stats = _segment(_stats)


def _res(cfg, comp, ix, p, x: jax.Array, temb_p: jax.Array) -> jax.Array:
    sdxl = cfg.kind == "sdxl"
    if not cfg.use_kernels:
        whole = _res_add_block if sdxl else _res_block
        return whole(cfg, comp, ix, p, x, temb_p)
    h = _gn_stitch(ix, p["gn1"], x, _gn_stats(cfg, comp, ix, x))
    h, stats = (_res_add_in if sdxl else _res_mid)(cfg, comp, ix, p, h,
                                                   temb_p)
    return _res_post(cfg, comp, ix, p, x, _gn_stitch(ix, p["gn2"], h, stats))


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


def _modulate(x: jax.Array, mod) -> jax.Array:
    """adaLN's scale and shift (the DiT's ``mod``); None leaves x as is."""
    if mod is None:
        return x
    sc, sh, _ = mod
    return x * (1 + sc[:, None, None, :]) + sh[:, None, None, :]


def _attn_tail(cfg, csp: CSP, p, x: jax.Array, h: jax.Array, text, mod):
    """After self-attention: cross-attention, the feed-forward with its
    GroupNorm, and adaLN's gated residual around the block."""
    P, s, _, C = h.shape
    h = _cross_attn(csp, p, h, text, cfg.n_heads)
    hn = _norm(cfg, csp, p["gn_ff"], h)
    ff = jax.nn.gelu(hn.reshape(P, s * s, C) @ p["ff1"]) @ p["ff2"]
    h = h + ff.reshape(P, s, s, C)
    if mod is None:
        return h
    return x + mod[2][:, None, None, :] * (h - x)


@_segment
def _attn_block(cfg, csp: CSP, p, x: jax.Array, text: jax.Array, mod):
    """A transformer block on the jnp path, whole. ``mod``: the DiT's adaLN
    (scale, shift, gate) per patch, or None."""
    h = _norm(cfg, csp, p["gn"], _modulate(x, mod))
    h = h + patched_ops.grouped_self_attention(
        csp, h, p["wq"], p["wk"], p["wv"], p["wo"], cfg.n_heads)
    return _attn_tail(cfg, csp, p, x, h, text, mod)


def _group_qkv(csp: CSP, p, h: jax.Array, n_heads: int):
    """Each resolution group's Q, K, V for the attention kernel, as
    (images, heads, tokens, head_dim)."""
    C = h.shape[-1]
    qkv = []
    for g in range(csp.n_groups):
        imgs = patching.group_images(csp, h, g)
        n, H, W, _ = imgs.shape
        t = imgs.reshape(n, H * W, C)
        qkv.append(tuple(
            (t @ p[w]).reshape(n, H * W, n_heads,
                               C // n_heads).transpose(0, 2, 1, 3)
            for w in ("wq", "wk", "wv")))
    return qkv


def _ungroup_out(csp: CSP, outs, wo: jax.Array) -> jax.Array:
    """The attention kernel's outputs per group, projected by ``wo``, back in
    patch order: (P, p, p, C)."""
    parts = []
    for g, o in enumerate(outs):
        n, _, S, _ = o.shape
        H, W = map(int, csp.group_res[g])
        o = o.transpose(0, 2, 1, 3).reshape(n, S, -1) @ wo
        parts.append(patching.ungroup_images(csp, o.reshape(n, H, W, -1), g))
    return jnp.concatenate(parts, axis=0)


@_segment
def _attn_pre(cfg, csp: CSP, p, x: jax.Array, mod):
    """Up to the attention kernel: the normalized input, and each resolution
    group's Q, K, V."""
    h = _norm(cfg, csp, p["gn"], _modulate(x, mod))
    return h, _group_qkv(csp, p, h, cfg.n_heads)


@_segment
def _attn_post(cfg, csp: CSP, p, x: jax.Array, h: jax.Array, outs, text,
               mod):
    """From the attention kernel's outputs to the block's output."""
    h = h + _ungroup_out(csp, outs, p["wo"])
    return _attn_tail(cfg, csp, p, x, h, text, mod)


def _attn(cfg, comp, ix, p, x: jax.Array, text: jax.Array,
          mod=None) -> jax.Array:
    if not cfg.use_kernels:
        return _attn_block(cfg, comp, ix, p, x, text, mod)
    h, qkv = _attn_pre(cfg, comp, ix, p, x, mod)
    outs = [kernel_ops.grouped_attention_kernel(*t) for t in qkv]
    return _attn_post(cfg, comp, ix, p, x, h, outs, text, mod)


# SDXL's Transformer2DModel: GroupNorm and proj_in (``_tf_in``), then per
# layer LayerNorm and Q, K, V (``_tf_pre``), one attention kernel call per
# resolution group, the output projection, cross-attention and the GEGLU
# feed-forward (``_tf_post``), and proj_out with the residual (``_tf_out``).

TF_GN_EPS = 1e-6        # the stack's GroupNorm; its LayerNorms take 1e-5


def _stack_layers(p):
    """A stack's layers in order, its keys l0, l1, ..."""
    return [p[f"l{j}"] for j in range(len(p)) if f"l{j}" in p]


def _xattn(csp: CSP, p, h: jax.Array, text: jax.Array,
           n_heads: int) -> jax.Array:
    """Cross-attention of each patch's pixels to its request's prompt; the
    prompt's keys and values are made once per request. text: (R, T, d)."""
    P, s, _, C = h.shape
    hd = C // n_heads
    seg = csp.patch_req
    k = (text @ p["xk"])[seg].reshape(P, -1, n_heads, hd)
    v = (text @ p["xv"])[seg].reshape(P, -1, n_heads, hd)
    q = (h.reshape(P, s * s, C) @ p["xq"]).reshape(P, s * s, n_heads, hd)
    w = jax.nn.softmax(jnp.einsum("pqhd,pkhd->phqk", q, k) * hd ** -0.5, -1)
    o = jnp.einsum("phqk,pkhd->pqhd", w, v).reshape(P, s, s, C)
    return o @ p["xo"] + p["xo_b"]


def _tf_tail(cfg, csp: CSP, p, h: jax.Array, text: jax.Array) -> jax.Array:
    """A stack layer after its self-attention: cross-attention and GEGLU."""
    heads = h.shape[-1] // cfg.head_dim
    ln2, ln3 = p["ln2"], p["ln3"]
    h = h + _xattn(csp, p, layernorm(h, ln2["scale"], ln2["bias"]), text,
                   heads)
    a, g = jnp.split(layernorm(h, ln3["scale"], ln3["bias"]) @ p["ff1"]
                     + p["ff1_b"], 2, axis=-1)
    return h + (a * jax.nn.gelu(g, approximate=False)) @ p["ff2"] \
        + p["ff2_b"]


def _tf_proj_in(cfg, csp: CSP, p, x: jax.Array) -> jax.Array:
    h = patched_ops.patched_groupnorm(csp, x, p["gn"]["scale"],
                                      p["gn"]["bias"], cfg.groups,
                                      eps=TF_GN_EPS, exact=cfg.exact_stats)
    return h @ p["proj_in"] + p["proj_in_b"]


@_segment
def _tf_block(cfg, csp: CSP, p, x: jax.Array, text: jax.Array) -> jax.Array:
    """A whole stack on the jnp path."""
    h = _tf_proj_in(cfg, csp, p, x)
    heads = h.shape[-1] // cfg.head_dim
    for q in _stack_layers(p):
        a = layernorm(h, q["ln1"]["scale"], q["ln1"]["bias"])
        h = h + patched_ops.grouped_self_attention(
            csp, a, q["wq"], q["wk"], q["wv"], q["wo"], heads) + q["wo_b"]
        h = _tf_tail(cfg, csp, q, h, text)
    return x + h @ p["proj_out"] + p["proj_out_b"]


@_segment
def _tf_in(cfg, csp: CSP, p, x: jax.Array) -> jax.Array:
    return _tf_proj_in(cfg, csp, p, x)


@_segment
def _tf_pre(cfg, csp: CSP, q, h: jax.Array):
    return _group_qkv(csp, q, layernorm(h, q["ln1"]["scale"],
                                        q["ln1"]["bias"]),
                      h.shape[-1] // cfg.head_dim)


@_segment
def _tf_post(cfg, csp: CSP, q, h: jax.Array, outs, text: jax.Array):
    h = h + _ungroup_out(csp, outs, q["wo"]) + q["wo_b"]
    return _tf_tail(cfg, csp, q, h, text)


@_segment
def _tf_out(cfg, csp: CSP, p, x: jax.Array, h: jax.Array) -> jax.Array:
    return x + h @ p["proj_out"] + p["proj_out_b"]


def _tf(cfg, comp, ix, p, x: jax.Array, text: jax.Array) -> jax.Array:
    if not cfg.use_kernels:
        return _tf_block(cfg, comp, ix, p, x, text)
    # the stack's own weights apart from its layers', so that stacks of any
    # depth share the end segments' programs
    ends = {k: v for k, v in p.items() if not k.startswith("l")}
    h = _tf_in(cfg, comp, ix, ends, x)
    for q in _stack_layers(p):
        qkv = _tf_pre(cfg, comp, ix, q, h)
        outs = [kernel_ops.grouped_attention_kernel(*t) for t in qkv]
        h = _tf_post(cfg, comp, ix, q, h, outs, text)
    return _tf_out(cfg, comp, ix, ends, x, h)


@_segment
def _conv(cfg, csp: CSP, p, x: jax.Array) -> jax.Array:
    """A kxk convolution over patches with halos."""
    return patched_ops.patched_conv(csp, x, p["w"], p["b"])


@_segment
def _downsample(cfg, csp: CSP, p, x: jax.Array) -> jax.Array:
    """Stride-2 3x3 conv with halo: (P, s, s, C) -> (P, s/2, s/2, C').

    Matches image-level SAME stride-2 conv (XLA pads right/bottom only for
    even sizes): windows start on even global rows, so only the right/bottom
    halo participates — drop the left/top halo row+col.
    """
    h = gather_halo(x, csp.neighbors)[:, 1:, 1:, :]
    return jax.lax.conv_general_dilated(
        h, p["w"], (2, 2), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]


@_segment
def _down_pad1(cfg, csp: CSP, p, x: jax.Array) -> jax.Array:
    """Stride-2 3x3 conv with padding 1 on every side (SDXL's Downsample2D):
    (P, s, s, C) -> (P, s/2, s/2, C'). Windows start one row and column
    before each even global row and column, so only the left/top halo
    participates: drop the right/bottom halo row+col."""
    h = gather_halo(x, csp.neighbors)[:, :-1, :-1, :]
    return jax.lax.conv_general_dilated(
        h, p["w"], (2, 2), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]


@_segment
def _upsample(cfg, csp: CSP, p, x: jax.Array) -> jax.Array:
    """Nearest x2 then 3x3 conv (halo at the upsampled scale)."""
    up = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    return patched_ops.patched_conv(csp, up, p["w"], p["b"])


@_segment
def _out_block(cfg, csp: CSP, p, x: jax.Array) -> jax.Array:
    """The UNet's final GroupNorm, SiLU and conv on the jnp path, whole."""
    h = _gn_halo(cfg, csp, p["out_norm"], x)
    return _conv_valid(p["out_conv"], jax.nn.silu(h))


@_segment
def _out_conv(cfg, csp: CSP, p, h: jax.Array) -> jax.Array:
    return _conv_valid(p, jax.nn.silu(h))


def _out(cfg, comp, ix, p, x: jax.Array) -> jax.Array:
    if not cfg.use_kernels:
        return _out_block(cfg, comp, ix, p, x)
    h = _gn_stitch(ix, p["out_norm"], x, _gn_stats(cfg, comp, ix, x))
    return _out_conv(cfg, comp, ix, p["out_conv"], h)


@_segment
def _linear(cfg, csp: CSP, w: jax.Array, b: jax.Array,
            x: jax.Array) -> jax.Array:
    return x @ w + b


_groupnorm = _segment(_norm)


def _added_cond(cfg, csp: CSP, p, text: jax.Array) -> jax.Array:
    """SDXL's ``text_time`` conditioning per request: the pooled prompt (the
    last token's last ``d_pooled`` features) and a sinusoid of each size id
    (8h, 8w, 0, 0, 8h, 8w) from the request's latent size, through an MLP."""
    h, w = csp.res[:, 0] * 8, csp.res[:, 1] * 8
    ids = jnp.stack([h, w, 0 * h, 0 * w, h, w], axis=1)          # (R, 6)
    ids = timestep_embedding(ids.reshape(-1), cfg.d_size_id)
    pooled = text[:, -1, cfg.d_text - cfg.d_pooled:]
    a = jnp.concatenate([pooled, ids.reshape(pooled.shape[0], -1)], axis=-1)
    return jax.nn.silu(a @ p["add_w1"] + p["add_b1"]) @ p["add_w2"] \
        + p["add_b2"]


@_segment
def _embed(cfg, csp: CSP, p, t_req: jax.Array, text=None):
    """The timestep MLP per patch: (P, t_dim), for SDXL (P, temb_dim) with
    the added conditioning from ``text``, which only SDXL is given; for the
    DiT, adaLN's (scale, shift, gate) per patch instead."""
    temb = timestep_embedding(t_req, cfg.t_dim)
    temb = jax.nn.silu(temb @ p["temb_w1"] + p["temb_b1"])
    temb = temb @ p["temb_w2"] + p["temb_b2"]                    # (R, t_dim)
    if cfg.kind == "dit":
        mod = jax.nn.silu(temb) @ p["adaln_w"] + p["adaln_b"]
        return tuple(jnp.split(mod[csp.patch_req], 3, axis=-1))
    if cfg.kind == "sdxl":
        temb = temb + _added_cond(cfg, csp, p, text)
    return temb[csp.patch_req]


# ---------------------------------------------------------------------------
# Block plan + forward
# ---------------------------------------------------------------------------

def sdxl_blocks(cfg: DiffusionConfig) -> List[Tuple[str, int, int, int]]:
    """[(name, level, cin, cout)] of the ``sdxl`` kind's cached blocks in
    the order they run. A Transformer2D stack is one block; an up ResBlock's
    input holds the skip it takes, popped from those pushed after
    ``conv_in``, each down ResBlock (or its stack) and each Downsample."""
    W, L, n = cfg.width, cfg.levels, cfg.blocks_per_level
    ch = [W * 2 ** lvl for lvl in range(L)]
    plan = [("conv_in", 0, cfg.latent_channels, W)]
    skips, c = [W], W
    for lvl in range(L):
        for i in range(n):
            plan.append((f"down{lvl}_res{i}", lvl, c, ch[lvl]))
            c = ch[lvl]
            if cfg.tf_depth[lvl]:
                plan.append((f"down{lvl}_tf{i}", lvl, c, c))
            skips.append(c)
        if lvl + 1 < L:
            plan.append((f"down{lvl}_ds", lvl, c, c))
            skips.append(c)
    plan += [("mid_res0", L - 1, c, c), ("mid_tf", L - 1, c, c),
             ("mid_res1", L - 1, c, c)]
    for lvl in reversed(range(L)):
        for i in range(n + 1):
            plan.append((f"up{lvl}_res{i}", lvl, c + skips.pop(), ch[lvl]))
            c = ch[lvl]
            if cfg.tf_depth[lvl]:
                plan.append((f"up{lvl}_tf{i}", lvl, c, c))
        if lvl:
            plan.append((f"up{lvl}_us", lvl - 1, c, c))
    plan.append(("out", 0, W, cfg.latent_channels))
    return plan


def block_plan(cfg: DiffusionConfig) -> List[Tuple[str, str, int]]:
    """[(name, kind, level)]; kind: 'pixel' | 'context'. The engine's cache
    manager keys caches by block name and treats kinds differently (§5.1)."""
    if cfg.kind == "sdxl":
        return [(name, "context", lvl) for name, lvl, _, _ in sdxl_blocks(cfg)]
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
                    block_hook: Optional[Callable] = None,
                    ix: Optional[dict] = None) -> jax.Array:
    """One model evaluation on a CSP patch batch.

    t_req: (R,) timestep per request (mixed steps in one batch, Fig. 1);
    text: (R, n_text, d_text). block_hook(name, kind, fn, x) -> x lets the
    cache manager interpose per block (None = plain execution). ix:
    ``csp_arrays(csp)``, made here when not given.
    """
    comp = Composition.of(csp)
    ix = csp_arrays(csp) if ix is None else ix
    emb = _embed(cfg, comp, ix, {k: v for k, v in params.items()
                                 if k.startswith(("temb_", "adaln_", "add_"))},
                 t_req, *([text] if cfg.kind == "sdxl" else []))
    run = block_hook or _plain_block

    def block(name, fn, x, *rest, level=0):
        c = dataclasses.replace(comp, level=level)
        return run(name, "context",
                   lambda xx: fn(cfg, c, ix, params[name], xx, *rest), x)

    if cfg.kind == "dit":
        x = run("tok_in", "pixel", lambda xx: _linear(
            cfg, comp, ix, params["tok_in"], params["tok_in_b"], xx), patches)
        for i in range(cfg.dit_depth):
            x = block(f"blk{i}", _attn, x, text, emb)
        x = _groupnorm(cfg, comp, ix, params["out_norm"], x)
        return run("tok_out", "pixel", lambda xx: _linear(
            cfg, comp, ix, params["tok_out"], params["tok_out_b"], xx), x)

    if cfg.kind == "sdxl":
        return _sdxl_forward(cfg, block, params, comp, ix, patches, emb, text,
                             run)

    # unet
    x = block("stem", _conv, patches)
    skips = []
    for lvl in range(cfg.levels):
        for i in range(cfg.blocks_per_level):
            x = block(f"down{lvl}_res{i}", _res, x, emb, level=lvl)
            if lvl in cfg.attn_levels:
                x = block(f"down{lvl}_attn{i}", _attn, x, text, level=lvl)
        skips.append(x)
        if lvl + 1 < cfg.levels:
            x = block(f"down{lvl}_ds", _downsample, x, level=lvl)
    lm = cfg.levels - 1
    x = block("mid_res1", _res, x, emb, level=lm)
    x = block("mid_attn", _attn, x, text, level=lm)
    x = block("mid_res2", _res, x, emb, level=lm)
    for lvl in reversed(range(cfg.levels)):
        if lvl + 1 < cfg.levels:
            x = block(f"up{lvl}_us", _upsample, x, level=lvl)
        for i in range(cfg.blocks_per_level):
            if i == 0:
                x = jnp.concatenate([x, skips[lvl]], axis=-1)
            x = block(f"up{lvl}_res{i}", _res, x, emb, level=lvl)
            if lvl in cfg.attn_levels:
                x = block(f"up{lvl}_attn{i}", _attn, x, text, level=lvl)
    out = {k: params[k] for k in ("out_norm", "out_conv")}
    return run("out", "context", lambda xx: _out(cfg, comp, ix, out, xx), x)


def _sdxl_forward(cfg, block, params, comp, ix, x, emb, text, run):
    """The ``sdxl`` kind's blocks in ``sdxl_blocks`` order."""
    L, depth = cfg.levels, cfg.tf_depth
    x = block("conv_in", _conv, x)
    skips = [x]
    for lvl in range(L):
        for i in range(cfg.blocks_per_level):
            x = block(f"down{lvl}_res{i}", _res, x, emb, level=lvl)
            if depth[lvl]:
                x = block(f"down{lvl}_tf{i}", _tf, x, text, level=lvl)
            skips.append(x)
        if lvl + 1 < L:
            x = block(f"down{lvl}_ds", _down_pad1, x, level=lvl)
            skips.append(x)
    x = block("mid_res0", _res, x, emb, level=L - 1)
    x = block("mid_tf", _tf, x, text, level=L - 1)
    x = block("mid_res1", _res, x, emb, level=L - 1)
    for lvl in reversed(range(L)):
        for i in range(cfg.blocks_per_level + 1):
            x = jnp.concatenate([x, skips.pop()], axis=-1)
            x = block(f"up{lvl}_res{i}", _res, x, emb, level=lvl)
            if depth[lvl]:
                x = block(f"up{lvl}_tf{i}", _tf, x, text, level=lvl)
        if lvl:
            x = block(f"up{lvl}_us", _upsample, x, level=lvl - 1)
    out = {k: params[k] for k in ("out_norm", "out_conv")}
    return run("out", "context", lambda xx: _out(cfg, comp, ix, out, xx), x)


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
