"""Plain reference of the served models, for the check that decides `correct`.

Whole images, one request at a time, in straightforward `jax.numpy`: no
patches, no halo stitch, no kernels, no batching. It imports nothing of the
program. It follows the model as the program defines it (repro's `unet` and
`dit` kinds), the DDIM and flow-Euler samplers and the stub VAE decoder.

The patch cache reuses a block's cached output for a patch whose input moved
by less than `tau` (relative mean square) since that patch was last computed.
The reference applies the same rule to its own activations. A decision whose
delta lies within `band` of `tau` (|delta / tau - 1| < band) is one that
rounding alone can flip, so there the reference follows the decision the
served side made; everywhere else it decides for itself.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

GN_EPS = 1e-5


# ---------------------------------------------------------------------------
# Layers on whole images (1, H, W, C)
# ---------------------------------------------------------------------------

def group_norm(x, p, groups):
    n, h, w, c = x.shape
    g = x.reshape(n, h * w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 3), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + GN_EPS)
    return g.reshape(n, h, w, c) * p["scale"] + p["bias"]


def conv(x, p, stride=1, padding="SAME"):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def attention(q, k, v, heads):
    """q (n, S, C), k/v (n, T, C) -> (n, S, C); softmax over T."""
    n, s, c = q.shape
    d = c // heads
    q = q.reshape(n, s, heads, d)
    k = k.reshape(n, -1, heads, d)
    v = v.reshape(n, -1, heads, d)
    w = jax.nn.softmax(jnp.einsum("nshd,nthd->nhst", q, k) / math.sqrt(d), -1)
    return jnp.einsum("nhst,nthd->nshd", w, v).reshape(n, s, c)


def timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    a = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(a), jnp.sin(a)], axis=-1)


def res_block(cfg, p, x, temb):
    h = conv(silu(group_norm(x, p["gn1"], cfg["groups"])), p["conv1"])
    ss = silu(temb) @ p["temb_w"] + p["temb_b"]
    scale, shift = jnp.split(ss, 2, axis=-1)
    h = h * (1 + scale[:, None, None]) + shift[:, None, None]
    h = conv(silu(group_norm(h, p["gn2"], cfg["groups"])), p["conv2"])
    if "skip" in p:
        x = jnp.einsum("nhwc,cd->nhwd", x, p["skip"]["w"][0, 0]) + p["skip"]["b"]
    return x + h


def attn_block(cfg, p, x, text):
    """Normalise, self-attend over the whole image, cross-attend to the
    prompt, feed forward. As in the program, the self-attention residual
    starts from the normalised input."""
    n, hh, ww, c = x.shape
    heads = cfg["n_heads"]
    h = group_norm(x, p["gn"], cfg["groups"]).reshape(n, hh * ww, c)
    h = h + attention(h @ p["wq"], h @ p["wk"], h @ p["wv"], heads) @ p["wo"]
    h = h + attention(h @ p["xq"], text @ p["xk"], text @ p["xv"],
                      heads) @ p["xo"]
    hn = group_norm(h.reshape(n, hh, ww, c), p["gn_ff"], cfg["groups"])
    ff = gelu_tanh(hn.reshape(n, hh * ww, c) @ p["ff1"]) @ p["ff2"]
    return (h + ff).reshape(n, hh, ww, c)


# ---------------------------------------------------------------------------
# Backbones; `run(name, fn, x)` interposes the patch cache at each block
# ---------------------------------------------------------------------------

def time_mlp(cfg, P, t, dtype):
    temb = timestep_embedding(t, cfg["t_dim"]).astype(dtype)
    temb = silu(temb @ P["temb_w1"] + P["temb_b1"])
    return temb @ P["temb_w2"] + P["temb_b2"]


def unet(cfg, P, x, t, text, run):
    temb = time_mlp(cfg, P, t, x.dtype)
    levels, blocks = cfg["levels"], cfg["blocks_per_level"]
    attn = set(cfg["attn_levels"])
    x = run("stem", lambda z: conv(z, P["stem"]), x)
    skips = []
    for lvl in range(levels):
        for i in range(blocks):
            name = f"down{lvl}_res{i}"
            x = run(name, lambda z, n=name: res_block(cfg, P[n], z, temb), x)
            if lvl in attn:
                name = f"down{lvl}_attn{i}"
                x = run(name, lambda z, n=name: attn_block(cfg, P[n], z, text), x)
        skips.append(x)
        if lvl + 1 < levels:
            name = f"down{lvl}_ds"
            # stride-2 SAME on an even size pads one row/col at the far end
            x = run(name, lambda z, n=name: conv(
                z, P[n], 2, ((0, 1), (0, 1))), x)
    x = run("mid_res1", lambda z: res_block(cfg, P["mid_res1"], z, temb), x)
    x = run("mid_attn", lambda z: attn_block(cfg, P["mid_attn"], z, text), x)
    x = run("mid_res2", lambda z: res_block(cfg, P["mid_res2"], z, temb), x)
    for lvl in reversed(range(levels)):
        if lvl + 1 < levels:
            name = f"up{lvl}_us"
            x = run(name, lambda z, n=name: conv(
                jnp.repeat(jnp.repeat(z, 2, axis=1), 2, axis=2), P[n]), x)
        for i in range(blocks):
            if i == 0:
                x = jnp.concatenate([x, skips[lvl]], axis=-1)
            name = f"up{lvl}_res{i}"
            x = run(name, lambda z, n=name: res_block(cfg, P[n], z, temb), x)
            if lvl in attn:
                name = f"up{lvl}_attn{i}"
                x = run(name, lambda z, n=name: attn_block(cfg, P[n], z, text), x)
    return run("out", lambda z: conv(
        silu(group_norm(z, P["out_norm"], cfg["groups"])), P["out_conv"]), x)


def dit(cfg, P, x, t, text, run):
    temb = time_mlp(cfg, P, t, x.dtype)
    x = run("tok_in", lambda z: z @ P["tok_in"] + P["tok_in_b"], x)
    mod = silu(temb) @ P["adaln_w"] + P["adaln_b"]
    sc, sh, gate = (m[:, None, None] for m in jnp.split(mod, 3, axis=-1))
    for i in range(cfg["dit_depth"]):
        name = f"blk{i}"

        def blk(z, n=name):
            h = attn_block(cfg, P[n], z * (1 + sc) + sh, text)
            return z + gate * (h - z)

        x = run(name, blk, x)
    x = group_norm(x, P["out_norm"], cfg["groups"])
    return run("tok_out", lambda z: z @ P["tok_out"] + P["tok_out_b"], x)


# ---------------------------------------------------------------------------
# Samplers
# ---------------------------------------------------------------------------

def ddim_tables(steps, T=1000):
    betas = np.linspace(1e-4, 0.02, T, dtype=np.float64)
    ab = np.cumprod(1.0 - betas)
    ts = np.linspace(T - 1, 0, steps).round().astype(np.int64)
    ab_next = np.append(ab[ts][1:], 1.0)
    return (np.float32(ts), np.float32(ab[ts]), np.float32(ab_next))


def sampler_update(cfg, steps, k, x, model_out):
    """x_{k+1} from x_k and the backbone's output, in float32."""
    if cfg["kind"] == "dit":
        return x + (-1.0 / steps) * model_out       # flow: t 1 -> 0
    _, ab, ab_next = (jnp.asarray(a) for a in ddim_tables(steps))
    a, an = ab[k], ab_next[k]
    x0 = (x - jnp.sqrt(1 - a) * model_out) / jnp.sqrt(a)
    return jnp.sqrt(an) * x0 + jnp.sqrt(1 - an) * model_out


def model_time(cfg, steps, k):
    if cfg["kind"] == "dit":
        return (1.0 - k.astype(jnp.float32) / steps) * 1000.0
    ts, _, _ = ddim_tables(steps)
    return jnp.asarray(ts)[k]


# ---------------------------------------------------------------------------
# The patch cache, applied to whole images
# ---------------------------------------------------------------------------

def patch_delta(x, cached, grid):
    """Relative mean square change per patch: (1, H, W, C) -> (gh, gw)."""
    gh, gw = grid
    _, h, w, c = x.shape
    d = (x.astype(jnp.float32) - cached.astype(jnp.float32)) ** 2
    q = cached.astype(jnp.float32) ** 2
    shape = (gh, h // gh, gw, w // gw, c)
    num = jnp.mean(d.reshape(shape), axis=(1, 3, 4))
    den = jnp.mean(q.reshape(shape), axis=(1, 3, 4)) + 1e-8
    return num / den


def _per_pixel(mask, like):
    """(gh, gw) patch mask -> broadcastable over (1, H, W, C)."""
    gh, gw = mask.shape
    _, h, w, _ = like.shape
    m = jnp.repeat(jnp.repeat(mask, h // gh, axis=0), w // gw, axis=1)
    return m[None, :, :, None]


def cached_step(cfg, steps, tau, band, params, x, text, k, stores, decided):
    """One denoising step of one request with patch-cache reuse.

    stores: {block: (input, output)} at the last compute of each patch
    (zeros before step 0). decided: {block: (gh, gw) bool}, the program's
    reuse decisions at this step. Returns (x_next, stores, stats) where stats
    has, per block, the reference's deltas and the number of decisions outside
    the band on which it disagreed with the program."""
    grid = cfg["grid"]
    dtype = jax.tree_util.tree_leaves(params)[0].dtype
    new_stores, deltas, disagree = {}, {}, {}

    def run(name, fn, z):
        cin, cout = stores[name]
        d = patch_delta(z, cin, grid)
        own = (d < tau) & (k > 0)
        near = jnp.abs(d / tau - 1.0) < band
        m = jnp.where(near & (k > 0), decided[name], own)
        mi = _per_pixel(m, z)
        y = fn(jnp.where(mi, cin, z))
        mo = _per_pixel(m, y)
        y = jnp.where(mo, cout, y)
        new_stores[name] = (jnp.where(mi, cin, z), y)
        deltas[name] = d
        disagree[name] = jnp.sum((decided[name] != own) & ~near & (k > 0))
        return y

    fwd = unet if cfg["kind"] == "unet" else dit
    t = model_time(cfg, steps, k)[None]
    out = fwd(cfg, params, x.astype(dtype), t, text.astype(dtype), run)
    x_next = sampler_update(cfg, steps, k, x, out.astype(jnp.float32))
    return x_next, new_stores, {"delta": deltas, "disagree": disagree}


def empty_stores(cfg, params, x):
    """Zero stores shaped like each block's input and output."""
    shapes = {}
    dtype = jax.tree_util.tree_leaves(params)[0].dtype

    def run(name, fn, z):
        y = fn(z)
        shapes[name] = (jax.ShapeDtypeStruct(z.shape, dtype),
                        jax.ShapeDtypeStruct(y.shape, dtype))
        return y

    fwd = unet if cfg["kind"] == "unet" else dit
    text = jax.ShapeDtypeStruct((1, cfg["n_text"], cfg["d_text"]), dtype)
    jax.eval_shape(lambda p, z, c: fwd(cfg, p, z.astype(dtype),
                                       jnp.zeros((1,)), c, run),
                   params, x, text)
    return {n: (jnp.zeros(a.shape, a.dtype), jnp.zeros(b.shape, b.dtype))
            for n, (a, b) in shapes.items()}


# ---------------------------------------------------------------------------
# Decoder
# ---------------------------------------------------------------------------

def vae_decode(vp, z):
    """(1, h, w, 4) latent -> (1, 8h, 8w, 3) image by pixel shuffle."""
    h = silu(conv(z, vp["conv1"]))
    h = conv(h, vp["conv2"])
    n, hh, ww, _ = h.shape
    h = h.reshape(n, hh, ww, 8, 8, 3).transpose(0, 1, 3, 2, 4, 5)
    return jnp.tanh(h.reshape(n, hh * 8, ww * 8, 3))


# ---------------------------------------------------------------------------
# A whole request
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg_key", "steps", "tau", "band"))
def _jit_step(cfg_key, steps, tau, band, params, x, text, k, stores, decided):
    return cached_step(dict(cfg_key), steps, tau, band, params, x, text, k,
                       stores, decided)


@jax.jit
def _jit_decode(vp, z):
    return vae_decode(vp, z)


def _frozen(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()
                        if not isinstance(v, dict)))


def serve_steps(cfg, steps, tau, band, params, text, noise, decisions,
                precision):
    """Every denoising step of one request from its noise.

    decisions: list over steps of {block: (gh, gw) bool}, the served side's
    reuse decisions. Returns ([latent after each step] as numpy, per-step
    stats as numpy)."""
    x = jnp.asarray(noise, jnp.float32)[None]
    text = jnp.asarray(text)[None]
    outs, stats = [], []
    with jax.default_matmul_precision(precision):
        stores = empty_stores(cfg, params, x)
        for k in range(steps):
            x, stores, st = _jit_step(_frozen(cfg), steps, tau, band, params,
                                      x, text, jnp.int32(k), stores,
                                      decisions[k])
            outs.append(x[0])
            stats.append(st)
    outs, stats = jax.device_get((outs, stats))
    return [np.asarray(o) for o in outs], stats


def decode(vae, latent, precision):
    """The decoded image (8h, 8w, 3) of one latent (h, w, C), as numpy."""
    dtype = jax.tree_util.tree_leaves(vae)[0].dtype
    with jax.default_matmul_precision(precision):
        z = jnp.asarray(latent, jnp.float32)[None].astype(dtype)
        img = _jit_decode(vae, z).astype(jnp.float32)
    return np.asarray(img[0])
