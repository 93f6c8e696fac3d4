"""Plain reference of the served models, for the check that decides `correct`.

Whole images, one request at a time, in straightforward `jax.numpy`: no
patches, no halo stitch, no kernels, no batching. It imports nothing of the
program. Each model kind's backbone is its module's `forward`
(`bench/models/<kind>.py`); this file holds what the kinds share: the DDIM
and flow-Euler samplers, the patch cache's rule and the stub VAE decoder.

The patch cache reuses a block's cached output for a patch whose input moved
by less than `tau` (relative mean square) since that patch was last computed.
The reference applies the same rule to its own activations. A decision whose
delta lies within `band` of `tau` (|delta / tau - 1| < band) is one that
rounding alone can flip, so there the reference follows the decision the
served side made; everywhere else it decides for itself.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import kind
from bench.models._layers import conv, silu


# ---------------------------------------------------------------------------
# Samplers
# ---------------------------------------------------------------------------

def ddim_tables(steps, T=1000):
    betas = np.linspace(1e-4, 0.02, T, dtype=np.float64)
    ab = np.cumprod(1.0 - betas)
    ts = np.linspace(T - 1, 0, steps).round().astype(np.int64)
    ab_next = np.append(ab[ts][1:], 1.0)
    return (np.float32(ts), np.float32(ab[ts]), np.float32(ab_next))


def _sampler(cfg):
    sampler = kind(cfg["kind"]).SAMPLER
    if sampler not in ("ddim", "flow"):
        raise ValueError(f"model kind {cfg['kind']!r}: unknown sampler "
                         f"{sampler!r}")
    return sampler


def sampler_update(cfg, steps, k, x, model_out):
    """x_{k+1} from x_k and the backbone's output, in float32."""
    if _sampler(cfg) == "flow":
        return x + (-1.0 / steps) * model_out       # flow: t 1 -> 0
    _, ab, ab_next = (jnp.asarray(a) for a in ddim_tables(steps))
    a, an = ab[k], ab_next[k]
    x0 = (x - jnp.sqrt(1 - a) * model_out) / jnp.sqrt(a)
    return jnp.sqrt(an) * x0 + jnp.sqrt(1 - an) * model_out


def model_time(cfg, steps, k):
    if _sampler(cfg) == "flow":
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

    fwd = kind(cfg["kind"]).forward
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

    fwd = kind(cfg["kind"]).forward
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
