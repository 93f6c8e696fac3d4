"""Samplers: DDIM (eps-prediction, UNet) and rectified-flow Euler (DiT).

Requests in one CSP batch sit at *different* step indices (paper Fig. 1);
all per-step coefficients are per-request vectors broadcast per patch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csp import CSP
from repro.models import diffusion as dm


def ddim_schedule(total_steps: int, T: int = 1000):
    betas = np.linspace(1e-4, 0.02, T, dtype=np.float64)
    ab = np.cumprod(1.0 - betas)
    ts = np.linspace(T - 1, 0, total_steps).round().astype(np.int64)
    return jnp.asarray(ts), jnp.asarray(ab[ts], jnp.float32)


@functools.partial(jax.jit, static_argnums=2)
def _flow_coeffs(step_req, seg, total_steps: int):
    """Rectified flow, t going 1 -> 0: the model's time per request and the
    step dt = t_next - t per patch."""
    t_cur = 1.0 - step_req.astype(jnp.float32) / total_steps
    t_next = 1.0 - (step_req.astype(jnp.float32) + 1) / total_steps
    return t_cur * 1000.0, (t_next - t_cur)[seg][:, None, None, None]


@jax.jit
def _flow_update(patches, dt, v):
    return patches + dt * v


@functools.partial(jax.jit, static_argnums=2)
def _ddim_coeffs(k, seg, total_steps: int):
    """DDIM: the model's time per request, alpha-bar now and next per
    patch."""
    ts, ab = ddim_schedule(total_steps)
    ab_k = ab[k][seg][:, None, None, None]
    ab_next = jnp.where(k + 1 < total_steps, ab[jnp.minimum(k + 1,
                                                            total_steps - 1)],
                        1.0)[seg][:, None, None, None]
    return ts[k].astype(jnp.float32), ab_k, ab_next


@jax.jit
def _ddim_update(patches, eps, ab_k, ab_next):
    x0 = (patches - jnp.sqrt(1 - ab_k) * eps) / jnp.sqrt(ab_k)
    return jnp.sqrt(ab_next) * x0 + jnp.sqrt(1 - ab_next) * eps


def sampler_step(cfg: dm.DiffusionConfig, params, csp: CSP,
                 patches: jax.Array, step_req: jax.Array, total_steps: int,
                 text: jax.Array, block_hook=None) -> jax.Array:
    """Advance every request one denoising step. step_req: (R,) int32, the
    number of steps already taken (0 .. total_steps-1)."""
    ix = dm.csp_arrays(csp)
    seg = ix["patch_req"]
    if cfg.kind == "dit":
        t_model, dt = _flow_coeffs(step_req, seg, total_steps)
        v = dm.denoise_patched(cfg, params, csp, patches, t_model, text,
                               block_hook, ix)
        return _flow_update(patches, dt, v)
    # DDIM (eta=0)
    t_model, ab_k, ab_next = _ddim_coeffs(step_req, seg, total_steps)
    eps = dm.denoise_patched(cfg, params, csp, patches, t_model, text,
                             block_hook, ix)
    return _ddim_update(patches, eps, ab_k, ab_next)
