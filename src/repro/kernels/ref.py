"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def ref_groupnorm_stitch(patches, neighbors, mean_c, rstd_c, scale, bias,
                         halo: int = 1):
    """Normalize (per-patch per-channel stats) then halo-gather."""
    from repro.core.stitcher import gather_halo
    P, p, _, C = patches.shape
    x = patches.astype(jnp.float32)
    normed = ((x - mean_c[:, None, None, :]) * rstd_c[:, None, None, :]
              * scale.astype(jnp.float32) + bias.astype(jnp.float32)
              ).astype(patches.dtype)
    return gather_halo(normed, np.asarray(neighbors), halo)


def ref_attention(q, k, v, scale=None):
    """q,k,v: (B, H, S, D) full bidirectional attention, fp32 softmax."""
    D = q.shape[-1]
    sc = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sc
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)
