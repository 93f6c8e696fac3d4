"""Resolution-grouped patch attention — Pallas TPU flash kernel.

Used by the diffusion transformer blocks after CSP regrouping (paper §4.2):
each resolution group is an image batch whose tokens attend bidirectionally
within the image. Diffusion sequence lengths are modest (<= 4096 tokens for a
64x64 latent level), so the whole K/V for one (batch, head) fits VMEM: at
S=4096, D=32, f32 the double-buffered K/V, padded to 128 lanes, take 8 MiB of
v5e's 16 MiB default scoped VMEM. The grid is (B, H, nq) with a full-Sk K/V
block per program and an online-softmax ``fori_loop`` over KV chunks inside —
the classic TPU flash layout with q-block x MXU-aligned chunks.

Layout is (B, H, S, D): heads stay out of the block's last two dimensions,
which Mosaic requires to be (8, 128)-aligned or whole-array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, sk_valid: int,
            scale: float):
    # q_ref/o_ref: (1, 1, bq, D); k_ref/v_ref: (1, 1, Skp, D)
    bq, D = q_ref.shape[2:]
    Sk = k_ref.shape[2]
    q = q_ref[0, 0].astype(jnp.float32) * scale                 # (bq, D)

    def body(j, carry):
        m, l, acc = carry
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, 0, pl.ds(start, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(start, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        if sk_valid < Sk:                         # padded keys exist
            kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < sk_valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = corr * acc + jnp.dot(p, v,
                                       preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, D), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, Sk // block_k, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def patch_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jax.Array:
    """q,k,v: (B, H, S, D) full bidirectional attention -> (B, H, S, D)."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    scale = D ** -0.5

    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sqp, Skp = Sq + pq, Sk + pk

    fn = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, sk_valid=Sk, scale=scale),
        grid=(B, H, Sqp // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Skp, D), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Skp, D), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="patch_attention",
    )
    return fn(qp, kp, vp)[:, :, :Sq]
