"""Resolution-grouped patch attention — Pallas TPU flash kernel.

Used by the diffusion transformer blocks after CSP regrouping (paper §4.2):
each resolution group is an image batch whose tokens attend bidirectionally
within the image. Diffusion sequence lengths are modest (<= 4096 tokens for a
64x64 latent level), so the whole K/V for one (batch, head) fits VMEM: at
S=4096 the double-buffered bfloat16 K/V, padded to 128 lanes, take 4 MiB of
v5e's 16 MiB default scoped VMEM. The grid is (B, H, nq) with a full-Sk K/V
block per program and an online-softmax ``fori_loop`` over KV chunks inside —
the classic TPU flash layout with q-block x MXU-aligned chunks.

Both products take their operands in ``mxu_dtype``: bfloat16 is one MXU pass,
float32 the MXU's f32 format at HIGHEST precision. The scores, the softmax
statistics and both accumulations are float32 either way, and the output
keeps the input's dtype.

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
LANES = 128
MAX_BLOCK = 512


def block_size(S: int) -> int:
    """The q or k block for sequence length S: the largest multiple of 128 up
    to 512 that divides S padded to 128, so that no block pads S further."""
    sp = -(-S // LANES) * LANES
    return max(b for b in range(LANES, MAX_BLOCK + 1, LANES) if sp % b == 0)


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_k: int, sk_valid: int,
            scale: float, precision):
    # q_ref/o_ref: (1, 1, bq, D); k_ref/v_ref: (1, 1, Skp, D)
    bq, D = q_ref.shape[2:]
    Sk = k_ref.shape[2]
    q = q_ref[0, 0]                                             # (bq, D)

    def chunk(j, carry, masked):
        m, l, acc = carry
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, 0, pl.ds(start, block_k), :]
        v = v_ref[0, 0, pl.ds(start, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32) * scale
        if masked:                                # this chunk holds padding
            kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(kpos < sk_valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = corr * acc + jnp.dot(p.astype(v.dtype), v,
                                       precision=precision,
                                       preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    carry = (jnp.full((bq, 1), NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32),
             jnp.zeros((bq, D), jnp.float32))
    whole = sk_valid // block_k                   # chunks with no padding
    carry = jax.lax.fori_loop(0, whole,
                              functools.partial(chunk, masked=False), carry)
    if whole < Sk // block_k:                     # padding < 128 <= block_k
        carry = chunk(whole, carry, masked=True)
    _, l, acc = carry
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mxu_dtype", "interpret"))
def patch_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mxu_dtype=jnp.float32, interpret: bool = True
                    ) -> jax.Array:
    """q,k,v: (B, H, S, D) full bidirectional attention -> (B, H, S, D) in
    q's dtype, with blocks from `block_size`."""
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    block_q, block_k = block_size(Sq), block_size(Sk)
    mxu_dtype = jnp.dtype(mxu_dtype)
    precision = (jax.lax.Precision.HIGHEST if mxu_dtype == jnp.float32
                 else None)

    def prep(x, block):
        return jnp.pad(x.astype(mxu_dtype),
                       ((0, 0), (0, 0), (0, (-x.shape[2]) % block), (0, 0)))

    qp, kp, vp = prep(q, block_q), prep(k, block_k), prep(v, block_k)
    Sqp, Skp = qp.shape[2], kp.shape[2]

    fn = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, sk_valid=Sk,
                          scale=D ** -0.5, precision=precision),
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
