"""Fused GroupNorm + Patch Edge Stitcher — Pallas TPU kernel (paper §4.3).

The paper's CUDA design: one thread block normalizes one patch, parks its
boundary pixels in shared memory, and scatters them into neighbor patches'
global-memory slots, overlapping stitch latency with normalization.

TPU adaptation (docs/ARCHITECTURE.md §3): Pallas programs cannot write other
programs' output blocks, so the data flow is inverted into a *pull* model.
The grid runs one program per patch and the per-patch neighbor ids arrive via
**scalar prefetch**. Besides the patch's own tile, each program names eight
neighbor edge blocks whose index maps read those ids, so the Pallas pipeline
fetches the next program's edges while the current one normalizes — the same
overlap the paper gets from its TB trick. Each program emits a normalized,
pre-haloed (p+2h, p+2h, C) tile ready for VALID convolution.

Edge blocks keep Mosaic's (8, 128) tiling: a row strip is (h, p, C) with the
row axis outside the tiled pair, a column strip is the aligned (p, 8, C)
column block that holds it, and the kernel slices the h columns it needs in
VMEM. Hand-written DMAs from an HBM ref are refused at the UNet's widths
(64-lane rows at C=64, one-column slices at C>=256), which is why the gather
goes through BlockSpecs.

Exactness: mean/rstd arrive precomputed per patch (from the CSP per-request
segment reduction), so normalization statistics span the *whole image* —
neighbors belong to the same request by construction and use identical stats.
With per-patch stats instead, this reproduces the paper's approximation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Slot order of the neighbor table: N, S, W, E, NW, NE, SW, SE. For each,
# whether the strip comes from the neighbor's last (True) or first (False)
# rows/cols, or spans all of them (None): a neighbor above lends its last
# rows, one to the left its last cols.
_SLOTS = ((True, None), (False, None), (None, True), (None, False),
          (True, True), (True, False), (False, True), (False, False))


def _kernel(nbr_ref,            # scalar prefetch: (P, 8) int32
            own_ref,            # (1, p, p, C) this patch
            st_ref,             # (1, 9, 2, C) mean, rstd: own, then slots
            aff_ref,            # (2, C) scale, bias
            *refs):             # 8 edge blocks in slot order, then out_ref
    edge_refs, out_ref = refs[:8], refs[8]
    i = pl.program_id(0)
    p = own_ref.shape[1]
    h = (out_ref.shape[1] - p) // 2
    sc = aff_ref[0:1, :]
    bi = aff_ref[1:2, :]

    def norm(x, k):
        # stats (2, C) broadcast over the leading spatial dims
        st = st_ref[0, k]
        return ((x.astype(jnp.float32) - st[0:1]) * st[1:2] * sc + bi
                ).astype(out_ref.dtype)

    out_ref[0, h:h + p, h:h + p, :] = norm(own_ref[0], 0)

    # Halo ring. Absent neighbors contribute zeros *post-normalization* (the
    # conv sees zero padding, paper §4.2). Strips are normalized with the
    # *neighbor's* stats — the paper's TB semantics (identical to ours in
    # exact mode: same request, same stats).
    span = {None: slice(h, h + p), True: slice(0, h), False: slice(h + p, None)}
    for slot, ((rlast, clast), ref) in enumerate(zip(_SLOTS, edge_refs)):
        x = ref[0]
        if clast is not None:      # keep the h columns of the aligned block
            cw = x.shape[1]
            x = x[:, cw - h:] if clast else x[:, :h]
        normed = norm(x, 1 + slot)
        out_ref[0, span[rlast], span[clast], :] = jnp.where(
            nbr_ref[i, slot] >= 0, normed, jnp.zeros_like(normed))


def _edge_spec(p: int, h: int, cw: int, C: int, slot: int) -> pl.BlockSpec:
    """BlockSpec of one neighbor's edge strip; absent neighbors (-1) fetch
    patch 0 and are zeroed in the kernel."""
    rlast, clast = _SLOTS[slot]
    rows = p if rlast is None else h
    cols = p if clast is None else cw
    r_blk = p // h - 1 if rlast else 0
    c_blk = p // cw - 1 if clast else 0

    def index(i, nbr):
        return jnp.maximum(nbr[i, slot], 0), r_blk, c_blk, 0

    return pl.BlockSpec((1, rows, cols, C), index)


@functools.partial(jax.jit,
                   static_argnames=("halo", "interpret"))
def groupnorm_stitch(patches: jax.Array, neighbors: jax.Array,
                     mean_c: jax.Array, rstd_c: jax.Array,
                     scale: jax.Array, bias: jax.Array,
                     halo: int = 1, interpret: bool = True) -> jax.Array:
    """patches (P,p,p,C); neighbors (P,8) int32; mean_c/rstd_c (P,C) per-patch
    per-channel stats (already broadcast from (request, group));
    scale/bias (C,). Returns normalized haloed tiles (P, p+2h, p+2h, C).

    ``interpret=True`` runs the Pallas interpreter for CPU tests; the chip
    compiles with Mosaic."""
    P, p, _, C = patches.shape
    h = halo
    # column strips travel as the aligned 8-wide column block that holds them
    cw = 8 if p % 8 == 0 else p
    if p % h or h > cw:
        raise ValueError(f"halo {h} does not tile patch {p}")
    nbr = neighbors.astype(jnp.int32)
    stats = jnp.stack([mean_c, rstd_c], axis=1).astype(jnp.float32)  # (P,2,C)
    # own stats then each neighbor's: a tiny XLA gather, one block per program
    stats9 = jnp.concatenate(
        [stats[:, None], stats[jnp.maximum(nbr, 0)]], axis=1)       # (P,9,2,C)
    affine = jnp.stack([scale, bias]).astype(jnp.float32)           # (2, C)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P,),
        in_specs=[
            pl.BlockSpec((1, p, p, C), lambda i, nbr: (i, 0, 0, 0)),
            pl.BlockSpec((1, 9, 2, C), lambda i, nbr: (i, 0, 0, 0)),
            pl.BlockSpec((2, C), lambda i, nbr: (0, 0)),
        ] + [_edge_spec(p, h, cw, C, slot) for slot in range(8)],
        out_specs=pl.BlockSpec((1, p + 2 * h, p + 2 * h, C),
                               lambda i, nbr: (i, 0, 0, 0)),
    )
    fn = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, p + 2 * h, p + 2 * h, C),
                                       patches.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="groupnorm_stitch",
    )
    return fn(nbr, patches, stats9, affine, *([patches] * 8))
