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
pre-haloed (p+2h, p+2h, C) tile ready for VALID convolution. Where a
patch's blocks outgrow the scoped VMEM (SDXL's 640- to 2560-channel calls),
``tiling`` splits each patch's program over channel tiles of 128 lanes'
multiples, or over two row halves where no such tile divides C (960); the
stats are per channel, so a channel tile never needs whole groups.

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
            own_ref,            # (1, r, p, c) this patch's rows of the tile
            st_ref,             # (1, 9, 2, c) mean, rstd: own, then slots
            aff_ref,            # (2, c) scale, bias
            *refs,              # 8 edge blocks in slot order, then out_ref
            row_tiles: int):
    edge_refs, out_ref = refs[:8], refs[8]
    i = pl.program_id(0)
    r, p = own_ref.shape[1], own_ref.shape[2]
    h = (out_ref.shape[2] - p) // 2
    sc = aff_ref[0:1, :]
    bi = aff_ref[1:2, :]

    def norm(x, k):
        # stats (2, c) broadcast over the leading spatial dims
        st = st_ref[0, k]
        return ((x.astype(jnp.float32) - st[0:1]) * st[1:2] * sc + bi
                ).astype(out_ref.dtype)

    def emit(top: bool, bottom: bool):
        """Write the tile's rows: its own, and the top/bottom halo rows when
        the tile holds them."""
        r0 = h if top else 0
        out_ref[0, r0:r0 + r, h:h + p, :] = norm(own_ref[0], 0)
        # Halo ring. Absent neighbors contribute zeros *post-normalization*
        # (the conv sees zero padding, paper §4.2). Strips are normalized
        # with the *neighbor's* stats — the paper's TB semantics (identical
        # to ours in exact mode: same request, same stats).
        rows = {None: slice(r0, r0 + r), True: slice(0, h),
                False: slice(r0 + r, r0 + r + h)}
        cols = {None: slice(h, h + p), True: slice(0, h),
                False: slice(h + p, None)}
        for slot, ((rlast, clast), ref) in enumerate(zip(_SLOTS, edge_refs)):
            if (rlast is True and not top) or (rlast is False and not bottom):
                continue
            x = ref[0]
            if clast is not None:  # keep the h columns of the aligned block
                cw = x.shape[1]
                x = x[:, cw - h:] if clast else x[:, :h]
            normed = norm(x, 1 + slot)
            out_ref[0, rows[rlast], cols[clast], :] = jnp.where(
                nbr_ref[i, slot] >= 0, normed, jnp.zeros_like(normed))

    if row_tiles == 1:
        emit(True, True)
        return

    @pl.when(pl.program_id(1) == 0)
    def _():
        emit(True, False)

    @pl.when(pl.program_id(1) == 1)
    def _():
        emit(False, True)


def _edge_spec(p: int, h: int, cw: int, c: int, row_tiles: int,
               slot: int) -> pl.BlockSpec:
    """BlockSpec of one neighbor's edge strip for a (row tile, channel tile);
    absent neighbors (-1) fetch patch 0 and are zeroed in the kernel. With
    two row tiles, a column strip holds the tile's half of the rows."""
    rlast, clast = _SLOTS[slot]
    rows = p // row_tiles if rlast is None else h
    cols = p if clast is None else cw
    r_blk = p // h - 1 if rlast else 0
    c_blk = p // cw - 1 if clast else 0

    def index(i, j, k, nbr):
        return (jnp.maximum(nbr[i, slot], 0),
                j if rlast is None else r_blk, c_blk, k)

    return pl.BlockSpec((1, rows, cols, c), index)


# Scoped VMEM a program may take by ``_vmem_bytes``'s estimate: Mosaic's
# default limit on v5e is 16 MiB, and the estimate leaves out the body's
# smaller temporaries.
VMEM_BUDGET = 15 * 2 ** 20


def _vmem_bytes(p: int, h: int, cw: int, c: int, row_tiles: int,
                itemsize: int) -> int:
    """Double-buffered blocks of one program, each padded to Mosaic's
    (8, 128) tiles, plus one f32 temporary the size of the own tile."""
    def tile(*shape):
        *lead, a, b = shape
        n = -(-a // 8) * 8 * (-(-b // 128) * 128)
        for d in lead:
            n *= d
        return n

    r = p // row_tiles
    out_rows = r + (2 * h) // row_tiles
    blocks = itemsize * (tile(r, p, c) + tile(out_rows, p + 2 * h, c)
                         + 2 * tile(h, p, c) + 2 * tile(r, cw, c)
                         + 4 * tile(h, cw, c))
    blocks += 4 * (tile(9, 2, c) + tile(2, c))
    return 2 * blocks + 4 * tile(r, p, c)


def tiling(p: int, c: int, halo: int = 1, itemsize: int = 4):
    """(row tiles, channel tile) of a call on (P, p, p, c): whole patches
    and all channels where they fit ``VMEM_BUDGET``, else the widest channel
    tile of 128 lanes' multiple that divides c and fits, else two row tiles
    (for widths such as 960 with no such divisor), with channel tiles if
    still needed."""
    cw = 8 if p % 8 == 0 else p
    tiles = [c] + [t for t in range((c - 1) // 128 * 128, 0, -128)
                   if c % t == 0]
    for row_tiles in (1, 2):
        if row_tiles == 2 and p % 2:
            break
        for t in tiles:
            if _vmem_bytes(p, halo, cw, t, row_tiles,
                           itemsize) <= VMEM_BUDGET:
                return row_tiles, t
    raise ValueError(f"no tiling of ({p}, {p}, {c}) fits {VMEM_BUDGET} "
                     "bytes of VMEM")


@functools.partial(jax.jit,
                   static_argnames=("halo", "interpret"))
def groupnorm_stitch(patches: jax.Array, neighbors: jax.Array,
                     mean_c: jax.Array, rstd_c: jax.Array,
                     scale: jax.Array, bias: jax.Array,
                     halo: int = 1, interpret: bool = True) -> jax.Array:
    """patches (P,p,p,C); neighbors (P,8) int32; mean_c/rstd_c (P,C) per-patch
    per-channel stats (already broadcast from (request, group));
    scale/bias (C,). Returns normalized haloed tiles (P, p+2h, p+2h, C).

    The grid is (patch, row tile, channel tile), as ``tiling`` chooses from
    the shape. Every element is normalized alike in any tiling, so all give
    the same bits.
    ``interpret=True`` runs the Pallas interpreter for CPU tests; the chip
    compiles with Mosaic."""
    P, p, _, C = patches.shape
    h = halo
    # column strips travel as the aligned 8-wide column block that holds them
    cw = 8 if p % 8 == 0 else p
    if p % h or h > cw:
        raise ValueError(f"halo {h} does not tile patch {p}")
    row_tiles, tc = tiling(p, C, h, patches.dtype.itemsize)
    nbr = neighbors.astype(jnp.int32)
    stats = jnp.stack([mean_c, rstd_c], axis=1).astype(jnp.float32)  # (P,2,C)
    # own stats then each neighbor's: a tiny XLA gather, one block per program
    stats9 = jnp.concatenate(
        [stats[:, None], stats[jnp.maximum(nbr, 0)]], axis=1)       # (P,9,2,C)
    affine = jnp.stack([scale, bias]).astype(jnp.float32)           # (2, C)
    r = p // row_tiles
    out_rows = r + (2 * h) // row_tiles

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(P, row_tiles, C // tc),
        in_specs=[
            pl.BlockSpec((1, r, p, tc), lambda i, j, k, nbr: (i, j, 0, k)),
            pl.BlockSpec((1, 9, 2, tc), lambda i, j, k, nbr: (i, 0, 0, k)),
            pl.BlockSpec((2, tc), lambda i, j, k, nbr: (0, k)),
        ] + [_edge_spec(p, h, cw, tc, row_tiles, slot) for slot in range(8)],
        out_specs=pl.BlockSpec((1, out_rows, p + 2 * h, tc),
                               lambda i, j, k, nbr: (i, j, 0, k)),
    )
    fn = pl.pallas_call(
        functools.partial(_kernel, row_tiles=row_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, p + 2 * h, p + 2 * h, C),
                                       patches.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
        name="groupnorm_stitch",
    )
    return fn(nbr, patches, stats9, affine, *([patches] * 8))
