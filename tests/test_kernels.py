"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import patched_ops, stitcher
from repro.core.patching import split
from repro.kernels import ops, ref
from repro.kernels.patch_attention import patch_attention


@pytest.mark.parametrize("res,C,G,dtype", [
    ([(16, 16)], 8, 4, jnp.float32),
    ([(16, 16), (32, 32)], 16, 4, jnp.float32),
    ([(24, 24), (16, 16), (32, 32)], 8, 2, jnp.float32),
    ([(16, 16), (24, 24)], 16, 8, jnp.bfloat16),
])
@pytest.mark.parametrize("exact", [True, False])
def test_groupnorm_stitch_sweep(res, C, G, dtype, exact):
    rng = np.random.default_rng(0)
    imgs = [jnp.asarray(rng.normal(size=(h, w, C)), dtype) for h, w in res]
    csp, patches = split(imgs)
    scale = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    got = ops.fused_groupnorm_stitch(csp, patches, scale, bias, G, exact=exact)
    normed = patched_ops.patched_groupnorm(csp, patches, scale, bias, G,
                                           exact=exact)
    want = stitcher.gather_halo(normed, csp.neighbors)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,S,H,D,dtype", [
    (2, 100, 4, 32, jnp.float32),
    (1, 256, 2, 64, jnp.float32),
    (3, 65, 1, 16, jnp.float32),
    (2, 128, 2, 32, jnp.bfloat16),
    (1, 17, 3, 8, jnp.float32),
])
def test_patch_attention_sweep(B, S, H, D, dtype):
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    got = patch_attention(q, k, v, interpret=True)
    want = ref.ref_attention(q, k, v)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_groupnorm_stitch_ref_against_kernel_ref():
    """ref.ref_groupnorm_stitch (per-patch-stat path) matches kernel."""
    rng = np.random.default_rng(2)
    imgs = [jnp.asarray(rng.normal(size=(16, 16, 8)), jnp.float32),
            jnp.asarray(rng.normal(size=(32, 32, 8)), jnp.float32)]
    csp, patches = split(imgs)
    P, p, _, C = patches.shape
    mean_c = jnp.asarray(rng.normal(size=(P, C)), jnp.float32)
    rstd_c = jnp.abs(jnp.asarray(rng.normal(size=(P, C)), jnp.float32)) + 0.5
    scale = jnp.ones((C,), jnp.float32)
    bias = jnp.zeros((C,), jnp.float32)
    from repro.kernels.groupnorm_stitch import groupnorm_stitch
    got = groupnorm_stitch(patches, jnp.asarray(csp.neighbors, jnp.int32),
                           mean_c, rstd_c, scale, bias, interpret=True)
    want = ref.ref_groupnorm_stitch(patches, csp.neighbors, mean_c, rstd_c,
                                    scale, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def _stitch_inputs(C, res=((16, 16), (24, 16)), patch=8):
    rng = np.random.default_rng(3)
    imgs = [jnp.asarray(rng.normal(size=(h, w, C)), jnp.float32)
            for h, w in res]
    csp, patches = split(imgs, patch=patch)
    P = patches.shape[0]
    mean_c = jnp.asarray(rng.normal(size=(P, C)), jnp.float32)
    rstd_c = jnp.abs(jnp.asarray(rng.normal(size=(P, C)), jnp.float32)) + 0.5
    scale = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    return patches, jnp.asarray(csp.neighbors, jnp.int32), mean_c, rstd_c, \
        scale, bias


@pytest.mark.parametrize("p,C,tiles,part", [
    (32, 640, (1, 128), 128),   # SDXL's level 1: channel tiles of 128 lanes
    (32, 960, (2, 960), 320),   # no 128-multiple divides 960: two row tiles
    (16, 1920, (1, 640), 640),  # level 1's widest up ResBlock, 16x16 patches
])
def test_groupnorm_stitch_tiled(p, C, tiles, part):
    """At SDXL's patches and widths the kernel tiles the call by itself,
    and writes what untiled calls on channel slices write, bit for bit, and
    what normalize-then-gather on the jnp path gives."""
    from repro.kernels.groupnorm_stitch import groupnorm_stitch, tiling
    assert tiling(p, C) == tiles and tiling(p, part) == (1, part)
    patches, nbr, mean_c, rstd_c, scale, bias = _stitch_inputs(
        C, res=((2 * p, 2 * p), (2 * p, p)), patch=p)
    got = groupnorm_stitch(patches, nbr, mean_c, rstd_c, scale, bias,
                           interpret=True)
    sl = [slice(c, c + part) for c in range(0, C, part)]
    whole = jnp.concatenate([
        groupnorm_stitch(patches[..., s], nbr, mean_c[:, s], rstd_c[:, s],
                         scale[s], bias[s], interpret=True) for s in sl],
        axis=-1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))
    want = ref.ref_groupnorm_stitch(patches, np.asarray(nbr), mean_c, rstd_c,
                                    scale, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_groupnorm_stitch_tiling_choice():
    """Shapes the kernel compiled untiled keep the one-program-per-patch
    grid; SDXL's widest calls are tiled to fit the scoped VMEM."""
    from repro.kernels.groupnorm_stitch import groupnorm_stitch, tiling
    assert tiling(32, 320) == (1, 320)
    assert tiling(8, 16) == (1, 16)
    assert tiling(32, 640) == (1, 128)
    assert tiling(32, 960) == (2, 960)
    assert tiling(16, 1920) == (1, 640)
    args = _stitch_inputs(16)
    got = groupnorm_stitch(*args, interpret=True)
    want = ref.ref_groupnorm_stitch(args[0], np.asarray(args[1]), *args[2:])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
