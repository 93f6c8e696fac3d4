"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import contextlib

import jax
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


F32, BF16 = jnp.float32, jnp.bfloat16
# (B, S, H, D, input dtype, MXU operand dtype); S not a multiple of 128 pads
# the keys, and S = 600 takes five 128-blocks, the last one masked
ATTENTION_CASES = [
    (2, 100, 4, 32, F32, F32),
    (1, 256, 2, 64, F32, F32),
    (3, 65, 1, 16, F32, F32),
    (2, 128, 2, 32, BF16, F32),
    (1, 17, 3, 8, F32, F32),
    (1, 600, 2, 64, F32, F32),
    (2, 100, 2, 72, F32, BF16),
    (3, 65, 2, 64, F32, BF16),
    (1, 17, 3, 72, F32, BF16),
    (1, 600, 2, 64, F32, BF16),
]


def _attention_id(case):
    B, S, H, D, dtype, mxu = case
    tail = "" if mxu == F32 else f"-mxu_{jnp.dtype(mxu).name}"
    return f"{B}-{S}-{H}-{D}-{jnp.dtype(dtype).name}{tail}"


@pytest.mark.parametrize("B,S,H,D,dtype,mxu", ATTENTION_CASES,
                         ids=[_attention_id(c) for c in ATTENTION_CASES])
def test_patch_attention_sweep(B, S, H, D, dtype, mxu):
    """The kernel against full-softmax attention. On the bfloat16 MXU path
    the reference takes the same bfloat16-rounded q, k and v, so the scores
    agree to f32 summation order; what is left is p's rounding to bfloat16
    before p.v, a relative 2**-9 on each weight, so at most 2**-9 max|v|
    (about 9e-3 for these normal draws): hence 1e-2."""
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), dtype)
    got = patch_attention(q, k, v, mxu_dtype=mxu, interpret=True)
    assert got.dtype == q.dtype
    want = ref.ref_attention(*(x.astype(mxu).astype(dtype) for x in (q, k, v)))
    if mxu == BF16:
        tol = 1e-2
    else:
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("on_tpu,precision,want", [
    (True, None, BF16),
    (True, "default", BF16),
    (True, "bfloat16", BF16),
    (True, "highest", F32),
    (True, "float32", F32),
    (False, None, F32),
    (False, "bfloat16", F32),
])
def test_attention_mxu_dtype(monkeypatch, on_tpu, precision, want):
    """The kernel's products follow the ambient matmul precision on the TPU,
    as XLA's f32 dots do, and stay f32 in interpret mode."""
    seen = {}

    def record(q, k, v, **kw):
        seen.update(kw)
        return q

    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    monkeypatch.setattr(ops, "patch_attention", record)
    x = jnp.zeros((1, 1, 8, 8), jnp.float32)
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        ops.grouped_attention_kernel(x, x, x)
    assert seen == dict(mxu_dtype=want, interpret=not on_tpu)


@pytest.mark.parametrize("S", [17, 100, 256, 576, 600, 1024, 2304, 4096])
def test_patch_attention_block_sizes(S):
    """A block is a multiple of 128 up to 512 that divides S padded to 128,
    so no block pads the sequence further (2304 stays 2304, 576 640)."""
    from repro.kernels.patch_attention import block_size
    sp = -(-S // 128) * 128
    b = block_size(S)
    assert b % 128 == 0 and b <= 512 and sp % b == 0
    assert -(-S // b) * b == sp
    assert b == {2304: 384, 4096: 512, 576: 128}.get(S, b)


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
