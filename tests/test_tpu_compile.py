"""Compile the serving path's Pallas kernels for a TPU v5e without a chip.

The chip's compiler is installed even where no chip is attached, so these
tests lower each kernel with ``interpret=False`` for a described v5e device
at the shapes the SDXL-lite preset serves (64/96/128 latents, GCD patch 32:
29 patches for one request of each size), and the attention kernel also at
the benchmark cells' own shapes, and check that Mosaic accepts it.
Interpret-mode tests cannot see what Mosaic refuses (tiling, scoped VMEM).

The topology is described inside a fixture, never at import time, so that
every test worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.groupnorm_stitch import groupnorm_stitch
from repro.kernels.patch_attention import patch_attention


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-device compile is written to the persistent cache but can
    never be read back here; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# (P, p, C): level 0 (32-px patches) and level 1 (16-px patches) at the
# widths of the down path, and the concatenated skips of the up path
@pytest.mark.parametrize("P,p,C", [(29, 32, 64), (29, 16, 128),
                                   (29, 32, 128), (29, 16, 256)])
def test_groupnorm_stitch_compiles_for_v5e(one_chip, P, p, C):
    f32 = jnp.float32
    text = _compiled_text(
        lambda x, n, m, r, s, b: groupnorm_stitch(x, n, m, r, s, b,
                                                  interpret=False),
        one_chip, ((P, p, p, C), f32), ((P, 8), jnp.int32), ((P, C), f32),
        ((P, C), f32), ((C,), f32), ((C,), f32))
    assert "tpu_custom_call" in text


# (n, H, S, D): two 128-latent images at level 1 (64x64 = 4096 tokens), the
# largest sequence served, and a 96-latent pair (48x48 = 2304 tokens)
@pytest.mark.parametrize("n,H,S,D", [(2, 4, 4096, 32), (2, 4, 2304, 32)])
def test_patch_attention_compiles_for_v5e(one_chip, n, H, S, D):
    text = _compiled_text(
        lambda q, k, v: patch_attention(q, k, v, interpret=False),
        one_chip, *[((n, H, S, D), jnp.float32)] * 3)
    assert "tpu_custom_call" in text


# (n, H, S, D) of the benchmark's cells on the default path (bfloat16 MXU
# operands, blocks from the sequence): PixArt's 1024-px and 768-px groups
# (16 heads of 72) and SDXL's 512-px group at its inner level (20 heads of 64)
@pytest.mark.parametrize("n,H,S,D", [(4, 16, 4096, 72), (4, 16, 2304, 72),
                                     (4, 20, 576, 64)])
def test_patch_attention_bf16_compiles_for_v5e(one_chip, n, H, S, D):
    text = _compiled_text(
        lambda q, k, v: patch_attention(q, k, v, mxu_dtype=jnp.bfloat16,
                                        interpret=False),
        one_chip, *[((n, H, S, D), jnp.float32)] * 3)
    assert "tpu_custom_call" in text
