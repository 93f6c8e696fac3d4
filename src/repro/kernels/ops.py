"""jit'd public wrappers for the Pallas kernels.

``interpret`` auto-selects: False on TPU (Mosaic compilation), True
elsewhere. Interpret mode runs the kernel body in the Pallas interpreter and
exists for the CPU tests only; the chip path (``chip_smoke.py``) refuses to
run without a TPU instead of falling back to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.groupnorm_stitch import groupnorm_stitch
from repro.kernels.patch_attention import patch_attention


@functools.lru_cache()
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def groupnorm_stats(csp, patches: jax.Array, groups: int, eps: float = 1e-5,
                    exact: bool = True):
    """Per-patch, per-channel (mean, rstd), each (P, C), for the kernel:
    exact per-request statistics by a segment reduction, or with
    exact=False each patch's own (the paper's approximation)."""
    from repro.core.patched_ops import csp_group_stats
    P, p, _, C = patches.shape
    G = groups
    if exact:
        mean, var = csp_group_stats(csp, patches, groups)          # (R, G)
        seg = jnp.asarray(csp.patch_req, jnp.int32)
        mean_p, var_p = mean[seg], var[seg]                        # (P, G)
    else:
        x = patches.astype(jnp.float32).reshape(P, p * p, G, C // G)
        mean_p = jnp.mean(x, axis=(1, 3))
        var_p = jnp.mean(jnp.square(x - mean_p[:, None, :, None]), axis=(1, 3))
    rstd_p = jax.lax.rsqrt(var_p + eps)
    return (jnp.repeat(mean_p, C // G, axis=-1),                   # (P, C)
            jnp.repeat(rstd_p, C // G, axis=-1))


def groupnorm_stitch_kernel(patches: jax.Array, neighbors, mean_c: jax.Array,
                            rstd_c: jax.Array, scale: jax.Array,
                            bias: jax.Array, halo: int = 1) -> jax.Array:
    """The Pallas kernel alone: normalize with the given stats + halo."""
    return groupnorm_stitch(patches, jnp.asarray(neighbors, jnp.int32),
                            mean_c, rstd_c, scale, bias, halo=halo,
                            interpret=not _on_tpu())


def fused_groupnorm_stitch(csp, patches: jax.Array, scale: jax.Array,
                           bias: jax.Array, groups: int, eps: float = 1e-5,
                           exact: bool = True, halo: int = 1) -> jax.Array:
    """CSP-aware fused GroupNorm + edge stitch.

    Phase 1 (cheap segment reduction, ``groupnorm_stats``): the stats;
    Phase 2 (the Pallas kernel): normalize + halo in one pass.
    """
    mean_c, rstd_c = groupnorm_stats(csp, patches, groups, eps, exact)
    return groupnorm_stitch_kernel(patches, csp.neighbors, mean_c, rstd_c,
                                   scale, bias, halo)


# matmul precisions at which XLA's own f32 dots on the TPU make one bfloat16
# MXU pass; under any other ("high", "highest", "float32", ...) the kernel's
# products keep the MXU's f32 format
_ONE_PASS = (None, "default", "bfloat16", "fastest")


def attention_mxu_dtype():
    """The operand dtype of the attention kernel's products at the ambient
    matmul precision: bfloat16 on the TPU where that precision asks for one
    pass, as XLA's dots do; float32 otherwise, and always in interpret mode,
    where XLA's CPU dots are exact."""
    if _on_tpu() and jax.config.jax_default_matmul_precision in _ONE_PASS:
        return jnp.bfloat16
    return jnp.float32


def grouped_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array
                             ) -> jax.Array:
    return patch_attention(q, k, v, mxu_dtype=attention_mxu_dtype(),
                           interpret=not _on_tpu())
