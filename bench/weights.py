"""Seeded random weights in the layout the program serves, made by the
benchmark on the device in one jitted call.

The layout (nested dicts of arrays, by block name) is the one repro's
`init_diffusion` and `init_vae` build; each model kind's module gives its
backbone's (`bench/models/<kind>.py`), and `bench/tests` checks that the two
agree at a small size. The scales follow the same rule: normal with standard
deviation 1/sqrt(fan in) unless stated, zero biases, unit norm scales.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import kind


def model_spec(m):
    """[(path, shape, init)] for a model section of a configuration file,
    from its kind's module (`bench/models/<kind>.py`). init: a standard
    deviation, None for 1/sqrt(fan in), "zeros" or "ones"."""
    return kind(m["kind"]).weight_spec(m)


def vae_spec(latent_channels, width=32):
    return [("conv1/w", (3, 3, latent_channels, width), 0.1),
            ("conv1/b", (width,), "zeros"),
            ("conv2/w", (3, 3, width, 3 * 64), 0.1),
            ("conv2/b", (3 * 64,), "zeros")]


def _build(spec, key, dtype):
    tree = {}
    keys = jax.random.split(key, len(spec))
    for (path, shape, init), k in zip(spec, keys):
        if init == "zeros":
            a = jnp.zeros(shape, dtype)
        elif init == "ones":
            a = jnp.ones(shape, dtype)
        else:
            std = init if init is not None else 1.0 / math.sqrt(
                shape[-2] if len(shape) >= 2 else shape[-1])
            a = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = a
    return tree


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number, 64-bit ones included."""
    w = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0])), int(w[1]))


def make_weights(model: dict, key: jax.Array):
    """(backbone params, VAE params) in float32, built in one jitted call."""
    spec = model_spec(model)
    vspec = vae_spec(model["latent_channels"])
    km, kv = jax.random.split(key)
    return jax.jit(lambda a, b: (_build(spec, a, jnp.float32),
                                 _build(vspec, b, jnp.float32)))(km, kv)


def param_count(model: dict) -> int:
    return sum(int(np.prod(s)) for _, s, _ in model_spec(model))
