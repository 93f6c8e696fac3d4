"""Seeded random weights in the layout the program serves, made by the
benchmark on the device in one jitted call.

The layout (nested dicts of arrays, by block name) is the one repro's
`init_diffusion` and `init_vae` build; `bench/tests` checks that the two
agree at a small size. The scales follow the same rule: normal with standard
deviation 1/sqrt(fan in) unless stated, zero biases, unit norm scales.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _conv(spec, path, k, cin, cout):
    spec.append((f"{path}/w", (k, k, cin, cout), 1.0 / math.sqrt(k * k * cin)))
    spec.append((f"{path}/b", (cout,), "zeros"))


def _norm(spec, path, c):
    spec.append((f"{path}/scale", (c,), "ones"))
    spec.append((f"{path}/bias", (c,), "zeros"))


def _res(spec, path, cin, cout, t_dim):
    _norm(spec, f"{path}/gn1", cin)
    _conv(spec, f"{path}/conv1", 3, cin, cout)
    spec.append((f"{path}/temb_w", (t_dim, 2 * cout), None))
    spec.append((f"{path}/temb_b", (2 * cout,), "zeros"))
    _norm(spec, f"{path}/gn2", cout)
    _conv(spec, f"{path}/conv2", 3, cout, cout)
    if cin != cout:
        _conv(spec, f"{path}/skip", 1, cin, cout)


def _attn(spec, path, c, d_text):
    _norm(spec, f"{path}/gn", c)
    for n in ("wq", "wk", "wv", "wo", "xq"):
        spec.append((f"{path}/{n}", (c, c), None))
    spec.append((f"{path}/xk", (d_text, c), None))
    spec.append((f"{path}/xv", (d_text, c), None))
    spec.append((f"{path}/xo", (c, c), None))
    _norm(spec, f"{path}/gn_ff", c)
    spec.append((f"{path}/ff1", (c, 4 * c), None))
    spec.append((f"{path}/ff2", (4 * c, c), None))


def model_spec(m):
    """[(path, shape, init)] for a model section of a configuration file.
    init: a standard deviation, None for 1/sqrt(fan in), "zeros" or "ones"."""
    spec = []
    t, W, C0 = m["t_dim"], m["width"], m["latent_channels"]
    spec += [("temb_w1", (t, t), None), ("temb_b1", (t,), "zeros"),
             ("temb_w2", (t, t), None), ("temb_b2", (t,), "zeros")]
    if m["kind"] == "dit":
        spec += [("tok_in", (C0, W), None), ("tok_in_b", (W,), "zeros"),
                 ("adaln_w", (t, 3 * W), 0.02), ("adaln_b", (3 * W,), "zeros")]
        for i in range(m["dit_depth"]):
            _attn(spec, f"blk{i}", W, m["d_text"])
        _norm(spec, "out_norm", W)
        spec += [("tok_out", (W, C0), 0.02), ("tok_out_b", (C0,), "zeros")]
        return spec
    levels = m["levels"]
    chans = [W * 2 ** lvl for lvl in range(levels)]
    _conv(spec, "stem", 3, C0, W)
    for lvl in range(levels):
        c = chans[lvl]
        for i in range(m["blocks_per_level"]):
            _res(spec, f"down{lvl}_res{i}", c, c, t)
            if lvl in m["attn_levels"]:
                _attn(spec, f"down{lvl}_attn{i}", c, m["d_text"])
        if lvl + 1 < levels:
            _conv(spec, f"down{lvl}_ds", 3, c, chans[lvl + 1])
    cm = chans[-1]
    _res(spec, "mid_res1", cm, cm, t)
    _attn(spec, "mid_attn", cm, m["d_text"])
    _res(spec, "mid_res2", cm, cm, t)
    for lvl in reversed(range(levels)):
        c = chans[lvl]
        if lvl + 1 < levels:
            _conv(spec, f"up{lvl}_us", 3, chans[lvl + 1], c)
        for i in range(m["blocks_per_level"]):
            _res(spec, f"up{lvl}_res{i}", 2 * c if i == 0 else c, c, t)
            if lvl in m["attn_levels"]:
                _attn(spec, f"up{lvl}_attn{i}", c, m["d_text"])
    _norm(spec, "out_norm", W)
    _conv(spec, "out_conv", 3, W, C0)
    return spec


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
