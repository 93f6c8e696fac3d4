"""Plain layers shared by the model kinds: whole images (1, H, W, C) in
straightforward `jax.numpy`, each beside its weights' layout and its
operation count.

A layout is a list of (path, shape, init), where init is a standard
deviation, None for 1/sqrt(fan in), "zeros" or "ones"; `bench/weights.py`
draws the weights in the list's order. A count is the multiply-adds of every
matrix product and convolution, at two operations each; elementwise work
(norms, activations, softmax) is left out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

GN_EPS = 1e-5


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def group_norm(x, p, groups):
    n, h, w, c = x.shape
    g = x.reshape(n, h * w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 3), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + GN_EPS)
    return g.reshape(n, h, w, c) * p["scale"] + p["bias"]


def conv(x, p, stride=1, padding="SAME"):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def attention(q, k, v, heads):
    """q (n, S, C), k/v (n, T, C) -> (n, S, C); softmax over T."""
    n, s, c = q.shape
    d = c // heads
    q = q.reshape(n, s, heads, d)
    k = k.reshape(n, -1, heads, d)
    v = v.reshape(n, -1, heads, d)
    w = jax.nn.softmax(jnp.einsum("nshd,nthd->nhst", q, k) / math.sqrt(d), -1)
    return jnp.einsum("nhst,nthd->nshd", w, v).reshape(n, s, c)


def timestep_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    a = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(a), jnp.sin(a)], axis=-1)


def time_mlp(cfg, P, t, dtype):
    temb = timestep_embedding(t, cfg["t_dim"]).astype(dtype)
    temb = silu(temb @ P["temb_w1"] + P["temb_b1"])
    return temb @ P["temb_w2"] + P["temb_b2"]


def res_block(cfg, p, x, temb):
    h = conv(silu(group_norm(x, p["gn1"], cfg["groups"])), p["conv1"])
    ss = silu(temb) @ p["temb_w"] + p["temb_b"]
    scale, shift = jnp.split(ss, 2, axis=-1)
    h = h * (1 + scale[:, None, None]) + shift[:, None, None]
    h = conv(silu(group_norm(h, p["gn2"], cfg["groups"])), p["conv2"])
    if "skip" in p:
        x = jnp.einsum("nhwc,cd->nhwd", x, p["skip"]["w"][0, 0]) + p["skip"]["b"]
    return x + h


def attn_block(cfg, p, x, text):
    """Normalise, self-attend over the whole image, cross-attend to the
    prompt, feed forward. As in the program, the self-attention residual
    starts from the normalised input."""
    n, hh, ww, c = x.shape
    heads = cfg["n_heads"]
    h = group_norm(x, p["gn"], cfg["groups"]).reshape(n, hh * ww, c)
    h = h + attention(h @ p["wq"], h @ p["wk"], h @ p["wv"], heads) @ p["wo"]
    h = h + attention(h @ p["xq"], text @ p["xk"], text @ p["xv"],
                      heads) @ p["xo"]
    hn = group_norm(h.reshape(n, hh, ww, c), p["gn_ff"], cfg["groups"])
    ff = gelu_tanh(hn.reshape(n, hh * ww, c) @ p["ff1"]) @ p["ff2"]
    return (h + ff).reshape(n, hh, ww, c)


# ---------------------------------------------------------------------------
# Weights' layout
# ---------------------------------------------------------------------------

def conv_spec(spec, path, k, cin, cout):
    spec.append((f"{path}/w", (k, k, cin, cout), 1.0 / math.sqrt(k * k * cin)))
    spec.append((f"{path}/b", (cout,), "zeros"))


def norm_spec(spec, path, c):
    spec.append((f"{path}/scale", (c,), "ones"))
    spec.append((f"{path}/bias", (c,), "zeros"))


def time_mlp_spec(spec, t):
    spec.extend([("temb_w1", (t, t), None), ("temb_b1", (t,), "zeros"),
                 ("temb_w2", (t, t), None), ("temb_b2", (t,), "zeros")])


def res_spec(spec, path, cin, cout, t_dim):
    norm_spec(spec, f"{path}/gn1", cin)
    conv_spec(spec, f"{path}/conv1", 3, cin, cout)
    spec.append((f"{path}/temb_w", (t_dim, 2 * cout), None))
    spec.append((f"{path}/temb_b", (2 * cout,), "zeros"))
    norm_spec(spec, f"{path}/gn2", cout)
    conv_spec(spec, f"{path}/conv2", 3, cout, cout)
    if cin != cout:
        conv_spec(spec, f"{path}/skip", 1, cin, cout)


def attn_spec(spec, path, c, d_text):
    norm_spec(spec, f"{path}/gn", c)
    for n in ("wq", "wk", "wv", "wo", "xq"):
        spec.append((f"{path}/{n}", (c, c), None))
    spec.append((f"{path}/xk", (d_text, c), None))
    spec.append((f"{path}/xv", (d_text, c), None))
    spec.append((f"{path}/xo", (c, c), None))
    norm_spec(spec, f"{path}/gn_ff", c)
    spec.append((f"{path}/ff1", (c, 4 * c), None))
    spec.append((f"{path}/ff2", (4 * c, c), None))


# ---------------------------------------------------------------------------
# Operation counts
# ---------------------------------------------------------------------------

def conv_flops(h, w, cin, cout, k):
    return 2 * h * w * cin * cout * k * k


def time_mlp_flops(t):
    return 2 * 2 * t * t


def res_flops(h, w, cin, cout, t_dim):
    f = conv_flops(h, w, cin, cout, 3) + conv_flops(h, w, cout, cout, 3)
    f += 2 * t_dim * 2 * cout
    if cin != cout:
        f += conv_flops(h, w, cin, cout, 1)
    return f


def attn_flops(s, c, n_text, d_text):
    """The prompt's keys and values counted once per block and image."""
    f = 2 * s * c * c * 6                # wq wk wv wo, xq xo
    f += 2 * n_text * d_text * c * 2     # prompt keys and values
    f += 4 * s * s * c                   # self-attention scores and sum
    f += 4 * s * n_text * c              # cross-attention
    f += 2 * s * c * 4 * c * 2           # feed-forward
    return f
