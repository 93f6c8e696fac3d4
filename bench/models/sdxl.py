"""SDXL-base's UNet (`UNet2DConditionModel` with the config of
stabilityai/stable-diffusion-xl-base-1.0, arXiv:2307.01952), as the
program's `sdxl` kind lays it out:

- time: `temb = W2 silu(W1 sinusoid(t, t_dim))`, widening t_dim -> temb_dim;
  plus the added conditioning `W2' silu(W1' [pooled, sinusoid(id, d_size_id)
  for each of six size ids])`;
- ResnetBlock2D: GN -> SiLU -> conv3, plus `Linear(silu(temb))` added (no
  scale-shift), GN -> SiLU -> conv3, a 1x1 shortcut where widths differ;
- Transformer2DModel: GN (eps 1e-6) -> proj_in, `tf_depth[level]` layers of
  LayerNorm -> self-attention, LayerNorm -> cross-attention to the prompt,
  LayerNorm -> GEGLU feed-forward (exact GELU), then proj_out and the
  residual; heads of `head_dim`, Q, K and V without bias;
- `blocks_per_level` ResBlocks down per level, a stride-2 conv with padding 1
  between levels, a mid block (ResBlock, stack, ResBlock), and
  `blocks_per_level + 1` ResBlocks up per level, each taking one skip.

The pooled text and the size ids come from the model's own inputs, as the
configuration's `assumed` says: the pooled embedding is the last prompt
token's last `d_pooled` features, the size ids are (8h, 8w, 0, 0, 8h, 8w)
from the latent's size. Sampled by DDIM."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.models import _layers as L

SAMPLER = "ddim"
TF_GN_EPS = 1e-6          # Transformer2DModel's GroupNorm
LN_EPS = 1e-5


def _chans(m):
    return [m["width"] * 2 ** lvl for lvl in range(m["levels"])]


def _skip_chans(m):
    """Widths of the skips in the order they are pushed."""
    chans = _chans(m)
    out = [chans[0]]
    for lvl, c in enumerate(chans):
        out += [c] * m["blocks_per_level"]
        if lvl + 1 < len(chans):
            out.append(c)
    return out


def _up_inputs(m):
    """[(level, i, cin, cout)] of the up ResBlocks, in order."""
    chans, skips = _chans(m), _skip_chans(m)
    out, c = [], chans[-1]
    for lvl in reversed(range(m["levels"])):
        for i in range(m["blocks_per_level"] + 1):
            out.append((lvl, i, c + skips.pop(), chans[lvl]))
            c = chans[lvl]
    return out


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def group_norm(x, p, groups, eps):
    n, h, w, c = x.shape
    g = x.reshape(n, h * w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 3), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 3), keepdims=True)
    g = (g - mean) * jax.lax.rsqrt(var + eps)
    return g.reshape(n, h, w, c) * p["scale"] + p["bias"]


def layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1 + jax.lax.erf(x / math.sqrt(2)))


def embed(cfg, P, t, text, size):
    """temb (n, temb_dim): the timestep MLP plus the added conditioning."""
    dtype = text.dtype
    temb = L.timestep_embedding(t, cfg["t_dim"]).astype(dtype)
    temb = L.silu(temb @ P["temb_w1"] + P["temb_b1"]) @ P["temb_w2"] \
        + P["temb_b2"]
    h, w = size
    ids = jnp.asarray([8 * h, 8 * w, 0, 0, 8 * h, 8 * w], jnp.float32)
    ids = L.timestep_embedding(ids, cfg["d_size_id"]).reshape(1, -1)
    pooled = text[:, -1, cfg["d_text"] - cfg["d_pooled"]:]
    a = jnp.concatenate([pooled, jnp.broadcast_to(
        ids.astype(dtype), (pooled.shape[0], ids.shape[1]))], axis=-1)
    a = L.silu(a @ P["add_w1"] + P["add_b1"]) @ P["add_w2"] + P["add_b2"]
    return temb + a


def res_block(cfg, p, x, temb):
    g = cfg["groups"]
    h = L.conv(L.silu(group_norm(x, p["gn1"], g, L.GN_EPS)), p["conv1"])
    h = h + (L.silu(temb) @ p["temb_w"] + p["temb_b"])[:, None, None]
    h = L.conv(L.silu(group_norm(h, p["gn2"], g, L.GN_EPS)), p["conv2"])
    if "skip" in p:
        x = jnp.einsum("nhwc,cd->nhwd", x, p["skip"]["w"][0, 0]) \
            + p["skip"]["b"]
    return x + h


def tf_stack(cfg, p, x, text, depth):
    n, hh, ww, c = x.shape
    heads = c // cfg["head_dim"]
    h = group_norm(x, p["gn"], cfg["groups"], TF_GN_EPS).reshape(n, hh * ww, c)
    h = h @ p["proj_in"] + p["proj_in_b"]
    for j in range(depth):
        q = p[f"l{j}"]
        a = layer_norm(h, q["ln1"])
        h = h + L.attention(a @ q["wq"], a @ q["wk"], a @ q["wv"],
                            heads) @ q["wo"] + q["wo_b"]
        a = layer_norm(h, q["ln2"])
        h = h + L.attention(a @ q["xq"], text @ q["xk"], text @ q["xv"],
                            heads) @ q["xo"] + q["xo_b"]
        a, g = jnp.split(layer_norm(h, q["ln3"]) @ q["ff1"] + q["ff1_b"], 2,
                         axis=-1)
        h = h + (a * gelu(g)) @ q["ff2"] + q["ff2_b"]
    h = h @ p["proj_out"] + p["proj_out_b"]
    return x + h.reshape(n, hh, ww, c)


def forward(cfg, P, x, t, text, run):
    temb = embed(cfg, P, t, text, x.shape[1:3])
    levels, blocks, depth = cfg["levels"], cfg["blocks_per_level"], \
        cfg["tf_depth"]

    def res(name, z):
        return run(name, lambda y: res_block(cfg, P[name], y, temb), z)

    def tf(name, z, d):
        return run(name, lambda y: tf_stack(cfg, P[name], y, text, d), z)

    x = run("conv_in", lambda z: L.conv(z, P["conv_in"]), x)
    skips = [x]
    for lvl in range(levels):
        for i in range(blocks):
            x = res(f"down{lvl}_res{i}", x)
            if depth[lvl]:
                x = tf(f"down{lvl}_tf{i}", x, depth[lvl])
            skips.append(x)
        if lvl + 1 < levels:
            name = f"down{lvl}_ds"
            x = run(name, lambda z, n=name: L.conv(
                z, P[n], 2, ((1, 1), (1, 1))), x)
            skips.append(x)
    x = res("mid_res0", x)
    x = tf("mid_tf", x, depth[-1])
    x = res("mid_res1", x)
    for lvl in reversed(range(levels)):
        for i in range(blocks + 1):
            x = res(f"up{lvl}_res{i}",
                    jnp.concatenate([x, skips.pop()], axis=-1))
            if depth[lvl]:
                x = tf(f"up{lvl}_tf{i}", x, depth[lvl])
        if lvl:
            name = f"up{lvl}_us"
            x = run(name, lambda z, n=name: L.conv(
                jnp.repeat(jnp.repeat(z, 2, axis=1), 2, axis=2), P[n]), x)
    return run("out", lambda z: L.conv(
        L.silu(group_norm(z, P["out_norm"], cfg["groups"], L.GN_EPS)),
        P["out_conv"]), x)


# ---------------------------------------------------------------------------
# Weights' layout
# ---------------------------------------------------------------------------

def _linear_spec(spec, path, cin, cout, bias=True):
    spec.append((path, (cin, cout), None))
    if bias:
        spec.append((f"{path}_b", (cout,), "zeros"))


def _res_spec(spec, path, cin, cout, temb):
    L.norm_spec(spec, f"{path}/gn1", cin)
    L.conv_spec(spec, f"{path}/conv1", 3, cin, cout)
    spec.append((f"{path}/temb_w", (temb, cout), None))
    spec.append((f"{path}/temb_b", (cout,), "zeros"))
    L.norm_spec(spec, f"{path}/gn2", cout)
    L.conv_spec(spec, f"{path}/conv2", 3, cout, cout)
    if cin != cout:
        L.conv_spec(spec, f"{path}/skip", 1, cin, cout)


def _tf_spec(spec, path, c, depth, d_text):
    L.norm_spec(spec, f"{path}/gn", c)
    _linear_spec(spec, f"{path}/proj_in", c, c)
    for j in range(depth):
        q = f"{path}/l{j}"
        L.norm_spec(spec, f"{q}/ln1", c)
        for n in ("wq", "wk", "wv"):
            _linear_spec(spec, f"{q}/{n}", c, c, bias=False)
        _linear_spec(spec, f"{q}/wo", c, c)
        L.norm_spec(spec, f"{q}/ln2", c)
        _linear_spec(spec, f"{q}/xq", c, c, bias=False)
        _linear_spec(spec, f"{q}/xk", d_text, c, bias=False)
        _linear_spec(spec, f"{q}/xv", d_text, c, bias=False)
        _linear_spec(spec, f"{q}/xo", c, c)
        L.norm_spec(spec, f"{q}/ln3", c)
        _linear_spec(spec, f"{q}/ff1", c, 8 * c)
        _linear_spec(spec, f"{q}/ff2", 4 * c, c)
    _linear_spec(spec, f"{path}/proj_out", c, c)


def weight_spec(m):
    spec = []
    t, temb = m["t_dim"], m["temb_dim"]
    W, C0 = m["width"], m["latent_channels"]
    d_add = m["d_pooled"] + 6 * m["d_size_id"]
    spec += [("temb_w1", (t, temb), None), ("temb_b1", (temb,), "zeros"),
             ("temb_w2", (temb, temb), None), ("temb_b2", (temb,), "zeros"),
             ("add_w1", (d_add, temb), None), ("add_b1", (temb,), "zeros"),
             ("add_w2", (temb, temb), None), ("add_b2", (temb,), "zeros")]
    levels, depth, dt = m["levels"], m["tf_depth"], m["d_text"]
    chans = _chans(m)
    L.conv_spec(spec, "conv_in", 3, C0, W)
    cin = W
    for lvl in range(levels):
        c = chans[lvl]
        for i in range(m["blocks_per_level"]):
            _res_spec(spec, f"down{lvl}_res{i}", cin, c, temb)
            cin = c
            if depth[lvl]:
                _tf_spec(spec, f"down{lvl}_tf{i}", c, depth[lvl], dt)
        if lvl + 1 < levels:
            L.conv_spec(spec, f"down{lvl}_ds", 3, c, c)
    cm = chans[-1]
    _res_spec(spec, "mid_res0", cm, cm, temb)
    _tf_spec(spec, "mid_tf", cm, depth[-1], dt)
    _res_spec(spec, "mid_res1", cm, cm, temb)
    for lvl, i, cin, c in _up_inputs(m):
        _res_spec(spec, f"up{lvl}_res{i}", cin, c, temb)
        if depth[lvl]:
            _tf_spec(spec, f"up{lvl}_tf{i}", c, depth[lvl], dt)
        if lvl and i == m["blocks_per_level"]:
            L.conv_spec(spec, f"up{lvl}_us", 3, c, c)
    L.norm_spec(spec, "out_norm", W)
    L.conv_spec(spec, "out_conv", 3, W, C0)
    return spec


# ---------------------------------------------------------------------------
# Operation count
# ---------------------------------------------------------------------------

def _res_flops(h, w, cin, cout, temb):
    f = L.conv_flops(h, w, cin, cout, 3) + L.conv_flops(h, w, cout, cout, 3)
    f += 2 * temb * cout
    if cin != cout:
        f += L.conv_flops(h, w, cin, cout, 1)
    return f


def _tf_flops(s, c, depth, n_text, d_text):
    """The prompt's keys and values counted once per layer and image."""
    layer = 2 * s * c * c * 6            # wq wk wv wo, xq xo
    layer += 2 * n_text * d_text * c * 2  # prompt keys and values
    layer += 4 * s * s * c               # self-attention scores and sum
    layer += 4 * s * n_text * c          # cross-attention
    layer += 2 * s * c * 8 * c + 2 * s * 4 * c * c   # GEGLU in, out
    return 2 * 2 * s * c * c + depth * layer          # proj_in, proj_out


def step_flops(m, h, w):
    t, temb = m["t_dim"], m["temb_dim"]
    C0, W = m["latent_channels"], m["width"]
    nt, dt, depth = m["n_text"], m["d_text"], m["tf_depth"]
    levels = m["levels"]
    chans = _chans(m)
    f = 2 * (t * temb + temb * temb)
    f += 2 * ((m["d_pooled"] + 6 * m["d_size_id"]) * temb + temb * temb)
    f += L.conv_flops(h, w, C0, W, 3)
    cin = W
    for lvl in range(levels):
        c, hh, ww = chans[lvl], h >> lvl, w >> lvl
        for _ in range(m["blocks_per_level"]):
            f += _res_flops(hh, ww, cin, c, temb)
            cin = c
            if depth[lvl]:
                f += _tf_flops(hh * ww, c, depth[lvl], nt, dt)
        if lvl + 1 < levels:
            f += L.conv_flops(hh // 2, ww // 2, c, c, 3)
    cm, hm, wm = chans[-1], h >> (levels - 1), w >> (levels - 1)
    f += 2 * _res_flops(hm, wm, cm, cm, temb)
    f += _tf_flops(hm * wm, cm, depth[-1], nt, dt)
    for lvl, i, cin, c in _up_inputs(m):
        hh, ww = h >> lvl, w >> lvl
        f += _res_flops(hh, ww, cin, c, temb)
        if depth[lvl]:
            f += _tf_flops(hh * ww, c, depth[lvl], nt, dt)
        if lvl and i == m["blocks_per_level"]:
            f += L.conv_flops(2 * hh, 2 * ww, c, c, 3)
    return f + L.conv_flops(h, w, W, C0, 3)


def kernel_shapes(cfg, patches_total):
    """GroupNorm-stitch at both GroupNorms of every ResBlock and before the
    output conv; attention per resolution at each level with a stack."""
    from bench.harness import gcd_patch
    m = cfg["model"]
    res = cfg["resolutions"]
    p = gcd_patch(res)
    chans = _chans(m)
    gn = {(patches_total, p, p, m["width"])}
    cin = m["width"]
    for lvl, c in enumerate(chans):
        for _ in range(m["blocks_per_level"]):
            gn |= {(patches_total, p >> lvl, p >> lvl, x) for x in (cin, c)}
            cin = c
    for lvl, _, cin, c in _up_inputs(m):
        gn |= {(patches_total, p >> lvl, p >> lvl, x) for x in (cin, c)}
    attn = set()
    for lvl, c in enumerate(chans):
        if m["tf_depth"][lvl]:
            for h, w in res:
                attn.add((4, c // m["head_dim"], (h >> lvl) * (w >> lvl),
                          m["head_dim"]))
    return {"groupnorm_stitch": gn, "patch_attention": attn}
