"""The program's `dit` kind: a token projection in, `dit_depth` attention
blocks on 1x1 tokens gated by one adaLN modulation (scale, shift, gate)
from the timestep, a GroupNorm and a token projection out. Sampled by
flow-Euler."""
from __future__ import annotations

import jax.numpy as jnp

from bench.models import _layers as L

SAMPLER = "flow"


def weight_spec(m):
    spec = []
    t, W, C0 = m["t_dim"], m["width"], m["latent_channels"]
    L.time_mlp_spec(spec, t)
    spec += [("tok_in", (C0, W), None), ("tok_in_b", (W,), "zeros"),
             ("adaln_w", (t, 3 * W), 0.02), ("adaln_b", (3 * W,), "zeros")]
    for i in range(m["dit_depth"]):
        L.attn_spec(spec, f"blk{i}", W, m["d_text"])
    L.norm_spec(spec, "out_norm", W)
    spec += [("tok_out", (W, C0), 0.02), ("tok_out_b", (C0,), "zeros")]
    return spec


def forward(cfg, P, x, t, text, run):
    temb = L.time_mlp(cfg, P, t, x.dtype)
    x = run("tok_in", lambda z: z @ P["tok_in"] + P["tok_in_b"], x)
    mod = L.silu(temb) @ P["adaln_w"] + P["adaln_b"]
    sc, sh, gate = (m[:, None, None] for m in jnp.split(mod, 3, axis=-1))
    for i in range(cfg["dit_depth"]):
        name = f"blk{i}"

        def blk(z, n=name):
            h = L.attn_block(cfg, P[n], z * (1 + sc) + sh, text)
            return z + gate * (h - z)

        x = run(name, blk, x)
    x = L.group_norm(x, P["out_norm"], cfg["groups"])
    return run("tok_out", lambda z: z @ P["tok_out"] + P["tok_out_b"], x)


def step_flops(m, h, w):
    t, W, C0 = m["t_dim"], m["width"], m["latent_channels"]
    s = h * w
    f = L.time_mlp_flops(t) + 2 * s * C0 * W * 2 + 2 * t * 3 * W
    return f + m["dit_depth"] * L.attn_flops(s, W, m["n_text"], m["d_text"])


def kernel_shapes(cfg, patches_total):
    """Attention over each resolution's whole image; no GroupNorm-stitch."""
    m = cfg["model"]
    attn = {(4, m["n_heads"], h * w, m["width"] // m["n_heads"])
            for h, w in cfg["resolutions"]}
    return {"groupnorm_stitch": set(), "patch_attention": attn}
