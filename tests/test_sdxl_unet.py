"""The ``sdxl`` kind (SDXL-base's UNet2DConditionModel) on the patched
serving path, against the benchmark's plain reference
(``bench/models/sdxl.py``: whole images, jnp, float32), at a tiny
SDXL-shaped size on the CPU, and its layout at the published widths."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.models import sdxl as ref  # noqa: E402
from repro.core.patching import merge_by_request, split  # noqa: E402
from repro.core.requests import Request  # noqa: E402
from repro.core.serving import EngineConfig, PatchedServeEngine  # noqa: E402
from repro.models import diffusion as dm  # noqa: E402

RES = [(16, 16), (24, 24), (32, 32)]
TINY = dict(kind="sdxl", latent_channels=4, width=32, levels=3,
            blocks_per_level=2, tf_depth=(0, 1, 2), head_dim=8, groups=8,
            d_text=16, n_text=4, t_dim=32, temb_dim=128, d_pooled=8,
            d_size_id=8)
# SDXL-base's unet/config.json
PUBLISHED = dict(TINY, width=320, tf_depth=(0, 2, 10), head_dim=64,
                 groups=32, d_text=2048, n_text=77, t_dim=320, temb_dim=1280,
                 d_pooled=1280, d_size_id=256)


def config(**over):
    return dm.DiffusionConfig(name="tiny-sdxl", **dict(TINY, **over))


@pytest.fixture(scope="module")
def params():
    return dm.init_diffusion(config(), jax.random.PRNGKey(5))


def inputs():
    rng = np.random.default_rng(0)
    lats = [jnp.asarray(rng.normal(size=(h, w, 4)), jnp.float32)
            for h, w in RES]
    texts = [jnp.asarray(rng.normal(size=(4, 16)) * 0.3, jnp.float32)
             for _ in RES]
    return lats, texts


T = jnp.asarray([900.0, 500.0, 100.0])


@pytest.fixture(scope="module")
def reference(params):
    """The plain reference's output for each whole image."""
    lats, texts = inputs()
    m = dataclasses.asdict(config())
    fwd = jax.jit(lambda p, x, t, c: ref.forward(m, p, x, t, c,
                                                 lambda n, fn, z: fn(z)))
    with jax.default_matmul_precision("highest"):
        return [np.asarray(fwd(params, lats[i][None], T[i:i + 1],
                               texts[i][None])[0]) for i in range(len(RES))]


# Program and reference run the same float32 mathematics at "highest"
# precision; they differ in the order of sums (convolutions over stitched
# halos, GroupNorm statistics by segment, attention per resolution group and,
# with the kernels on, online softmax), about 1e-7 relative per operation,
# which 34 blocks grow to about 2e-6 (measured 1.0e-6 to 1.7e-6).
@pytest.mark.parametrize("use_kernels", [False, True])
def test_patched_program_matches_reference(params, reference, use_kernels):
    cfg = config(use_kernels=use_kernels)
    lats, texts = inputs()
    csp, patches = split(lats, patch=8, req_ids=[0, 1, 2])
    text = jnp.stack([texts[int(r)] for r in csp.req_ids])
    with jax.default_matmul_precision("highest"):
        out = merge_by_request(csp, dm.denoise_patched(
            cfg, params, csp, patches, T[jnp.asarray(csp.req_ids)], text))
    for i, want in enumerate(reference):
        got = np.asarray(out[i])
        err = np.linalg.norm(got - want) / np.linalg.norm(want)
        assert err < 1e-5, (RES[i], err)


def test_block_plan_and_skips(params):
    """34 cached blocks at SDXL's structure, run in the plan's order; each up
    ResBlock takes the skip pushed last: conv_in's output, then each down
    ResBlock's (or its stack's) and each Downsample's, popped in reverse."""
    plan = dm.sdxl_blocks(dm.DiffusionConfig(**PUBLISHED))
    assert len(plan) == 34
    assert [n for n, *_ in plan[:9]] == [
        "conv_in", "down0_res0", "down0_res1", "down0_ds", "down1_res0",
        "down1_tf0", "down1_res1", "down1_tf1", "down1_ds"]
    assert [cin for n, _, cin, _ in plan if n.startswith("up") and "_res" in n
            ] == [2560, 2560, 1920, 1920, 1280, 960, 960, 640, 640]
    assert dm.block_plan(dm.DiffusionConfig(**PUBLISHED))[-1] == (
        "out", "context", 0)

    cfg = config(use_kernels=False)
    lats, texts = inputs()
    csp, patches = split(lats, patch=8, req_ids=[0, 1, 2])
    seen = []

    def run(name, kind, fn, x):
        seen.append((name, x.shape[-1]))
        return fn(x)

    jax.eval_shape(lambda p, x: dm.denoise_patched(
        cfg, p, csp, x, jnp.ones((3,)), jnp.stack(texts), block_hook=run),
        params, patches)
    assert [n for n, _ in seen] == [n for n, *_ in dm.sdxl_blocks(cfg)]
    assert [c for _, c in seen] == [cin for _, _, cin, _ in
                                    dm.sdxl_blocks(cfg)]


@pytest.mark.parametrize("res", [[(16, 16)], [(16, 24), (32, 16)]])
def test_downsample_pads_one_on_every_side(res):
    rng = np.random.default_rng(1)
    imgs = [jnp.asarray(rng.normal(size=(h, w, 6)), jnp.float32)
            for h, w in res]
    p = {"w": jnp.asarray(rng.normal(size=(3, 3, 6, 5)), jnp.float32),
         "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
    csp, patches = split(imgs, patch=8)
    comp = dm.Composition.of(csp)
    with jax.default_matmul_precision("highest"):
        out = merge_by_request(csp, dm._down_pad1(
            config(), comp, dm.csp_arrays(csp), p, patches))
        for i, img in enumerate(imgs):
            want = jax.lax.conv_general_dilated(
                img[None], p["w"], (2, 2), ((1, 1), (1, 1)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))[0] + p["b"]
            np.testing.assert_allclose(np.asarray(out[i]), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


def _published_counts():
    """Parameters of each module of SDXL-base's UNet, from diffusers'
    layers at the config's widths: {module: count}."""
    def conv(cin, cout, k=3):
        return k * k * cin * cout + cout

    def res(cin, cout):
        n = 2 * cin + conv(cin, cout) + 1280 * cout + cout + 2 * cout \
            + conv(cout, cout)
        return n + (conv(cin, cout, 1) if cin != cout else 0)

    def tf(c, depth):
        layer = 3 * (2 * c) + 4 * c * c + c + c * c + 2 * 2048 * c \
            + c * c + c + c * 8 * c + 8 * c + 4 * c * c + c
        return 2 * c + 2 * (c * c + c) + depth * layer

    want = {"temb": 320 * 1280 + 1280 + 1280 * 1280 + 1280,
            "add": 2816 * 1280 + 1280 + 1280 * 1280 + 1280,
            "conv_in": conv(4, 320), "out": 2 * 320 + conv(320, 4),
            "down0_res0": res(320, 320), "down0_res1": res(320, 320),
            "down0_ds": conv(320, 320), "down1_res0": res(320, 640),
            "down1_res1": res(640, 640), "down1_ds": conv(640, 640),
            "down2_res0": res(640, 1280), "down2_res1": res(1280, 1280),
            "mid_res0": res(1280, 1280), "mid_tf": tf(1280, 10),
            "mid_res1": res(1280, 1280), "up2_us": conv(1280, 1280),
            "up1_us": conv(640, 640)}
    for i in range(2):
        want[f"down1_tf{i}"] = tf(640, 2)
        want[f"down2_tf{i}"] = tf(1280, 10)
    for i, (c2, c1, c0) in enumerate([(2560, 1920, 960), (2560, 1280, 640),
                                      (1920, 960, 640)]):
        want[f"up2_res{i}"] = res(c2, 1280)
        want[f"up1_res{i}"] = res(c1, 640)
        want[f"up0_res{i}"] = res(c0, 320)
        want[f"up2_tf{i}"] = tf(1280, 10)
        want[f"up1_tf{i}"] = tf(640, 2)
    return want


def test_published_parameter_count():
    shapes = jax.eval_shape(lambda: dm.init_diffusion(
        dm.DiffusionConfig(**PUBLISHED), jax.random.PRNGKey(0)))
    got = {}
    for k, v in shapes.items():
        module = ("out" if k.startswith("out_") else k.rsplit("_", 1)[0]
                  if k.startswith(("temb_", "add_")) else k)
        got[module] = got.get(module, 0) + sum(
            int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(v))
    want = _published_counts()
    differ = {k: (got.get(k), want.get(k)) for k in set(got) | set(want)
              if got.get(k) != want.get(k)}
    assert not differ, f"modules whose count differs (program, diffusers): {differ}"
    assert sum(got.values()) == 2_567_463_684


def test_engine_serves_mixed_sizes_through_the_cache(params):
    cfg = config()
    eng = PatchedServeEngine(
        cfg, params, EngineConfig(clock="real", use_cache=True,
                                  cache_tau=0.05, cache_capacity=64),
        dict.fromkeys(RES, 1.0), RES)
    for rid, res in enumerate(RES * 2):
        eng.submit(Request(rid=rid, resolution=res, arrival=0.0, slo=1e9,
                           total_steps=3))
    _, events = eng.drain()
    m = eng.metrics
    assert m.completed == 6 and m.dropped == 0
    assert m.compute_savings and sum(len(e.completed) for e in events) == 6
    assert all(np.isfinite(img).all() for img in eng.outputs.values())
