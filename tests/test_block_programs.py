"""The model step's blocks as compiled segments (``diffusion._segment``):
they compute what the same code computes op by op, compile once per batch
composition whatever the depth, and leave each Pallas attention call on the
host, where the benchmark counts them."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.patching import split
from repro.core.requests import Request
from repro.core.serving import EngineConfig, PatchedServeEngine
from repro.kernels import ops
from repro.models import diffusion as dm

RES = [(16, 16), (24, 24), (32, 32)]


def tiny(kind: str, name: str = "tiny") -> dm.DiffusionConfig:
    return dm.DiffusionConfig(name=name, kind=kind, width=32, levels=2,
                              blocks_per_level=1, attn_levels=(1,),
                              dit_depth=3, n_heads=2, groups=4, d_text=16,
                              n_text=4, t_dim=32, use_kernels=True)


def inputs(cfg, res=RES):
    rng = np.random.default_rng(0)
    imgs = [jnp.asarray(rng.normal(size=(h, w, cfg.latent_channels)),
                        jnp.float32) for h, w in res]
    csp, patches = split(imgs, patch=8)
    t = jnp.asarray(np.linspace(900, 100, len(res)), jnp.float32)
    text = jnp.asarray(rng.normal(size=(len(res), cfg.n_text, cfg.d_text)),
                       jnp.float32)
    return csp, patches, t, text


def engine(cfg, params, **kw) -> PatchedServeEngine:
    ecfg = EngineConfig(clock="real", use_cache=True, cache_tau=0.05,
                        cache_capacity=128, **kw)
    return PatchedServeEngine(cfg, params, ecfg, dict.fromkeys(RES, 1.0), RES)


def two_hooked_steps(cfg, params, csp, patches, t, text):
    """Two evaluations through the engine's cache hook: the first computes
    every patch (nothing is cached yet), the second reuses half of them, the
    hook's partial-mask path."""
    eng = engine(cfg, params)
    eng.predictor = lambda delta: jnp.arange(delta.shape[0]) % 2 == 0
    for rid in csp.req_ids:
        eng._uid_base[int(rid)] = int(rid) << 20
    outs = []
    for x in (patches, patches * 0.9):
        hook, savings = eng._block_hook(csp, 0.5)
        outs.append(dm.denoise_patched(cfg, params, csp, x, t, text,
                                       block_hook=hook))
    assert 0.0 < np.mean(savings) < 1.0, savings
    return outs


@pytest.mark.parametrize("kind", ["dit", "unet"])
def test_segments_match_op_by_op(kind):
    """With the kernels on, every block through its compiled segments gives
    what the same code gives op by op (``jax.disable_jit``), on the plain
    path and on the cache hook's partial path."""
    cfg = tiny(kind)
    params = dm.init_diffusion(cfg, jax.random.PRNGKey(1))
    args = inputs(cfg, [(8, 8), (16, 16), (24, 24)])
    got = two_hooked_steps(cfg, params, *args)
    with jax.disable_jit():
        want = two_hooked_steps(cfg, params, *args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-3, atol=1e-4)


def test_composition_key_ignores_request_ids():
    a = split([jnp.zeros((h, w, 4)) for h, w in RES], patch=8,
              req_ids=[7, 8, 9])[0]
    b = split([jnp.zeros((h, w, 4)) for h, w in reversed(RES)], patch=8,
              req_ids=[3, 1, 2])[0]
    assert dm.Composition.of(a) == dm.Composition.of(b)
    assert hash(dm.Composition.of(a)) == hash(dm.Composition.of(b))
    c = split([jnp.zeros((h, w, 4)) for h, w in RES + [(16, 16)]],
              patch=8)[0]
    assert dm.Composition.of(c) != dm.Composition.of(a)


def test_traces_once_per_composition():
    """A tick at a seen composition traces nothing; a new composition traces
    each segment once, not once per block."""
    cfg = dataclasses.replace(tiny("dit", name="traces"), dit_depth=4)
    eng = engine(cfg, dm.init_diffusion(cfg, jax.random.PRNGKey(2)))
    m = eng.metrics

    def submit(rid, res):
        eng.submit(Request(rid=rid, resolution=res, arrival=0.0, slo=1e9,
                           total_steps=4))

    for i, res in enumerate(RES):
        submit(i, res)
    before = dict(dm.SEGMENT_TRACES)
    eng.tick(0.0)
    first = {k: v - before.get(k, 0) for k, v in dm.SEGMENT_TRACES.items()
             if v != before.get(k, 0)}
    assert m.block_traces == sum(first.values()) > 0
    # one program each for every DiT block, whatever the depth
    assert first["_attn_pre"] == first["_attn_post"] == 1
    assert first["_linear"] == 2                # tok_in and tok_out shapes
    assert max(first.values()) < cfg.dit_depth

    eng.tick(1.0)
    assert m.block_traces == sum(first.values())

    submit(3, RES[0])                           # composition (2, 1, 1)
    eng.tick(2.0)
    assert m.block_traces == 2 * sum(first.values())
    eng.tick(3.0)
    assert m.block_traces == 2 * sum(first.values())


@pytest.mark.parametrize("kind,attn_blocks", [("dit", 3), ("unet", 3)])
def test_attention_kernel_called_on_host(kind, attn_blocks, monkeypatch):
    """The attention kernel is called through ``ops.grouped_attention_kernel``
    at run time, once per resolution group per executed attention block, so
    that a wrapper swapped in there (as the benchmark's
    ``patch_attention_roofline`` does) sees every call and its shape."""
    cfg = tiny(kind, name="host-calls")
    params = dm.init_diffusion(cfg, jax.random.PRNGKey(3))
    csp, patches, t, text = inputs(cfg)
    calls = []
    real = ops.grouped_attention_kernel

    def counted(q, k, v, *a, **kw):
        calls.append(tuple(q.shape))
        return real(q, k, v, *a, **kw)

    monkeypatch.setattr(ops, "grouped_attention_kernel", counted)
    for _ in range(2):                          # compiled, then warm
        calls.clear()
        dm.denoise_patched(cfg, params, csp, patches, t, text)
        assert len(calls) == attn_blocks * csp.n_groups
    hd = cfg.width // cfg.n_heads
    if kind == "dit":
        assert sorted(set(calls)) == [(1, cfg.n_heads, h * w, hd)
                                      for h, w in RES]
