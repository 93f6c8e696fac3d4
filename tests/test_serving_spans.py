"""The serving engine's own measurement: the host spans it records under a
profiler session (``serving.SPANS``) and its counters in ``Metrics``
(``host_syncs``, ``step_predicted``), on a tiny DiT of depth 2."""
import glob
import os

import jax
import pytest

from repro.core.requests import Request
from repro.core.serving import SPANS, EngineConfig, PatchedServeEngine
from repro.models import diffusion as dm

RES = [(16, 16), (24, 24), (32, 32)]
DEPTH = 2
HOOKED = DEPTH + 2                 # tok_in, blk0..blk{DEPTH-1}, tok_out
# span -> the span it runs inside (with the cache on)
PARENT = {"ps.schedule": "ps.tick", "ps.csp_split": "ps.tick",
          "ps.sampler": "ps.tick", "ps.cache": "ps.sampler",
          "ps.block": "ps.cache", "ps.csp_merge": "ps.tick",
          "ps.wait": "ps.tick", "ps.decode": "ps.tick"}


def engine(use_cache: bool) -> PatchedServeEngine:
    cfg = dm.DiffusionConfig(kind="dit", width=32, dit_depth=DEPTH,
                             n_heads=2, groups=8, d_text=16, n_text=4,
                             t_dim=32, use_kernels=False)
    params = dm.init_diffusion(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(clock="real", use_cache=use_cache, cache_tau=0.05,
                        cache_capacity=228)
    return PatchedServeEngine(cfg, params, ecfg, dict.fromkeys(RES, 1.0), RES)


def submit_all(eng, steps):
    for i, res in enumerate(RES):
        eng.submit(Request(rid=i, resolution=res, arrival=0.0, slo=1e9,
                           total_steps=steps))


def host_spans(trace_dir):
    """{name: [(start, end)]} of the program's spans on the host planes."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("ps."):
                        out.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return out


@pytest.mark.parametrize("use_cache", [True, False])
def test_tick_records_spans_nested(use_cache, tmp_path):
    """One tick that also completes its requests puts every span on the
    host plane, each inside its parent; the plain path (cache off) records
    ``ps.block`` from ``denoise_patched``."""
    eng = engine(use_cache)
    submit_all(eng, steps=1)
    with jax.profiler.trace(str(tmp_path)):
        ev = eng.tick(0.0)
    assert len(ev.completed) == len(RES)
    spans = host_spans(str(tmp_path))
    want = set(SPANS) - ({"ps.cache"} if not use_cache else set())
    assert set(spans) == want
    assert len(spans["ps.tick"]) == 1
    assert len(spans["ps.block"]) == HOOKED
    assert len(spans["ps.decode"]) == len(RES)
    parents = dict(PARENT, **({} if use_cache else {"ps.block": "ps.sampler"}))
    for child, parent in parents.items():
        for a, b in spans.get(child, ()):
            assert any(pa <= a and b <= pb
                       for pa, pb in spans.get(parent, ())), (child, parent)


@pytest.mark.parametrize("use_cache,per_step", [(True, HOOKED + 1),
                                                (False, 1)])
def test_host_syncs_and_predictions_per_step(use_cache, per_step):
    """A step waits once on its latents and, with the cache on, reads each
    hooked block's reuse mask; a decode reads its image."""
    eng = engine(use_cache)
    submit_all(eng, steps=2)
    m = eng.metrics
    eng.tick(0.0)
    assert m.host_syncs == per_step
    ev = eng.tick(1.0)
    assert len(ev.completed) == len(RES)
    assert m.host_syncs == 2 * per_step + len(RES)
    assert len(m.step_predicted) == len(m.step_latencies) == 2
    assert m.step_predicted[0] == pytest.approx(
        eng._predict_step_latency(ev.completed))
