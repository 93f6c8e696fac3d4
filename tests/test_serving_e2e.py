"""End-to-end serving: real-clock tiny run, scheduler properties on the
accounting engine's virtual clock,
quality preservation under patched execution + caching off."""
import jax
import numpy as np
import pytest

from repro.cluster import SimEngine
from repro.core.latency_model import analytic_step_latency
from repro.core.requests import poisson_workload
from repro.core.scheduler import SchedulerConfig
from repro.core.serving import EngineConfig, PatchedServeEngine
from repro.models import diffusion as dm

RES = [(16, 16), (24, 24), (32, 32)]


def tiny_model():
    cfg = dm.DiffusionConfig(kind="unet", width=16, levels=2,
                             blocks_per_level=1, n_heads=2, groups=4,
                             d_text=8, n_text=2, use_kernels=False)
    return cfg, dm.init_diffusion(cfg, jax.random.PRNGKey(0))


def sim_engine(policy="slo", use_cache=False, seed=0):
    ecfg = EngineConfig(use_cache=use_cache,
                        scheduler=SchedulerConfig(policy=policy))
    eng = SimEngine(ecfg, dict.fromkeys(map(tuple, RES), 1.0), RES)
    for res in eng.resolutions:
        eng.sa[res] = analytic_step_latency(
            [1 if r == res else 0 for r in eng.resolutions],
            eng.patches_per_res) * 10
    return eng


def _wl(eng, qps, duration=30.0, seed=0, slo_scale=5.0, steps=10):
    return poisson_workload(qps, duration, RES, slo_scale, eng.sa,
                            steps=steps, seed=seed)


def test_sim_all_served_at_low_qps():
    eng = sim_engine()
    m = eng.run(_wl(eng, qps=1.0, duration=20))
    assert m.completed > 0
    assert m.slo_satisfaction > 0.9


def test_sim_slo_degrades_with_qps():
    slos = []
    for qps in (2.0, 40.0):
        eng = sim_engine()
        m = eng.run(_wl(eng, qps=qps, duration=20))
        slos.append(m.slo_satisfaction)
    assert slos[0] >= slos[1]


def test_slo_policy_beats_fcfs_under_load():
    res = {}
    for pol in ("slo", "fcfs"):
        eng = sim_engine(policy=pol)
        m = eng.run(_wl(eng, qps=25.0, duration=30, seed=3))
        res[pol] = m.slo_satisfaction
    assert res["slo"] >= res["fcfs"] - 0.02, res


@pytest.mark.slow
def test_real_clock_end_to_end():
    cfg, params = tiny_model()
    ecfg = EngineConfig(clock="real", use_cache=False)
    eng = PatchedServeEngine(cfg, params, ecfg,
                             dict.fromkeys(map(tuple, RES), 1.0), RES)
    eng.calibrate(total_steps_hint=4)
    wl = poisson_workload(1.0, 2.0, RES, 20.0, eng.sa, steps=4, seed=2)
    m = eng.run(wl, max_wall=240)
    assert m.completed >= 1
    for img in eng.outputs.values():
        assert np.all(np.isfinite(img))
        assert img.shape[-1] == 3


@pytest.mark.slow
def test_cache_produces_savings_and_finite_outputs():
    cfg, params = tiny_model()
    ecfg = EngineConfig(clock="real", use_cache=True, cache_tau=0.05)
    eng = PatchedServeEngine(cfg, params, ecfg,
                             dict.fromkeys(map(tuple, RES), 1.0), RES)
    eng.calibrate(total_steps_hint=4)
    wl = poisson_workload(1.5, 2.0, RES, 30.0, eng.sa, steps=4, seed=2)
    assert wl, "empty workload"
    m = eng.run(wl, max_wall=240)
    assert m.completed >= 1
    assert m.compute_savings and np.mean(m.compute_savings) > 0.0
    for img in eng.outputs.values():
        assert np.all(np.isfinite(img))
