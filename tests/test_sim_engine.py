"""The split between the device engine and the cluster's accounting engine:
the accounting engine's numbers, what each engine accepts, and what the
served path imports."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.cluster import (DEFAULT_RES, SimEngine, cluster_workload,
                           sim_engine_factory)
from repro.core.latency_model import CacheHitModel
from repro.core.requests import Request
from repro.core.serving import EngineConfig, PatchedServeEngine
from repro.models import diffusion as dm

# (completed, slo_met, dropped, steps, sum of latencies) of one engine over
# cluster_workload(qps=16, duration=20, seed=7), recorded by this test's body
# at commit 8a019ec, where the accounting was a sim-clock mode of
# PatchedServeEngine; the split must not move it.
GOLDEN = {
    ("slo", "analytic"): (272, 272, 32, 1157, 58.48299398932955),
    ("slo", "patch_aware"): (268, 268, 36, 1147, 58.70743038700929),
    ("slo", "patch_aware_cache"): (284, 284, 20, 1449, 45.09385842223414),
    ("fcfs", "analytic"): (268, 268, 36, 1164, 56.38259814714479),
    ("fcfs", "patch_aware"): (260, 260, 44, 1146, 55.62902736456886),
    ("fcfs", "patch_aware_cache"): (281, 281, 23, 1454, 44.06488050250493),
}
# sum of the modeled hit rates the cache-aware surrogate reports, per policy
GOLDEN_HITS = {"slo": 642.0737338994484, "fcfs": 646.7101439301055}


@pytest.mark.parametrize("surrogate",
                         ["analytic", "patch_aware", "patch_aware_cache"])
@pytest.mark.parametrize("policy", ["slo", "fcfs"])
def test_accounting_engine_golden(policy, surrogate):
    factory = sim_engine_factory(
        DEFAULT_RES, sched_policy=policy,
        cache=CacheHitModel() if surrogate == "patch_aware_cache" else None)
    eng = factory(DEFAULT_RES)
    if surrogate == "analytic":
        eng.latency_model = None          # analytic_step_latency
    m = eng.run(cluster_workload(qps=16.0, duration=20.0, seed=7))
    *counts, lat_sum = GOLDEN[(policy, surrogate)]
    assert [m.completed, m.slo_met, m.dropped,
            len(m.step_latencies)] == counts
    assert sum(m.latencies) == pytest.approx(lat_sum, rel=0, abs=1e-9)
    if surrogate == "patch_aware_cache":
        assert len(m.compute_savings) == len(m.step_latencies)
        assert sum(m.compute_savings) == pytest.approx(
            GOLDEN_HITS[policy], rel=0, abs=1e-9)
    else:
        assert m.compute_savings == []


def test_device_engine_rejects_sim_clock_and_accounting_has_no_tensors():
    cfg = dm.DiffusionConfig(kind="unet", width=16, levels=2,
                             blocks_per_level=1, n_heads=2, groups=4,
                             d_text=8, n_text=2, use_kernels=False)
    with pytest.raises(ValueError, match="clock"):
        PatchedServeEngine(cfg, None, EngineConfig(clock="sim"), {},
                           DEFAULT_RES)

    eng = sim_engine_factory(DEFAULT_RES)(DEFAULT_RES)
    assert isinstance(eng, SimEngine)
    reqs = [Request(rid=i, resolution=res, arrival=0.0, slo=1e9,
                    total_steps=3)
            for i, res in enumerate(DEFAULT_RES * 2)]
    for r in reqs:
        eng.submit(r)
    end, events = eng.drain(now=0.0)
    assert all(r.state == "done" for r in reqs)
    assert all(r.latent is None and r.text is None for r in reqs)
    assert end == pytest.approx(sum(ev.dt for ev in events))


def test_device_engine_straggler_rule():
    """A measured step over ``straggler_factor`` x its prediction drops the
    active requests it made hopeless; a cold composition's first step is
    exempt."""
    cfg = dm.DiffusionConfig(kind="unet", width=16, levels=2,
                             blocks_per_level=1, n_heads=2, groups=4,
                             d_text=8, n_text=2, use_kernels=False)
    res = DEFAULT_RES[0]
    eng = PatchedServeEngine(cfg, None, EngineConfig(), {res: 1.0}, [res])

    def slow_step(active):                # every step takes 100 s
        for r in active:
            r.steps_done += 1
        return [], 100.0

    eng._timed_step = slow_step
    keep = Request(rid=0, resolution=res, arrival=0.0, slo=1e9,
                   total_steps=4)
    late = Request(rid=1, resolution=res, arrival=0.0, slo=350.0,
                   total_steps=4)
    eng.submit(keep)
    eng.submit(late)
    first = eng.tick(0.0)                 # cold: 100 + 3 x 100 > 350 kept
    assert first.stepped and not first.dropped
    second = eng.tick(first.end)          # warm: 200 + 2 x 100 > 350
    assert second.dropped == [late] and late.state == "dropped"
    assert eng.active == [keep] and eng.metrics.dropped == 1


def test_served_path_does_not_load_lm_stack():
    """The engine and the cluster layer import none of the LM training
    stack (checked in a fresh interpreter, since other tests load it)."""
    lm_stack = ["repro.models.lm", "repro.models.moe", "repro.models.mamba",
                "repro.models.attention", "repro.models.flash",
                "repro.launch.mesh"]
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(src), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro.core.serving, repro.cluster; "
         f"print([m for m in {lm_stack!r} if m in sys.modules])"],
        env=env, capture_output=True, text=True, timeout=300, check=True)
    assert out.stdout.strip() == "[]", out.stdout + out.stderr
