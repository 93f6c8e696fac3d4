"""Serving launcher: run the PatchedServe engine on a Poisson workload of a
tiny model. ``python -m repro.launch.serve --qps 1.0 --duration 5 --cache``."""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.core.requests import poisson_workload
from repro.core.scheduler import SchedulerConfig
from repro.core.serving import EngineConfig, PatchedServeEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.models import diffusion as dm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="unet", choices=["unet", "dit"])
    ap.add_argument("--qps", type=float, default=1.0)
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--slo-scale", type=float, default=5.0)
    ap.add_argument("--cache", action="store_true")
    ap.add_argument("--policy", default="slo", choices=["slo", "fcfs"])
    args = ap.parse_args()
    enable_compile_cache()

    mcfg = dm.DiffusionConfig(kind=args.model, width=32, levels=2,
                              blocks_per_level=1, n_heads=2, groups=4,
                              d_text=16, n_text=4, use_kernels=False)
    params = dm.init_diffusion(mcfg, jax.random.PRNGKey(0))
    resolutions = [(16, 16), (24, 24), (32, 32)]
    ecfg = EngineConfig(use_cache=args.cache,
                        scheduler=SchedulerConfig(policy=args.policy))
    eng = PatchedServeEngine(mcfg, params, ecfg,
                             dict.fromkeys(map(tuple, resolutions), 1.0),
                             resolutions)
    eng.calibrate(total_steps_hint=args.steps)
    wl = poisson_workload(args.qps, args.duration, resolutions,
                          args.slo_scale, eng.sa, steps=args.steps)
    m = eng.run(wl)
    print(f"requests={len(wl)} completed={m.completed} dropped={m.dropped} "
          f"SLO={m.slo_satisfaction:.3f} goodput={m.goodput:.3f}/s "
          f"mean_step={np.mean(m.step_latencies)*1e3 if m.step_latencies else 0:.1f}ms "
          f"savings={np.mean(m.compute_savings) if m.compute_savings else 0.0:.2f}")


if __name__ == "__main__":
    main()
