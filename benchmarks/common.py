"""Shared helpers: a tiny CPU engine for calibration scripts and the sim
cluster the sweep and its tests drive."""
from __future__ import annotations

from typing import List, Sequence

import jax
import numpy as np

from repro.core.requests import Request
from repro.core.scheduler import SchedulerConfig
from repro.core.serving import EngineConfig, PatchedServeEngine
from repro.models import diffusion as dm

RES = [(16, 16), (24, 24), (32, 32)]          # latent Low / Medium / High


def tiny_model(kind="unet", use_kernels=False, exact=True):
    cfg = dm.DiffusionConfig(kind=kind, width=32, levels=2, blocks_per_level=1,
                             n_heads=2, groups=4, d_text=16, n_text=4,
                             use_kernels=use_kernels, exact_stats=exact)
    return cfg, dm.init_diffusion(cfg, jax.random.PRNGKey(0))


def make_requests(counts: Sequence[int], steps=4, rid0=0) -> List[Request]:
    reqs = []
    rid = rid0
    rng = np.random.default_rng(0)
    for res, c in zip(RES, counts):
        for _ in range(c):
            r = Request(rid=rid, resolution=res, arrival=0.0, slo=1e9,
                        total_steps=steps)
            rid += 1
            reqs.append(r)
    return reqs


def real_engine(use_cache=False, policy="slo", same_res=False, tau=0.05,
                kind="unet"):
    cfg, params = tiny_model(kind)
    ecfg = EngineConfig(use_cache=use_cache, cache_tau=tau,
                        cache_capacity=256,   # sized to the tiny workloads
                        scheduler=SchedulerConfig(policy=policy,
                                                  same_res_only=same_res))
    return PatchedServeEngine(cfg, params, ecfg,
                              dict.fromkeys(map(tuple, RES), 1.0), RES)


def make_cluster(n_replicas=3, policy="round_robin", autoscaler=None,
                 steps=10, scale=1.0, record_timeseries=True,
                 initial_mix=None, repartition=None, cache=None,
                 failures=None, checkpoint=None, cache_tier=None,
                 trace=None, batcher=None, tiers=None, monitor=None):
    """Multi-replica sim cluster over the benchmark resolution ladder.
    Engines are accounting ``SimEngine``s (no tensors) with the
    patch-aware latency surrogate; pair with ``repro.cluster.simtools.cluster_workload`` so
    SLOs use the same standalone normalizers. ``cache=True`` (or a
    ``CacheHitModel``) makes the surrogate cache-aware; ``initial_mix`` +
    ``repartition`` drive the workload-adaptive affinity path; ``failures``
    (a ``FailureConfig``) injects Poisson replica crashes and correlated
    zone outages; ``checkpoint`` (a ``CheckpointConfig``) lets crash
    orphans resume from their last progress snapshot; ``cache_tier`` (a
    ``CacheTierConfig``) turns on the fleet patch-cache tier with
    per-replica L1 warmth dynamics (capacity_bytes=0: warmth dynamics
    without a fleet L2 — the no-tier baseline); ``trace`` (a
    ``TraceConfig``) turns on the per-request span tracer + fleet event
    bus (latency decomposition, SLO attribution, exporters); ``batcher``
    (a ``BatchFormerConfig``) turns on router-side gang batching — the
    former groups patch-compatible frontend work into gangs under
    per-request eligibility windows and each gang's predicted step-cost
    budget (None keeps per-request dispatch); ``tiers`` (a ``{name:
    count}`` dict over ``repro.cluster.replica.MODEL_TIERS``) builds a
    heterogeneous model-cascade fleet — replica count comes from the tier
    counts and ``n_replicas`` is ignored; ``monitor`` (a
    ``MonitorConfig``) turns on the streaming fleet health monitor —
    windowed timeseries over the trace bus, SLO burn-rate alerts,
    changepoint detection (None keeps monitoring off)."""
    from repro.cluster import Cluster, ClusterConfig, sim_engine_factory
    from repro.core.latency_model import CacheHitModel
    if cache is True:
        cache = CacheHitModel()
    factory = sim_engine_factory(RES, steps=steps, scale=scale,
                                 cache=cache or None)
    return Cluster(factory, RES,
                   ClusterConfig(n_replicas=n_replicas, policy=policy,
                                 autoscaler=autoscaler,
                                 initial_mix=initial_mix,
                                 repartition=repartition,
                                 failures=failures,
                                 checkpoint=checkpoint,
                                 cache_tier=cache_tier,
                                 trace=trace,
                                 monitor=monitor,
                                 batcher=batcher,
                                 tiers=tiers,
                                 record_timeseries=record_timeseries))
