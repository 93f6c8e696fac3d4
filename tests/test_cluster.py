"""Cluster serving — deterministic sim-clock tests: steppable-engine
equivalence, dispatch-policy ordering, affinity partitioning, autoscaler
convergence, cold start, unroutable-work handling, the workload-adaptive
layer (drift detection, drain-before-switch repartitioning, predictive
autoscaling, cache-aware latency surrogate), the elastic fleet controller
(predictive scale-down, fleet-size-aware repartitioning, replica failure
injection + recovery), and the fault-tolerance layer (partial-progress
checkpointing, correlated zone outages, fault-domain-aware dispatch)."""
import json

import jax
import numpy as np
import pytest

from repro.cluster import (AutoscalerConfig, CheckpointConfig, Cluster,
                           ClusterConfig, FailureConfig, MixTracker,
                           Replica, RepartitionConfig,
                           allocate_replica_counts, mix_drift,
                           partition_resolutions, phased_workload,
                           piecewise_rate_workload, ramp_workload,
                           sim_engine_factory)
from repro.cluster.simtools import (CRASH_FAULTS, DEFAULT_RES, UPDOWN_KNOTS,
                                    ZONE_FAULTS, PatchAwareLatency,
                                    cluster_workload)
from repro.core.csp import gcd_patch_size
from repro.core.latency_model import (CacheHitModel, fit_cache_hit_model,
                                      patch_aware_step_latency,
                                      resolution_concentration)
from repro.core.requests import Request
from repro.core.serving import EngineConfig, PatchedServeEngine
from repro.models import diffusion as dm

SKEW = (0.2, 0.2, 0.6)          # mostly-High mix: stresses routing
MIX_A = (0.6, 0.3, 0.1)         # drift scenario: Low-heavy ...
MIX_B = (0.1, 0.3, 0.6)         # ... flipping to High-heavy


def _cluster(policy, n=3, autoscaler=None, record=False):
    return Cluster(sim_engine_factory(DEFAULT_RES), DEFAULT_RES,
                   ClusterConfig(n_replicas=n, policy=policy,
                                 autoscaler=autoscaler,
                                 record_timeseries=record))


def _fleet(policy, qps, n=3, seed=1, mix=SKEW, duration=30.0, **kw):
    cl = _cluster(policy, n=n, **kw)
    return cl.run(cluster_workload(qps=qps, duration=duration, seed=seed,
                                   mix=mix)), cl


# ---------------- steppable engine API ----------------

def test_steppable_api_matches_run():
    """submit/tick driven externally reproduces the run() wrapper exactly
    on the sim clock."""
    factory = sim_engine_factory(DEFAULT_RES)
    wl = cluster_workload(qps=8.0, duration=10.0, seed=0)

    ref = factory(DEFAULT_RES).run([Request(**{
        k: getattr(r, k) for k in
        ("rid", "resolution", "arrival", "slo", "total_steps", "prompt")})
        for r in wl])

    eng = factory(DEFAULT_RES)
    pending = sorted(wl, key=lambda r: r.arrival)
    now = 0.0
    while pending or eng.has_work:
        if not eng.has_work and pending:
            now = max(now, pending[0].arrival)
        while pending and pending[0].arrival <= now:
            eng.submit(pending.pop(0))
        ev = eng.tick(now)
        if ev.stepped:
            now = ev.end
        elif not eng.active and pending:
            now = pending[0].arrival
    m = eng.metrics
    assert (m.completed, m.dropped, m.slo_met) == \
        (ref.completed, ref.dropped, ref.slo_met)
    np.testing.assert_allclose(m.latencies, ref.latencies)


def test_drain_empties_engine():
    eng = sim_engine_factory(DEFAULT_RES)(DEFAULT_RES)
    for r in cluster_workload(qps=50.0, duration=0.2, seed=0):
        eng.submit(r)
    assert eng.has_work
    end, events = eng.drain(now=0.0)
    assert not eng.has_work
    assert end > 0.0 and any(ev.stepped for ev in events)
    assert eng.metrics.completed + eng.metrics.dropped > 0


# ---------------- affinity partitioning ----------------

def test_partition_resolutions_maximizes_min_gcd():
    assert partition_resolutions(DEFAULT_RES, 1) == [sorted(DEFAULT_RES)]
    two = partition_resolutions(DEFAULT_RES, 2)
    # best split keeps 16/32 together (gcd 16) and isolates 24 (gcd 24)
    assert sorted(map(tuple, sum(two, []))) == sorted(map(tuple, DEFAULT_RES))
    assert min(gcd_patch_size(b) for b in two) == 16
    three = partition_resolutions(DEFAULT_RES, 3)
    assert [gcd_patch_size(b) for b in three] == [16, 24, 32]


def test_allocate_replica_counts_covers_all_blocks():
    blocks = partition_resolutions(DEFAULT_RES, 2)
    counts = allocate_replica_counts(blocks, 5)
    assert sum(counts) == 5 and min(counts) >= 1


# ---------------- dispatch policy ordering (issue checks a+b) ----------

def test_join_shortest_queue_beats_round_robin_on_skew():
    jsq, _ = _fleet("join_shortest_queue", qps=48.0)
    rr, _ = _fleet("round_robin", qps=48.0)
    assert jsq.slo_satisfaction > rr.slo_satisfaction, \
        (jsq.slo_satisfaction, rr.slo_satisfaction)


def test_least_slack_beats_round_robin_under_load():
    ls, _ = _fleet("least_slack", qps=48.0)
    rr, _ = _fleet("round_robin", qps=48.0)
    assert ls.slo_satisfaction > rr.slo_satisfaction
    assert ls.goodput >= rr.goodput


def test_resolution_affinity_grows_patches_and_wins():
    aff, cl = _fleet("resolution_affinity", qps=48.0)
    rr, _ = _fleet("round_robin", qps=48.0)
    mixed_patch = gcd_patch_size(DEFAULT_RES)
    patches = [rep.patch for rep in aff.per_replica.values()]
    # every affinity replica runs a strictly larger GCD patch than mixed
    # routing's fleet-wide GCD
    assert min(patches) > mixed_patch
    assert all(rep.patch == mixed_patch
               for rep in rr.per_replica.values())
    assert aff.slo_satisfaction > rr.slo_satisfaction
    # nothing got lost across the partition
    assert aff.completed + aff.dropped == rr.completed + rr.dropped


# ---------------- autoscaler (issue check c) ----------------

def test_autoscaler_converges_under_constant_qps():
    cl = _cluster("join_shortest_queue", n=1,
                  autoscaler=AutoscalerConfig(min_replicas=1,
                                              max_replicas=6),
                  record=True)
    m = cl.run(cluster_workload(qps=32.0, duration=60.0, seed=2, mix=None))
    counts = [(t, n) for t, _, _, n in m.queue_ts]
    last_third = [n for t, n in counts if t > m.span * 2 / 3]
    assert last_third, "no time series recorded"
    # scaled up from 1 and settled on one stable count
    assert min(last_third) == max(last_third)
    assert 1 < last_third[0] <= 6
    # the ramp is monotone: no down-scaling while load is constant
    assert all(a > 0 for _, a in cl.autoscaler.actions)
    assert m.slo_satisfaction > 0.9


def test_cold_start_delays_readiness():
    eng = sim_engine_factory(DEFAULT_RES)(DEFAULT_RES)
    rep = Replica(0, eng, spawn_at=1.0, cold_start=2.0)
    assert not rep.ready(2.9)
    assert rep.ready(3.0)
    assert rep.alive_span(end=5.0) == pytest.approx(4.0)


def test_autoscaler_cold_start_charged():
    """During warm-up the new replica takes nothing; frontend pressure only
    drains after ready_at."""
    cfg = AutoscalerConfig(min_replicas=1, max_replicas=2, cold_start=3.0,
                           cooldown=1.0)
    cl = _cluster("join_shortest_queue", n=1, autoscaler=cfg, record=True)
    m = cl.run(cluster_workload(qps=32.0, duration=15.0, seed=2, mix=None))
    spawned = [r for r in cl.replicas if r.spawn_at > 0.0]
    assert spawned, "autoscaler never scaled up"
    for rep in spawned:
        assert rep.ready_at == pytest.approx(rep.spawn_at + 3.0)
        served = rep.engine.metrics.completed + rep.engine.metrics.dropped
        if served:
            # nothing finished before the replica was ready
            assert all(lat >= 0 for lat in rep.engine.metrics.latencies)
            assert rep.busy_time == 0.0 or rep.next_free >= rep.ready_at


# ---------------- router edge cases ----------------

def test_unroutable_resolution_is_dropped_not_hung():
    cl = _cluster("round_robin", n=2)
    odd = Request(rid=0, resolution=(40, 40), arrival=0.0, slo=10.0,
                  total_steps=2)
    m = cl.run([odd])
    assert m.router_dropped == 1
    assert odd.state == "dropped"
    assert m.completed == 0


def test_fleet_conservation():
    """Every request ends exactly once: completed or dropped."""
    for policy in ("round_robin", "join_shortest_queue", "least_slack",
                   "resolution_affinity"):
        m, _ = _fleet(policy, qps=24.0, duration=10.0)
        wl = cluster_workload(qps=24.0, duration=10.0, seed=1, mix=SKEW)
        assert m.completed + m.dropped == len(wl), policy


# ---------------- drift detection (adaptive layer) ----------------

def _feed(tracker, mix, t0, n, seed, qps=20.0):
    rng = np.random.default_rng(seed)
    t = t0
    for _ in range(n):
        t += rng.exponential(1.0 / qps)
        i = rng.choice(len(DEFAULT_RES), p=np.asarray(mix) / np.sum(mix))
        tracker.observe(t, DEFAULT_RES[i])
    return t


def test_drift_detector_fires_on_shift_not_noise():
    """Windowed mix drift crosses the threshold on a real mix flip but not
    under resampling noise of an unchanged mix."""
    threshold = RepartitionConfig().drift_threshold
    for seed in (0, 1, 2):
        tr = MixTracker(DEFAULT_RES, window=10.0)
        t = _feed(tr, MIX_A, 0.0, 120, seed)
        # noise only: fresh samples from the same mix stay under threshold
        assert mix_drift(tr.mix(t), MIX_A) < threshold
        # real shift: window fills with MIX_B arrivals
        t = _feed(tr, MIX_B, t, 250, seed + 10)
        assert mix_drift(tr.mix(t), MIX_A) > threshold


def test_mix_tracker_window_forgets_old_arrivals():
    tr = MixTracker(DEFAULT_RES, window=5.0)
    tr.observe(0.0, DEFAULT_RES[0])
    tr.observe(6.0, DEFAULT_RES[2])      # evicts the t=0 sample
    mix = tr.mix(6.0)
    assert mix[0] == 0.0 and mix[2] == 1.0
    assert tr.n_samples == 1


def test_allocate_replica_counts_follows_mix():
    """Replica allocation shifts toward the blocks carrying the observed
    traffic — the repartition lever."""
    blocks = partition_resolutions(DEFAULT_RES, 4)
    low_heavy = {res: m for res, m in zip(sorted(DEFAULT_RES), MIX_A)}
    high_heavy = {res: m for res, m in zip(sorted(DEFAULT_RES), MIX_B)}
    c_low = allocate_replica_counts(blocks, 4, mix=low_heavy)
    c_high = allocate_replica_counts(blocks, 4, mix=high_heavy)
    hi = next(i for i, b in enumerate(blocks) if (32, 32) in b)
    assert c_high[hi] > c_low[hi]
    assert sum(c_low) == sum(c_high) == 4 and min(c_low + c_high) >= 1


# ---------------- drift-triggered repartitioning ----------------

def _drift_cluster(repartition, qps=128.0, seed=1):
    factory = sim_engine_factory(DEFAULT_RES, cache=CacheHitModel())
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=4, policy="resolution_affinity",
                               initial_mix=MIX_A, repartition=repartition,
                               record_timeseries=False))
    wl = phased_workload([(30.0, qps, MIX_A), (30.0, qps, MIX_B)], seed=seed)
    return cl.run(wl), cl, wl


def test_repartition_fires_and_preserves_in_flight():
    m, cl, wl = _drift_cluster(RepartitionConfig())
    # the mix flip triggered at least one repartition + block migration
    assert m.repartitions and m.migrations >= 1
    assert all(e["t"] > 30.0 for e in m.repartitions)  # after the flip
    # in-flight preservation: every request ended exactly once, none stuck
    assert m.completed + m.dropped == len(wl)
    assert all(r.state in ("done", "dropped") for r in wl)
    # migrated replicas switched engines without losing served work
    moved = [r for r in cl.replicas if r.migrations]
    assert moved and all(r.merged_metrics.completed > 0 for r in moved)


def test_adaptive_repartition_beats_static_on_drift():
    static, _, _ = _drift_cluster(None)
    adaptive, _, _ = _drift_cluster(RepartitionConfig())
    assert adaptive.slo_satisfaction > static.slo_satisfaction, \
        (adaptive.slo_satisfaction, static.slo_satisfaction)
    assert adaptive.goodput >= static.goodput


def test_repartition_charges_switch_cost():
    """A migrated replica is not dispatchable before drain + switch_cost."""
    m, cl, _ = _drift_cluster(RepartitionConfig(switch_cost=2.0))
    moved = [r for r in cl.replicas if r.migrations]
    assert moved
    t0 = min(e["t"] for e in m.repartitions)
    for rep in moved:
        # it went unready for at least the switch cost after the plan fired
        assert rep.ready_at >= t0 + 2.0


def test_static_affinity_unchanged_without_repartition_config():
    """No RepartitionConfig -> the PR-1 frozen-partition behavior."""
    m, cl, _ = _drift_cluster(None)
    assert not m.repartitions and m.migrations == 0
    assert cl.mix_tracker is None


def test_invalid_initial_mix_fails_fast():
    factory = sim_engine_factory(DEFAULT_RES)
    for bad in ((0.5, 0.5), (0.0, 0.0, 0.0), (1.5, -1.0, 0.5)):
        with pytest.raises(ValueError, match="initial_mix"):
            Cluster(factory, DEFAULT_RES,
                    ClusterConfig(n_replicas=3,
                                  policy="resolution_affinity",
                                  initial_mix=bad))


def test_repartition_gate_ignores_stale_window():
    """After an idle gap longer than the mix window, the pre-trim sample
    count must not satisfy min_samples — else a repartition fires from the
    empty window's uniform-fallback mix."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=3, policy="resolution_affinity",
                               initial_mix=MIX_B,
                               repartition=RepartitionConfig(
                                   min_samples=10, cooldown=0.0)))
    for i in range(40):                       # burst, then a long gap
        cl.mix_tracker.observe(i * 0.1, DEFAULT_RES[2])
    assert not cl._maybe_repartition(100.0)
    assert not cl.repartition_log


def test_drained_migrator_swaps_before_queue_is_declared_dead():
    """A request routable only to a migrating replica's target block must
    wait for the engine swap, not be dropped as unservable the moment the
    migrator finishes draining."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=2, policy="resolution_affinity",
                               repartition=RepartitionConfig(
                                   switch_cost=0.5),
                               record_timeseries=False))
    # r1 owns the {24x24} block; give it in-flight work, then mark it
    # migrating so the frontend request below has no ready server until
    # the drain + swap completes
    r1 = next(r for r in cl.replicas if r.supports((24, 24)))
    inflight = Request(rid=900, resolution=(24, 24), arrival=0.0, slo=1e9,
                       total_steps=2)
    r1.submit(inflight)
    r1.migrating_to = [(24, 24)]
    queued = Request(rid=901, resolution=(24, 24), arrival=0.0, slo=1e9,
                     total_steps=2)
    m = cl.run([queued])
    assert m.router_dropped == 0
    assert queued.state == "done" and inflight.state == "done"
    assert r1.migrations == 1 and r1.migrating_to is None


def test_repartition_with_autoscaler_keeps_every_block_served():
    """Autoscaler scale-down and repartition migration interact safely:
    no resolution ever becomes permanently unroutable (a retired mover
    would strand its target block), every request still ends once."""
    factory = sim_engine_factory(DEFAULT_RES, cache=CacheHitModel())
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=4, policy="resolution_affinity",
                               initial_mix=MIX_A,
                               repartition=RepartitionConfig(),
                               autoscaler=AutoscalerConfig(
                                   min_replicas=3, max_replicas=6,
                                   cold_start=1.0, cooldown=2.0),
                               record_timeseries=False))
    wl = phased_workload([(15.0, 96.0, MIX_A), (15.0, 96.0, MIX_B),
                          (20.0, 4.0, MIX_B)], seed=2)
    m = cl.run(wl)
    assert m.router_dropped == 0
    assert m.completed + m.dropped == len(wl)
    assert all(r.state in ("done", "dropped") for r in wl)


# ---------------- predictive autoscaling ----------------

def _ramp_cluster(predictive, seed=3):
    cfg = AutoscalerConfig(min_replicas=2, max_replicas=8, cold_start=5.0,
                           cooldown=2.0, predictive=predictive,
                           service_rate=24.0)
    cl = Cluster(sim_engine_factory(DEFAULT_RES), DEFAULT_RES,
                 ClusterConfig(n_replicas=2, policy="join_shortest_queue",
                               autoscaler=cfg, record_timeseries=True))
    m = cl.run(ramp_workload(8.0, 140.0, 35.0, seed=seed))
    return m, cl


def _time_to_ready(m, k):
    for t, _, _, n in m.queue_ts:
        if n >= k:
            return t
    return float("inf")


def test_predictive_prespawns_and_beats_reactive():
    reactive, cl_r = _ramp_cluster(False)
    predictive, cl_p = _ramp_cluster(True)
    # the forecaster actually pre-spawned (reactive path never does)
    assert cl_p.autoscaler.predictive_spawns
    assert not cl_r.autoscaler.predictive_spawns
    # pre-spawn lands before reactive even starts scaling
    first_r = min(t for t, a in cl_r.autoscaler.actions if a > 0)
    assert min(cl_p.autoscaler.predictive_spawns) < first_r
    # capacity arrives earlier: time until 5 replicas are warm
    assert _time_to_ready(predictive, 5) < _time_to_ready(reactive, 5)
    assert predictive.slo_satisfaction > reactive.slo_satisfaction


def test_forecaster_tracks_ramp_and_reliability():
    from repro.cluster import ArrivalForecaster
    fc = ArrivalForecaster(bin_s=1.0)
    rng = np.random.default_rng(0)
    t = 0.0
    while t < 30.0:                     # rate ramps 5 -> 65 qps
        rate = 5.0 + 2.0 * t
        t += rng.exponential(1.0 / rate)
        fc.observe(t)
    fc.advance(30.0)
    assert fc.reliable(min_bins=4, max_rel_err=0.5)
    # trend extrapolates: the 5s-out forecast exceeds the current level
    assert fc.forecast(5.0) > fc.level
    assert fc.forecast(5.0) == pytest.approx(65.0 + 10.0, rel=0.4)


def test_unreliable_forecast_falls_back_to_reactive():
    """With no arrival history the predictive path must stand down."""
    from repro.cluster import ArrivalForecaster
    fc = ArrivalForecaster()
    assert not fc.reliable(min_bins=4, max_rel_err=0.5)
    assert fc.forecast(10.0) == 0.0


def test_service_rate_learning_ignores_drops():
    """The learned per-replica throughput counts completions only — drops
    are demand that was shed, not capacity."""
    from repro.cluster import Autoscaler
    from repro.core.serving import TickEvents
    asc = Autoscaler(AutoscalerConfig(predictive=True, window=10.0))
    done = [Request(rid=i, resolution=DEFAULT_RES[0], arrival=0.0, slo=9.0,
                    total_steps=1) for i in range(10)]
    for r in done:
        r.finish = 5.0
    shed = [Request(rid=100 + i, resolution=DEFAULT_RES[0], arrival=0.0,
                    slo=1.0, total_steps=1) for i in range(40)]
    asc.observe(0.0, [TickEvents(now=0.0, completed=done[:5])])
    asc.observe(5.0, [TickEvents(now=5.0, completed=done[5:], dropped=shed)])
    asc._learn_service_rate(now=5.0, backlog=10.0, ready=1)
    # 10 completions over a 5 s span and 1 ready replica -> 2 req/s, not
    # the 10 req/s that counting the 40 drops would give
    assert asc.service_rate() == pytest.approx(2.0)


# ---------------- cache-aware latency surrogate ----------------

def test_hit_model_monotone_in_concentration_and_step():
    model = CacheHitModel()
    concs = np.linspace(0.2, 1.0, 9)
    hits = [model.hit_rate(c, 0.5) for c in concs]
    assert all(b > a for a, b in zip(hits, hits[1:]))
    fracs = np.linspace(0.0, 1.0, 9)
    hits = [model.hit_rate(0.8, f) for f in fracs]
    assert all(b > a for a, b in zip(hits, hits[1:]))


def test_surrogate_latency_decreases_with_hit_rate():
    counts, patch = [2, 2, 2], gcd_patch_size(DEFAULT_RES)
    lats = [patch_aware_step_latency(counts, DEFAULT_RES, patch,
                                     cache_hit_rate=h)
            for h in (0.0, 0.3, 0.6, 0.9)]
    assert all(b < a for a, b in zip(lats, lats[1:]))


def test_concentration_rewards_affinity_blocks():
    patch = gcd_patch_size(DEFAULT_RES)
    ppr = [(h // patch) * (w // patch) for h, w in DEFAULT_RES]
    pure = resolution_concentration([4, 0, 0], ppr)
    mixed = resolution_concentration([2, 2, 2], ppr)
    assert pure == pytest.approx(1.0)
    assert mixed < pure
    # an affinity replica (single-res block) models a higher hit rate and a
    # later-step batch predicts faster than the same batch at step 0
    lm = PatchAwareLatency(DEFAULT_RES, patch, cache=CacheHitModel())
    assert lm.modeled_hit_rate(pure, 0.5) > lm.modeled_hit_rate(mixed, 0.5)
    early = [Request(rid=i, resolution=DEFAULT_RES[0], arrival=0.0,
                     slo=1e9, total_steps=10) for i in range(4)]
    late = [Request(rid=i, resolution=DEFAULT_RES[0], arrival=0.0,
                    slo=1e9, total_steps=10, steps_done=8)
            for i in range(4)]
    assert lm.predict_batch([4, 0, 0], late) < \
        lm.predict_batch([4, 0, 0], early)


def test_fit_cache_hit_model_recovers_monotone_fit():
    truth = CacheHitModel(b0=-2.5, b_conc=2.0, b_step=3.0)
    rng = np.random.default_rng(0)
    samples = []
    for _ in range(200):
        c, f = rng.uniform(0.2, 1.0), rng.uniform(0.0, 1.0)
        noisy = np.clip(truth.hit_rate(c, f) + rng.normal(0, 0.02), 0, 1)
        samples.append((c, f, noisy))
    fit = fit_cache_hit_model(samples)
    assert fit.b_conc > 0 and fit.b_step > 0
    for c, f in ((0.3, 0.2), (0.7, 0.5), (1.0, 0.9)):
        assert fit.hit_rate(c, f) == pytest.approx(truth.hit_rate(c, f),
                                                   abs=0.05)


def test_cluster_reports_cache_hit_rates():
    """Cache-aware fleets report per-replica + fleet hit rates, and
    affinity replicas (concentrated resolution sets) beat mixed ones."""
    factory = sim_engine_factory(DEFAULT_RES, cache=CacheHitModel())
    aff = Cluster(factory, DEFAULT_RES,
                  ClusterConfig(n_replicas=3, policy="resolution_affinity",
                                record_timeseries=False))
    rr = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=3, policy="round_robin",
                               record_timeseries=False))
    ma = aff.run(cluster_workload(qps=48.0, duration=15.0, seed=1, mix=SKEW))
    mr = rr.run(cluster_workload(qps=48.0, duration=15.0, seed=1, mix=SKEW))
    assert 0.0 < mr.cache_hit_rate < ma.cache_hit_rate <= 1.0
    assert all(rep.cache_hit_rate > 0 for rep in ma.per_replica.values())
    assert "cache_hit_rate" in ma.summary()


# ---------------- predictive scale-down (elastic controller) --------------
# UPDOWN_KNOTS (simtools): 8 -> 140 qps over 35 s, back down to 6 by 65 s —
# the falling edge a predictive retirement should move ahead of


def _updown_cluster(predictive_down, seed=3, policy="join_shortest_queue"):
    cfg = AutoscalerConfig(min_replicas=2, max_replicas=8, cold_start=5.0,
                           cooldown=2.0, predictive=True,
                           predictive_down=predictive_down,
                           service_rate=24.0)
    cl = Cluster(sim_engine_factory(DEFAULT_RES), DEFAULT_RES,
                 ClusterConfig(n_replicas=2, policy=policy,
                               autoscaler=cfg, record_timeseries=True))
    wl = piecewise_rate_workload(UPDOWN_KNOTS, seed=seed)
    return cl.run(wl), cl, wl


def test_predictive_down_retires_ahead_of_rampdown():
    held, cl_h, _ = _updown_cluster(False)
    early, cl_e, _ = _updown_cluster(True)
    # the elastic path actually retired ahead of the falling edge ...
    assert cl_e.autoscaler.predictive_retirements
    assert all(t > 35.0 for t in cl_e.autoscaler.predictive_retirements)
    # ... before the reactive idle signal would have (the held run never
    # scaled down inside the horizon at all)
    held_downs = [t for t, a in cl_h.autoscaler.actions if a < 0]
    first_early = min(cl_e.autoscaler.predictive_retirements)
    assert not held_downs or first_early < min(held_downs)
    # capacity tracked the ramp-down: strictly smaller final fleet
    assert early.replica_count_stats()["final"] < \
        held.replica_count_stats()["final"]
    # and early retirement did not cost SLO (drain-before-retire)
    assert early.slo_satisfaction >= held.slo_satisfaction - 0.005


def test_predictive_retirement_never_kills_inflight():
    m, cl, wl = _updown_cluster(True)
    assert cl.autoscaler.predictive_retirements
    # every retired replica drained before it died: its engine is empty and
    # nothing it held was lost
    retired = [r for r in cl.replicas if r.retired_at is not None]
    assert retired
    for rep in retired:
        assert not rep.engine.has_work
        assert rep.failed_at is None          # retired, not crashed
    assert m.completed + m.dropped == len(wl)
    assert all(r.state in ("done", "dropped") for r in wl)


def test_predictive_down_holds_steady_under_constant_load():
    """The hysteresis band (down_headroom > headroom) must not flap the
    fleet when the arrival rate is flat."""
    cfg = AutoscalerConfig(min_replicas=1, max_replicas=6, cold_start=2.0,
                           cooldown=2.0, predictive=True,
                           predictive_down=True, service_rate=24.0)
    cl = Cluster(sim_engine_factory(DEFAULT_RES), DEFAULT_RES,
                 ClusterConfig(n_replicas=1, policy="join_shortest_queue",
                               autoscaler=cfg, record_timeseries=True))
    m = cl.run(cluster_workload(qps=32.0, duration=60.0, seed=2, mix=None))
    counts = [n for t, _, _, n in m.queue_ts if t > m.span * 2 / 3]
    assert counts and min(counts) == max(counts)   # settled, no oscillation
    assert m.slo_satisfaction > 0.9


# ---------------- fleet-size-aware repartitioning -------------------------

def test_resize_repartition_fires_on_scale_up():
    """Autoscaler growth must re-cut the block structure for the new fleet
    size, not just bolt replicas onto the old blocks."""
    cfg = AutoscalerConfig(min_replicas=2, max_replicas=8, cold_start=5.0,
                           cooldown=2.0, predictive=True,
                           predictive_down=True, service_rate=24.0)
    cl = Cluster(sim_engine_factory(DEFAULT_RES), DEFAULT_RES,
                 ClusterConfig(n_replicas=2, policy="resolution_affinity",
                               autoscaler=cfg,
                               repartition=RepartitionConfig(
                                   cooldown=3.0, switch_cost=0.5),
                               record_timeseries=False))
    wl = piecewise_rate_workload(UPDOWN_KNOTS, seed=3)
    m = cl.run(wl)
    resizes = [e for e in m.repartitions if e["reason"] == "resize"]
    assert resizes
    # growth re-cut the 2-replica two-block structure into the per-
    # resolution blocks the larger fleet affords (bigger GCD patches)
    assert max(e["k"] for e in resizes) > 2
    assert any(len(e["blocks"]) == len(DEFAULT_RES) for e in resizes)
    assert m.migrations >= 1
    assert m.completed + m.dropped == len(wl)
    assert all(r.state in ("done", "dropped") for r in wl)


def test_resize_repartition_converges_at_stable_fleet_size():
    """Resize replanning is a fixed point: with no fleet-size change it
    must never fire again (no migration ping-pong)."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=4, policy="resolution_affinity",
                               repartition=RepartitionConfig(cooldown=0.0),
                               record_timeseries=False))
    # stable fleet: planned-for size matches -> no-op, repeatedly
    assert not cl._maybe_resize_repartition(1.0)
    assert not cl._maybe_resize_repartition(2.0)
    assert not cl.repartition_log
    # a size change (one replica begins retiring) fires exactly one replan
    cl.replicas[0].retiring = True
    assert cl._maybe_resize_repartition(3.0)
    assert [e["reason"] for e in cl.repartition_log] == ["resize"]
    assert cl.repartition_log[-1]["k"] == 3
    # drain the queued migrations so the plan is no longer in flight, then
    # verify stability at the new size
    cl._migration_queue.clear()
    for rep in cl.replicas:
        rep.migrating_to = None
    assert not cl._maybe_resize_repartition(4.0)
    assert len(cl.repartition_log) == 1


def test_resize_replan_waits_for_inflight_migrations():
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=4, policy="resolution_affinity",
                               repartition=RepartitionConfig(cooldown=0.0),
                               record_timeseries=False))
    cl.replicas[0].retiring = True            # size change pending ...
    cl.replicas[1].migrating_to = [(16, 16)]  # ... but a move is in flight
    assert not cl._maybe_resize_repartition(5.0)
    cl.replicas[1].migrating_to = None
    assert cl._maybe_resize_repartition(5.0)


# ---------------- failure injection + recovery ----------------------------

def _crash_cluster(recover, seed=5, qps=56.0, duration=40.0):
    cl = Cluster(sim_engine_factory(DEFAULT_RES), DEFAULT_RES,
                 ClusterConfig(n_replicas=4, policy="join_shortest_queue",
                               failures=FailureConfig(mtbf=25.0,
                                                      recover=recover,
                                                      seed=seed),
                               record_timeseries=True))
    wl = cluster_workload(qps=qps, duration=duration, seed=1)
    return cl.run(wl), cl, wl


def test_crash_requeues_orphans_and_recovers():
    m, cl, wl = _crash_cluster(recover=True)
    assert m.replicas_failed > 0
    assert m.recoveries == m.replicas_failed   # every crash was replaced
    assert m.requests_requeued > 0
    assert m.requeue_delays and all(d >= 0 for d in m.requeue_delays)
    # crashed replicas really died holding nothing (orphans were pulled out)
    for rep in cl.replicas:
        if rep.failed_at is not None:
            assert not rep.engine.has_work
            assert rep.retired_at == rep.failed_at
    # conservation through the crash-requeue path
    assert m.completed + m.dropped == len(wl)
    assert all(r.state in ("done", "dropped") for r in wl)


def test_crash_requeued_requests_not_double_counted():
    """A request that dies with its replica and is requeued must appear in
    fleet metrics exactly once — wherever it finally completed."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=2, policy="join_shortest_queue",
                               failures=FailureConfig(mtbf=1e9, recover=True,
                                                      cold_start=1.0),
                               record_timeseries=False))
    victim = cl.replicas[0]
    victim.crash_at = 1.5            # deterministic mid-run crash
    # saturating burst so the victim is guaranteed to hold work at t=1.5
    wl = cluster_workload(qps=120.0, duration=3.0, seed=0)
    m = cl.run(wl)
    assert victim.failed_at == 1.5
    assert m.replicas_failed == 1 and m.requests_requeued > 0
    # exactly-once accounting: fleet totals match the workload, and every
    # completion recorded exactly one latency sample
    assert m.completed + m.dropped == len(wl)
    assert len(m.latencies) == m.completed
    assert sum(r.metrics.completed + r.metrics.dropped
               for r in m.per_replica.values()) \
        + m.router_dropped == len(wl)
    # requeued requests restarted from scratch — the victim's own counters
    # hold only what it truly finished before dying
    assert victim.merged_metrics.completed + victim.merged_metrics.dropped \
        < len(wl)


def test_recovery_replacement_keeps_block_served():
    """Under resolution_affinity, recovery must respawn over the dead
    replica's block so its resolutions never become unroutable; without
    recovery the block dies with it."""
    def run(recover):
        factory = sim_engine_factory(DEFAULT_RES)
        cl = Cluster(factory, DEFAULT_RES,
                     ClusterConfig(n_replicas=3,
                                   policy="resolution_affinity",
                                   failures=FailureConfig(
                                       mtbf=1e9, recover=recover,
                                       cold_start=1.0),
                                   record_timeseries=False))
        victim = next(r for r in cl.replicas if r.supports((24, 24)))
        victim.crash_at = 2.0
        wl = cluster_workload(qps=30.0, duration=10.0, seed=4)
        return cl.run(wl), cl, wl

    dead, cl_d, wl_d = run(recover=False)
    alive, cl_a, wl_a = run(recover=True)
    # without recovery every (24, 24) arrival after the crash is stranded
    # and eventually dropped by the router
    assert dead.router_dropped > 0
    assert dead.completed + dead.dropped == len(wl_d)
    # with recovery a replacement covers the block: nothing is unroutable
    assert alive.router_dropped == 0
    assert alive.recoveries == 1
    replacement = cl_a.replicas[-1]
    assert replacement.supports((24, 24))
    assert alive.slo_satisfaction > dead.slo_satisfaction


def test_crash_of_queued_mover_replacement_inherits_target_block():
    """A replica can crash while its repartition migration is still queued
    (not yet started). The replacement must be spawned over the *planned
    target* block — recovery keeps the fleet size unchanged, so no resize
    replan would ever repair a block the plan lost."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=3, policy="resolution_affinity",
                               repartition=RepartitionConfig(),
                               failures=FailureConfig(mtbf=1e9,
                                                      recover=True,
                                                      cold_start=0.5),
                               record_timeseries=False))
    mover = cl.replicas[0]
    target = [(24, 24)] if tuple(mover.resolutions[0]) != (24, 24) \
        else [(32, 32)]
    cl._migration_queue.append((mover, list(target)))
    mover.crash_at = 1.0
    assert cl._maybe_fail(2.0)
    # the dead mover's queue entry is gone and its replacement covers the
    # block the plan was counting on, not the block it died holding
    assert all(qrep is not mover for qrep, _ in cl._migration_queue)
    replacement = cl.replicas[-1]
    assert [tuple(r) for r in replacement.resolutions] == \
        [tuple(r) for r in target]


def test_predictive_down_implies_predictive():
    """predictive_down without predictive would be silently inert (the
    forecaster never even sees arrivals); the config promotes it."""
    cfg = AutoscalerConfig(predictive_down=True)
    assert cfg.predictive
    assert not AutoscalerConfig().predictive


def test_crashed_retiring_victim_stays_down():
    """A scale-down victim that crashes while draining must not be
    respawned — recovery would silently undo a retirement the autoscaler
    already decided and logged."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=3, policy="join_shortest_queue",
                               failures=FailureConfig(mtbf=1e9,
                                                      recover=True,
                                                      cold_start=0.5),
                               record_timeseries=False))
    victim = cl.replicas[0]
    victim.retiring = True               # draining toward retirement
    victim.crash_at = 1.0
    assert cl._maybe_fail(2.0)
    assert len(cl.replicas) == 3         # no replacement spawned
    assert cl._recoveries == 0
    assert cl.failure_log[-1]["replaced"] is False


def test_crash_of_active_migrator_restarts_queued_migrations():
    """If the actively migrating replica crashes, the queued movers must be
    started immediately — nothing else ever would (the replan gates stay
    blocked while the queue is non-empty)."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=3, policy="resolution_affinity",
                               repartition=RepartitionConfig(),
                               failures=FailureConfig(mtbf=1e9,
                                                      recover=True,
                                                      cold_start=0.5),
                               record_timeseries=False))
    active, queued = cl.replicas[0], cl.replicas[1]
    active.migrating_to = [(16, 16)]
    cl._migration_queue.append((queued, [(24, 24)]))
    active.crash_at = 1.0
    assert cl._maybe_fail(2.0)
    # the queued mover was promoted to actively migrating
    assert queued.migrating_to == [(24, 24)]
    assert not cl._migration_queue


def test_piecewise_rate_workload_supports_step_knots():
    """Duplicate-time knots express a step change; sorting must not
    reorder them by qps (which would reverse a downward cliff)."""
    wl = piecewise_rate_workload([(0.0, 140.0), (35.0, 140.0),
                                  (35.0, 6.0), (65.0, 6.0)], seed=0)
    before = sum(1 for r in wl if r.arrival < 35.0)
    after = sum(1 for r in wl if r.arrival >= 35.0)
    # ~140*35 arrivals before the cliff, ~6*30 after
    assert before > 10 * after
    assert after > 0


def test_phantom_retirement_is_rolled_back():
    """When every scale-down candidate is its block's last server, the
    autoscaler's -1 must be undone: not logged as a retirement (the
    benchmark asserts on predictive_retirements) and not burning
    cooldown."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=3, policy="resolution_affinity",
                               autoscaler=AutoscalerConfig(
                                   min_replicas=1, max_replicas=4),
                               record_timeseries=False))
    asc = cl.autoscaler
    # one server per block -> no legal victim
    assert not cl._scale_down(5.0)
    # simulate the -1 decide() just issued, then the driver's rollback
    prev = asc._last_action
    asc._last_action_prev = prev
    asc._last_action = 5.0
    asc.actions.append((5.0, -1))
    asc.predictive_retirements.append(5.0)
    asc.cancel_retirement(5.0)
    assert asc.actions == [] and asc.predictive_retirements == []
    assert asc._last_action == prev


def test_crash_recovery_beats_no_recovery_on_slo():
    dead, _, _ = _crash_cluster(recover=False)
    alive, _, _ = _crash_cluster(recover=True)
    assert dead.replicas_failed > 0
    assert alive.slo_satisfaction > dead.slo_satisfaction


def test_failure_metrics_in_summary_are_json_ready():
    m, _, _ = _crash_cluster(recover=True, duration=20.0)
    s = m.summary()
    f = s["failures"]
    assert f["replicas_failed"] == m.replicas_failed
    assert f["recoveries"] == m.recoveries
    assert f["requests_requeued"] == m.requests_requeued
    assert f["requeue_delay_mean"] >= 0.0
    assert len(f["events"]) == m.replicas_failed
    json.dumps(s)                    # artifact-ready


# ---------------- partial-progress checkpointing ---------------------------

def _ckpt_replica(every_k=2, write_cost=0.0, n_reqs=3, steps=10):
    factory = sim_engine_factory(DEFAULT_RES)
    rep = Replica(0, factory(DEFAULT_RES),
                  checkpoint=CheckpointConfig(every_k_steps=every_k,
                                              write_cost=write_cost))
    reqs = [Request(rid=i, resolution=DEFAULT_RES[0], arrival=0.0, slo=1e9,
                    total_steps=steps) for i in range(n_reqs)]
    for r in reqs:
        rep.submit(r)
    return rep, reqs


def test_checkpoint_restore_is_monotone():
    """Restored steps_done never exceeds the progress a request actually
    had at crash time, lags it by less than every_k_steps for active
    requests, and only ever lands on snapshot boundaries."""
    rep, reqs = _ckpt_replica(every_k=2)
    now = 0.0
    for _ in range(5):
        rep.tick(now)
        now = rep.next_free
    progress = {r.rid: r.steps_done for r in reqs}
    assert any(p > 0 for p in progress.values())
    orphans = rep.fail(now)
    assert {r.rid for r in orphans} == set(progress)
    for r in orphans:
        assert 0 <= r.steps_done <= progress[r.rid]
        assert progress[r.rid] - r.steps_done < 2   # snapshot gap < k
        assert r.steps_done % 2 == 0                # boundary-aligned
        assert r.state == "waiting" and r.finish is None


def test_checkpoint_restore_survives_second_crash():
    """A requeued orphan's restored progress is durable: a second crash on
    the next replica must never restore below it (submit seeds the new
    replica's store with the inherited steps_done)."""
    rep, reqs = _ckpt_replica(every_k=2)
    now = 0.0
    for _ in range(6):
        rep.tick(now)
        now = rep.next_free
    orphans = rep.fail(now)
    restored = {r.rid: r.steps_done for r in orphans}
    rep2 = Replica(1, sim_engine_factory(DEFAULT_RES)(DEFAULT_RES),
                   checkpoint=CheckpointConfig(every_k_steps=2))
    for r in orphans:
        rep2.submit(r)
    # crash immediately — before rep2 ever ticked
    for r in rep2.fail(now + 1.0):
        assert r.steps_done == restored[r.rid]


def test_checkpoint_write_cost_charged_on_clock():
    """A snapshot write extends the replica's busy horizon by write_cost
    per snapshotted request; a cost-free config ticks identically."""
    taxed, _ = _ckpt_replica(every_k=2, write_cost=0.5)
    free, _ = _ckpt_replica(every_k=2, write_cost=0.0)
    t_taxed = t_free = 0.0
    charged = 0
    for _ in range(4):
        ev_t = taxed.tick(t_taxed)
        ev_f = free.tick(t_free)
        assert ev_t.dt == pytest.approx(ev_f.dt)   # engine time unchanged
        gap = (taxed.next_free - t_taxed) - (free.next_free - t_free)
        if gap > 0:
            charged += 1
            # every active request snapshots at once (same steps_done)
            assert gap == pytest.approx(0.5 * len(taxed.engine.active))
        t_taxed, t_free = taxed.next_free, free.next_free
    assert charged >= 1
    assert taxed.checkpoint_writes == free.checkpoint_writes > 0
    assert taxed.checkpoint_time > 0.0 and free.checkpoint_time == 0.0


def test_checkpoint_config_validation():
    with pytest.raises(ValueError, match="every_k_steps"):
        CheckpointConfig(every_k_steps=0)
    with pytest.raises(ValueError, match="write_cost"):
        CheckpointConfig(write_cost=-0.1)


def test_checkpointed_crashes_keep_exactly_once_accounting():
    """Conservation and single-count latency accounting hold through the
    checkpoint-restore requeue path, and restored progress is reported."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=2, policy="join_shortest_queue",
                               failures=FailureConfig(mtbf=1e9, recover=True,
                                                      cold_start=1.0),
                               checkpoint=CheckpointConfig(every_k_steps=2),
                               record_timeseries=False))
    cl.replicas[0].crash_at = 1.5
    wl = cluster_workload(qps=120.0, duration=3.0, seed=0)
    m = cl.run(wl)
    assert m.replicas_failed == 1 and m.requests_requeued > 0
    assert m.steps_resumed > 0
    assert m.checkpoint_writes > 0 and m.checkpoint_time > 0.0
    assert m.completed + m.dropped == len(wl)
    assert len(m.latencies) == m.completed
    assert all(r.state in ("done", "dropped") for r in wl)
    s = m.summary()
    assert s["checkpoint"]["steps_resumed"] == m.steps_resumed
    json.dumps(s)


def test_checkpointed_recovery_beats_restart_from_zero():
    """The shared CRASH_FAULTS scenario: resuming crash orphans from their
    last snapshot must beat restarting them from denoise step 0 on fleet
    SLO satisfaction — the benchmark's asserted headline."""
    sc = CRASH_FAULTS
    out = {}
    for tag, ckpt in (("restart", None), ("ckpt", CheckpointConfig())):
        factory = sim_engine_factory(DEFAULT_RES, steps=sc["steps"])
        cl = Cluster(factory, DEFAULT_RES,
                     ClusterConfig(n_replicas=sc["n_replicas"],
                                   policy="join_shortest_queue",
                                   failures=FailureConfig(
                                       mtbf=sc["mtbf"], recover=True,
                                       cold_start=sc["cold_start"], seed=7),
                                   checkpoint=ckpt,
                                   record_timeseries=False))
        out[tag] = cl.run(cluster_workload(
            qps=sc["qps"], duration=sc["duration"], steps=sc["steps"],
            slo_scale=sc["slo_scale"], seed=7))
    assert out["ckpt"].steps_resumed > 0
    assert out["restart"].steps_resumed == 0
    assert out["ckpt"].slo_satisfaction > out["restart"].slo_satisfaction


def test_requeue_delay_accounting_across_multi_crash_batch():
    """Two replicas crashing in the same detection pass: every orphan gets
    exactly one requeue-delay sample (crash instant minus arrival) and the
    batched requeue re-enters the router head in global arrival order."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=3, policy="join_shortest_queue",
                               failures=FailureConfig(mtbf=1e9,
                                                      recover=False),
                               record_timeseries=False))
    for r in cluster_workload(qps=200.0, duration=0.5, seed=0):
        cl.router.enqueue(r)
    cl.router.dispatch(cl._dispatchable(), now=0.6)
    r0, r1 = cl.replicas[0], cl.replicas[1]
    orphans = (r0.engine.wait + r0.engine.active
               + r1.engine.wait + r1.engine.active)
    assert orphans, "burst did not load the crash victims"
    r0.crash_at = r1.crash_at = 1.0
    assert cl._maybe_fail(1.5)
    assert cl.router.requeued == len(orphans)
    assert len(cl._requeue_delays) == len(orphans)
    assert sorted(cl._requeue_delays) == pytest.approx(
        sorted(1.0 - r.arrival for r in orphans))
    head = cl.router.queue[:len(orphans)]
    assert {r.rid for r in head} == {r.rid for r in orphans}
    arrivals = [r.arrival for r in head]
    assert arrivals == sorted(arrivals)   # one batch, global arrival order
    assert sum(e["requeued"] for e in cl.failure_log) == len(orphans)


# ---------------- correlated zone failures + fault-domain dispatch ---------

def _zone_cluster(policy, n=6, zones=3, zone_mtbf=1e9, downtime=5.0,
                  cold=0.5, recover=True, seed=0):
    factory = sim_engine_factory(DEFAULT_RES)
    return Cluster(factory, DEFAULT_RES,
                   ClusterConfig(n_replicas=n, policy=policy,
                                 failures=FailureConfig(
                                     mtbf=None, recover=recover,
                                     cold_start=cold, zones=zones,
                                     zone_mtbf=zone_mtbf,
                                     zone_downtime=downtime, seed=seed),
                                 record_timeseries=False))


def test_zone_assignment_round_robin_by_default():
    cl = _zone_cluster("join_shortest_queue")
    assert [r.zone for r in cl.replicas] == [0, 1, 2, 0, 1, 2]


def test_zone_spread_places_each_block_across_zones():
    """The spread-aware affinity variant puts a resolution block's replicas
    in distinct fault domains, so one outage cannot silence a block."""
    cl = _zone_cluster("resolution_affinity_spread")
    by_block = {}
    for r in cl.replicas:
        by_block.setdefault(frozenset(map(tuple, r.resolutions)),
                            []).append(r.zone)
    assert len(by_block) == 3            # per-resolution blocks at k=6
    for zones in by_block.values():
        assert len(zones) == len(set(zones)), by_block
    # and the fleet as a whole is balanced over the 3 domains
    counts = [sum(1 for r in cl.replicas if r.zone == z) for z in range(3)]
    assert counts == [2, 2, 2]


def test_zone_outage_kills_whole_zone_and_respawns_in_survivors():
    """An outage takes every replica of the zone at the same instant
    (cause tagged), and zone-aware recovery places replacements only in
    live zones."""
    cl = _zone_cluster("zone_spread")
    victims = [r for r in cl.replicas if r.zone == 1]
    cl._zone_outage_at = {1: 2.0}        # deterministic outage
    wl = cluster_workload(qps=40.0, duration=8.0, seed=3)
    m = cl.run(wl)
    assert len(m.zone_outages) == 1
    assert m.zone_outages[0]["zone"] == 1
    assert m.zone_outages[0]["killed"] == len(victims) == 2
    for rep in victims:
        assert rep.failed_at == pytest.approx(2.0)
    zone_events = [e for e in m.failures if e["cause"] == "zone"]
    assert len(zone_events) == 2
    replacements = cl.replicas[6:]
    assert len(replacements) == 2
    assert all(rep.zone != 1 for rep in replacements)
    # conservation through the correlated kill
    assert m.completed + m.dropped == len(wl)
    assert all(r.state in ("done", "dropped") for r in wl)


def test_blind_replacement_into_down_zone_stalls_until_recovery():
    """Zone-blind round-robin placement can respawn into the still-down
    zone; the replacement then cannot boot before the zone recovers, so its
    cold start only begins at down_until — the capacity hole zone-aware
    placement avoids."""
    cl = _zone_cluster("join_shortest_queue", n=2, zones=2, downtime=5.0,
                       cold=0.5)
    cl._zone_outage_at = {0: 2.0}
    wl = cluster_workload(qps=40.0, duration=8.0, seed=3)
    cl.run(wl)
    replacement = cl.replicas[2]         # round-robin counter wraps to 0
    assert replacement.zone == 0
    assert replacement.ready_at == pytest.approx(2.0 + 5.0 + 0.5)


def test_zone_availability_metric_reflects_downtime():
    cl = _zone_cluster("zone_spread", downtime=4.0)
    cl._zone_outage_at = {2: 3.0}
    wl = cluster_workload(qps=40.0, duration=10.0, seed=3)
    m = cl.run(wl)
    assert m.zone_availability[0] == 1.0 and m.zone_availability[1] == 1.0
    # zone 2 was down 4 s of the span
    assert m.zone_availability[2] == pytest.approx(1.0 - 4.0 / m.span,
                                                   abs=1e-3)
    s = m.summary()["failures"]
    assert s["zone_availability"]["2"] < 1.0
    json.dumps(s)


def test_zone_wipe_kills_even_when_crash_budget_spent():
    """max_failures budgets the independent Poisson process only: a zone
    outage still wipes its zone when the crash budget is spent — even for
    a replica whose own (capped, cancelled) crash_at fell due in the same
    detection pass — and zone kills never consume the crash budget."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=2, policy="join_shortest_queue",
                               failures=FailureConfig(
                                   mtbf=1e9, max_failures=0, recover=False,
                                   zones=2, zone_mtbf=1e9,
                                   zone_downtime=4.0),
                               record_timeseries=False))
    r0 = cl.replicas[0]                  # zone 0
    r0.crash_at = 1.9                    # independent crash, capped away
    cl._zone_outage_at = {0: 2.0}        # outage due in the same pass
    assert cl._maybe_fail(2.5)
    assert r0.failed_at == pytest.approx(2.0)   # died at the outage instant
    assert cl.failure_log[-1]["cause"] == "zone"
    assert cl._n_crashes == 0            # the wipe spent no crash budget
    # zone-1 replica untouched
    assert cl.replicas[1].failed_at is None


def test_zone_kills_leave_crash_budget_intact():
    """After an outage kills a whole zone, a later independent crash must
    still fire: correlated kills do not drain max_failures."""
    factory = sim_engine_factory(DEFAULT_RES)
    cl = Cluster(factory, DEFAULT_RES,
                 ClusterConfig(n_replicas=2, policy="join_shortest_queue",
                               failures=FailureConfig(
                                   mtbf=1e9, max_failures=1, recover=False,
                                   zones=2, zone_mtbf=1e9,
                                   zone_downtime=4.0),
                               record_timeseries=False))
    cl._zone_outage_at = {0: 2.0}
    assert cl._maybe_fail(3.0)           # wipes zone 0
    r1 = cl.replicas[1]
    r1.crash_at = 5.0                    # independent crash, budget of 1
    assert cl._maybe_fail(6.0)
    assert r1.failed_at == pytest.approx(5.0)
    assert [e["cause"] for e in cl.failure_log] == ["zone", "crash"]


def test_checkpoint_restores_latent_on_tensor_path():
    """On a device engine the snapshot must carry the latent: a resumed
    orphan continues mid-denoise from the snapshotted state instead of
    skipping its first k steps on fresh noise."""
    mcfg = dm.DiffusionConfig(kind="unet", width=16, levels=2,
                              blocks_per_level=1, n_heads=2, groups=4,
                              d_text=8, n_text=2, use_kernels=False)
    params = dm.init_diffusion(mcfg, jax.random.PRNGKey(0))
    res = [DEFAULT_RES[0]]

    def engine():
        return PatchedServeEngine(mcfg, params, EngineConfig(),
                                  {res[0]: 1.0}, res)

    rep = Replica(0, engine(), checkpoint=CheckpointConfig(every_k_steps=2))
    req = Request(rid=0, resolution=res[0], arrival=0.0, slo=1e9,
                  total_steps=6)
    rep.submit(req)
    now = 0.0
    for _ in range(4):
        rep.tick(now)
        now = rep.next_free
    assert req.steps_done == 4 and req.latent is not None
    snap_latent = rep._ckpt[0][1]
    assert snap_latent is not None
    orphan = rep.fail(now)[0]
    # restored together: progress AND the matching snapshotted latent
    assert orphan.steps_done == 4
    assert orphan.latent is snap_latent
    # a second replica must serve only the remaining steps, without
    # re-noising the restored latent (engine _prepare keeps it)
    eng2 = engine()
    prepared = []

    def prepare(r, _inner=eng2._prepare):
        _inner(r)
        prepared.append(r.latent)

    eng2._prepare = prepare
    rep2 = Replica(1, eng2, checkpoint=CheckpointConfig(every_k_steps=2))
    rep2.submit(orphan)
    ev = rep2.tick(now + 1.0)
    # admitted AND already stepped once in the same tick, from the restored
    # latent (a re-noised one would be a fresh rng draw)
    assert ev.admitted and ev.stepped
    assert len(prepared) == 1 and prepared[0] is snap_latent
    assert orphan.steps_done == 5
    assert not np.allclose(np.asarray(orphan.latent), np.asarray(snap_latent))
    steps, t = 1, rep2.next_free
    while rep2.has_work and steps < 10:
        if rep2.tick(t).stepped:
            steps += 1
        t = rep2.next_free
    assert orphan.state == "done" and steps == 2   # 6 total - 4 restored


def test_checkpoint_store_gc_on_stepless_drop():
    """A hopeless request dropped at admission — on a tick that never
    steps — must still have its snapshot garbage-collected."""
    factory = sim_engine_factory(DEFAULT_RES)
    rep = Replica(0, factory(DEFAULT_RES),
                  checkpoint=CheckpointConfig(every_k_steps=2))
    doomed = Request(rid=0, resolution=DEFAULT_RES[0], arrival=0.0,
                     slo=-1.0, total_steps=10)   # deadline already past
    rep.submit(doomed)
    assert 0 in rep._ckpt                # seeded at submit
    ev = rep.tick(0.0)
    assert ev.dropped and not ev.stepped
    assert 0 not in rep._ckpt            # GC ran despite no step


def test_zone_config_validation():
    factory = sim_engine_factory(DEFAULT_RES)
    with pytest.raises(ValueError, match="zones"):
        Cluster(factory, DEFAULT_RES,
                ClusterConfig(n_replicas=2,
                              failures=FailureConfig(zones=0)))
    with pytest.raises(ValueError, match="zone outages"):
        Cluster(factory, DEFAULT_RES,
                ClusterConfig(n_replicas=2,
                              failures=FailureConfig(zones=1,
                                                     zone_mtbf=10.0)))


def test_predictive_spawn_discounts_stalled_boots():
    """A replica that cannot be up by the forecast horizon (a replacement
    stalled behind a zone outage) is not horizon capacity: the predictive
    autoscaler must provision around it instead of waiting out the stall.
    The reactive backlog signal is deliberately damped (scale_up_backlog
    high, as a jitter-averse deployment would tune it) so the test pins
    the *predictive* discount, not reactive pressure from the stall."""
    from repro.cluster import Autoscaler
    cfg = AutoscalerConfig(predictive=True, service_rate=10.0,
                           cold_start=2.0, cooldown=0.0, max_replicas=4,
                           scale_up_backlog=100.0)
    factory = sim_engine_factory(DEFAULT_RES)

    def mk(ready_at):
        rep = Replica(0, factory(DEFAULT_RES))
        rep.ready_at = rep.next_free = ready_at
        return rep

    def fed(seed=0, qps=13.0, until=10.0):
        asc = Autoscaler(cfg)
        rng, t = np.random.default_rng(seed), 0.0
        while t < until:
            t += rng.exponential(1.0 / qps)
            asc.observe_arrival(t)
        return asc, until

    # steady ~13 qps, mu=10: two *up* replicas cover the forecast ...
    asc, t = fed()
    assert asc.decide(t, 0, [mk(0.0), mk(0.0)]) == 0
    # ... but if one of them cannot boot for another 50 s, it is not
    # capacity at the horizon and a pre-spawn must fire
    asc, t = fed()
    assert asc.decide(t, 0, [mk(0.0), mk(t + 50.0)]) == +1
    assert asc.predictive_spawns


def test_zone_spread_beats_zone_blind_under_outages():
    """The shared ZONE_FAULTS scenario: fault-domain-aware dispatch +
    placement must beat zone-blind join_shortest_queue on fleet SLO
    satisfaction — the benchmark's asserted headline."""
    sc = ZONE_FAULTS
    out = {}
    for tag, pol in (("blind", "join_shortest_queue"),
                     ("spread", "zone_spread")):
        cl = _zone_cluster(pol, n=sc["n_replicas"], zones=sc["zones"],
                           zone_mtbf=sc["zone_mtbf"],
                           downtime=sc["zone_downtime"],
                           cold=sc["cold_start"], seed=7)
        out[tag] = cl.run(cluster_workload(qps=sc["qps"],
                                           duration=sc["duration"], seed=7))
    assert out["spread"].zone_outages          # outages actually fired
    assert out["spread"].slo_satisfaction > out["blind"].slo_satisfaction
