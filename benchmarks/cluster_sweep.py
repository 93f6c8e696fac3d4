"""Cluster serving sweep: QPS x dispatch policy x replica count on the sim
clock, JSON artifact like the figures pipeline (paper Fig. 12-15 analogues,
lifted to fleet scale).

Run:  PYTHONPATH=src python -m benchmarks.cluster_sweep [--fast]
          [--adaptive] [--out benchmarks/cluster_results.json]

Emits one record per (qps, policy, n_replicas) with the fleet summary from
``ClusterMetrics.summary()`` plus an autoscaler trajectory section, and
prints a compact table. The headline check — SLO-aware routing
(``least_slack``) and resolution-partitioned placement
(``resolution_affinity``) beating ``round_robin`` — is asserted at the end
so CI catches regressions in the policies themselves.

``--adaptive`` adds the workload-adaptation axis: (1) drifting-mix
workloads (Low-heavy flipping to High-heavy mid-run) served by a static
affinity partition provisioned for the opening mix vs. drift-triggered
repartitioning, both on the cache-aware latency surrogate; (2) a ramping
arrival rate served by the reactive autoscaler vs. the predictive
(Holt-forecast) one. The adaptive runs must beat their frozen baselines on
fleet SLO satisfaction — asserted, like the routing headline.

``--elastic`` adds the elastic fleet controller axis: (1) an up-then-down
arrival wave served by the PR-2 frozen baseline (reactive autoscaler,
block structure frozen at the initial fleet size) vs. the elastic
controller (predictive spawn + predictive retirement + fleet-size-aware
repartitioning) — the controller must win fleet SLO satisfaction *and*
track the ramp-down with a strictly smaller final fleet; (2) a constant-
rate workload under Poisson replica crashes, with vs. without recovery
(crash-requeue + cold-started replacement) — recovery must win fleet SLO
satisfaction. Both wins are asserted; CI's bench-smoke job runs them on
every PR.

``--faults`` adds the fault-tolerance axis (shared scenarios
``simtools.CRASH_FAULTS`` / ``ZONE_FAULTS``): (1) long-denoise requests
under frequent Poisson crashes, restart-from-zero vs. partial-progress
checkpointing (``CheckpointConfig``: snapshots every k steps, write cost
charged on the sim clock, crash orphans resume from the last snapshot) —
checkpointing must win fleet SLO satisfaction; (2) recurrent correlated
zone outages on a near-capacity fleet, zone-blind dispatch
(``join_shortest_queue`` + round-robin zone placement) vs. the
fault-domain-aware ``zone_spread`` policy (zone-balanced placement that
avoids down zones, least-loaded-zone dispatch) — zone_spread must win
fleet SLO satisfaction. Both wins are asserted in CI.

``--batching`` adds the router-side gang-batching axis (shared scenario
``simtools.BATCH_MIX``): a steady hybrid-resolution Poisson stream near
the fleet's knee, three arms at equal fleet size — ``per_request``
(plain join_shortest_queue dispatch), ``nowait`` (the ablation: the
batch former gangs only what is simultaneously queued, ``max_wait=0``,
never deliberately waits) and ``gang`` (the full former: patch-
compatible work held up to its eligibility window and dispatched as
gangs under the marginal-patch step-cost budget). Gang-batched dispatch
must beat per-request on fleet SLO satisfaction — asserted, together
with structural guards: gangs actually formed, no held request's slack
ever dipped below its max-wait (tight-SLO work is never delayed), no
hold overshot its deadline, and the gang arm's traced latency
decomposition (now including ``batch_wait``) still conserves to 1e-9.

``--cascade`` adds the query-aware model-cascade axis (shared scenario
``simtools.CASCADE_MIX``): a hybrid-resolution stream where each request
carries a hidden difficulty (the minimum model quality that makes its
output acceptable), served by four fleets at equal tier-weighted GPU
cost. The ``cascade`` arm is heterogeneous (mostly lite replicas plus
one base and one max) under ``cascade`` dispatch — every request starts
on the cheapest tier whose predicted latency fits its slack, and a
confidence gate escalates under-quality completions to the next tier up
when the *remaining* slack can still pay for the bigger model (giving up
and accepting the cheap output otherwise). The homogeneous arms are
``always_cheap`` (all lite — raw SLO looks perfect, 40% of outputs come
back under quality), ``always_base`` (the strongest homogeneous
competitor — still gives up the hard tail) and ``always_big`` (all max —
every output is good but the fleet drowns at this cost). The headline —
the cascade beats every homogeneous arm on *quality-adjusted* SLO
attainment (``slo_quality_attainment``: deadline met AND difficulty
met) on every seed (>=3 seeds) — is asserted, with structural guards:
equal fleet cost across arms, escalations actually happened, every tier
completed work, and the traced cascade arm's latency decomposition (now
including the ``escalation`` component) conserves to 1e-9.

``--trace-dir DIR`` runs one traced regime (the crash+checkpoint
scenario — it exercises requeue, checkpoint and drop paths) with the
per-request span tracer on and persists three artifacts into DIR:
``trace.jsonl`` (fleet events + per-request span records with the full
latency decomposition), ``trace_chrome.json`` (Chrome ``chrome://tracing``
/ Perfetto timeline — replicas as tracks, zones as process groups), and
``timeseries.json`` (the fleet summary with the raw queue/replica time
series that the default summary reduces to stats). ``--trace-mode``
picks the retention policy: ``all`` (default), ``violations`` (per-request
events kept only for SLO misses/drops), or ``sample``. The SLO-violation
attribution histogram is printed either way; feed ``trace.jsonl`` to
``scripts/trace_report.py`` for the offline view.

``--cachetier`` adds the fleet patch-cache-tier axis (shared scenario
``simtools.CACHE_TIER``): repeat-heavy hybrid-resolution traffic whose
dominant resolution flips between phases, every run priced under the same
per-replica L1 warmth dynamics. The PR-4 dispatch policies run without a
fleet L2 (``capacity_bytes=0``); the headline run adds the shared tier and
``cache_affinity`` (warmth-directed) dispatch and must beat the best
no-tier policy on fleet SLO satisfaction — asserted, with tier-only /
dispatch-only / small-capacity ablations reported alongside.

``--warmboot`` adds the warm-boot elastic fleet axis (shared scenario
``simtools.FLASH_CROWD``): a small fleet absorbing a flash-crowd spike by
elastic scaling, where every cold spawn pays a long reuse-predictor
warmup unless the spawn path pre-fetches the new replica's block's
committed L2 entries during boot (size-dependent transfer time,
overlapped with cold start) and the autoscaler prices the shorter
effective cold start. Three arms per seed — no-tier, tier-without-
prefetch (ablation), tier + spawn prefetch — and the tier-warmed fleet
must beat the cold fleet on fleet SLO satisfaction on every seed
(>=3 seeds, asserted) with structural guards on the prefetch, publish
and warm-boot-pricing paths.

``--monitor`` adds the fleet-health-monitor validation (>=3 seeds, four
shared regimes): the streaming monitor (``ClusterConfig.monitor`` —
windowed metrics over the trace bus, SLO error-budget burn-rate rules,
changepoint detection) must stay silent on the healthy baseline
(``HEALTHY_BASELINE``), fire inside every injected incident on the
crash (``CRASH_FAULTS``) and zone-outage (``ZONE_FAULTS``) regimes
(recall 1.0), and on the flash crowd (``FLASH_CROWD``) alert inside the
crowd window and never before it. Every alert's streamed ``dominant``
latency component is checked against the tracer's post-hoc
SLO-violation attribution recomputed over exactly the alert's
evaluation window. All asserted; with ``--trace-dir`` the crash run's
``monitor_alerts.jsonl`` + ``monitor_prometheus.txt`` are persisted.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

from benchmarks.common import make_cluster
from repro.cluster import (AutoscalerConfig, CheckpointConfig,
                           FailureConfig, RepartitionConfig, TraceConfig)
from repro.cluster.monitor import dominant_over_spans
from repro.cluster.simtools import (BATCH_MIX, CACHE_TIER, CASCADE_MIX,
                                    CRASH_FAULTS, FLASH_CROWD,
                                    HEALTHY_BASELINE, MONITOR_ZONE_QPS,
                                    UPDOWN_KNOTS,
                                    ZONE_FAULTS, cachetier_config,
                                    cachetier_mean_mix, cascade_fleet_cost,
                                    cluster_workload, monitor_config,
                                    phased_workload,
                                    piecewise_rate_workload, ramp_workload)

POLICIES = ("round_robin", "join_shortest_queue", "least_slack",
            "resolution_affinity")

#: drifting-mix scenario: provisioned for Low-heavy, drifts to High-heavy
DRIFT_MIX_A = (0.6, 0.3, 0.1)
DRIFT_MIX_B = (0.1, 0.3, 0.6)


def sweep(qps_grid, replica_grid, duration, seed, mix):
    results = []
    for n in replica_grid:
        for qps in qps_grid:
            for pol in POLICIES:
                cl = make_cluster(n_replicas=n, policy=pol,
                                  record_timeseries=False)
                t0 = time.time()
                m = cl.run(cluster_workload(qps=qps, duration=duration,
                                            seed=seed, mix=mix))
                rec = {"qps": qps, "policy": pol, "n_replicas": n,
                       **m.summary(), "wall_s": round(time.time() - t0, 2)}
                results.append(rec)
                print(f"n={n} qps={qps:5.1f} {pol:22s} "
                      f"slo={rec['slo_satisfaction']:.3f} "
                      f"goodput={rec['goodput']:7.2f} "
                      f"util={rec['utilization']:.2f} "
                      f"p95={rec['latency_p95']:.3f}s")
    return results


def autoscale_trace(qps, duration, seed, mix):
    cl = make_cluster(n_replicas=1, policy="join_shortest_queue",
                      autoscaler=AutoscalerConfig(min_replicas=1,
                                                  max_replicas=6))
    m = cl.run(cluster_workload(qps=qps, duration=duration, seed=seed,
                                mix=mix))
    s = m.summary()
    print(f"autoscale qps={qps}: replicas {s['replicas']} "
          f"slo={s['slo_satisfaction']:.3f} util={s['utilization']:.2f}")
    return {"qps": qps, "policy": "join_shortest_queue+autoscaler", **s,
            "actions": cl.autoscaler.actions}


def adaptive_repartition_trace(qps_grid, duration, seed):
    """Static affinity (partition frozen at the opening mix) vs.
    drift-triggered repartitioning on the same drifting-mix workload,
    cache-aware surrogate for both."""
    runs = []
    for qps in qps_grid:
        row = {"qps": qps, "mix_a": list(DRIFT_MIX_A),
               "mix_b": list(DRIFT_MIX_B)}
        for tag, rcfg in (("static", None),
                          ("adaptive", RepartitionConfig())):
            cl = make_cluster(n_replicas=4, policy="resolution_affinity",
                              initial_mix=DRIFT_MIX_A, repartition=rcfg,
                              cache=True, record_timeseries=False)
            wl = phased_workload([(duration / 2, qps, DRIFT_MIX_A),
                                  (duration / 2, qps, DRIFT_MIX_B)],
                                 seed=seed)
            m = cl.run(wl)
            row[tag] = m.summary()
            print(f"drift qps={qps:5.1f} {tag:8s} "
                  f"slo={row[tag]['slo_satisfaction']:.3f} "
                  f"goodput={row[tag]['goodput']:7.2f} "
                  f"hit={row[tag]['cache_hit_rate']:.3f} "
                  f"migrations={row[tag]['migrations']}")
        runs.append(row)
    return runs


def predictive_autoscale_trace(duration, seed):
    """Reactive vs. predictive autoscaler on a linearly ramping arrival
    rate; the forecaster should pre-spawn so cold start lands before the
    wave."""
    out = {}
    for tag, predictive in (("reactive", False), ("predictive", True)):
        cfg = AutoscalerConfig(min_replicas=2, max_replicas=8,
                               cold_start=5.0, cooldown=2.0,
                               predictive=predictive, service_rate=24.0)
        cl = make_cluster(n_replicas=2, policy="join_shortest_queue",
                          autoscaler=cfg, record_timeseries=True)
        m = cl.run(ramp_workload(8.0, 140.0, duration, seed=seed))
        s = m.summary()
        s["actions"] = [(round(t, 2), a) for t, a in cl.autoscaler.actions]
        s["predictive_spawns"] = [
            round(t, 2) for t in cl.autoscaler.predictive_spawns]
        out[tag] = s
        print(f"ramp {tag:10s} slo={s['slo_satisfaction']:.3f} "
              f"p95={s['latency_p95']:.3f}s replicas={s['replicas']} "
              f"pre-spawns={len(s['predictive_spawns'])}")
    return out


def elastic_updown_trace(seed):
    """PR-2 frozen baseline (reactive autoscaler, blocks frozen at the
    initial fleet size) vs. the elastic controller (predictive spawn +
    predictive retirement + resize-triggered repartitioning) on the same
    up-then-down arrival wave, resolution-affinity placement for both."""
    base = AutoscalerConfig(min_replicas=2, max_replicas=8, cold_start=5.0,
                            cooldown=2.0, service_rate=24.0)
    out = {"knots": [list(k) for k in UPDOWN_KNOTS]}
    for tag, asc, rcfg in (
            ("baseline", base, None),
            ("elastic",
             replace(base, predictive=True, predictive_down=True),
             RepartitionConfig(cooldown=3.0, switch_cost=0.5))):
        cl = make_cluster(n_replicas=2, policy="resolution_affinity",
                          autoscaler=asc, repartition=rcfg,
                          record_timeseries=True)
        m = cl.run(piecewise_rate_workload(UPDOWN_KNOTS, seed=seed))
        s = m.summary()
        s["predictive_retirements"] = [
            round(t, 2) for t in cl.autoscaler.predictive_retirements]
        out[tag] = s
        print(f"updown {tag:9s} slo={s['slo_satisfaction']:.3f} "
              f"p95={s['latency_p95']:.3f}s replicas={s['replicas']} "
              f"early-retires={len(s['predictive_retirements'])} "
              f"migrations={s['migrations']}")
    return out


def failure_recovery_trace(seed, qps=56.0, duration=40.0):
    """Constant-rate fleet under Poisson replica crashes: the PR-2 baseline
    has no failure handling beyond requeueing the dead replica's work (the
    fleet just shrinks), the elastic controller also spawns a cold-started
    replacement per crash."""
    out = {"qps": qps, "mtbf": 25.0}
    for tag, recover in (("no_recovery", False), ("recovery", True)):
        cl = make_cluster(n_replicas=4, policy="join_shortest_queue",
                          failures=FailureConfig(mtbf=25.0, recover=recover,
                                                 seed=seed),
                          record_timeseries=False)
        m = cl.run(cluster_workload(qps=qps, duration=duration, seed=seed))
        s = m.summary()
        out[tag] = s
        f = s["failures"]
        print(f"crash {tag:12s} slo={s['slo_satisfaction']:.3f} "
              f"failed={f['replicas_failed']} "
              f"recovered={f['recoveries']} "
              f"requeued={f['requests_requeued']} "
              f"requeue-delay-p95={f['requeue_delay_p95']:.3f}s")
    return out


def checkpoint_recovery_trace(seed):
    """Long-denoise fleet under frequent Poisson crashes: crash orphans
    restart from denoise step 0 vs. resume from their last partial-progress
    checkpoint (snapshot write cost charged on the sim clock). The regime
    (``simtools.CRASH_FAULTS``) keeps the fleet under capacity so SLO
    misses are crash-caused — exactly the redone work checkpointing
    removes."""
    sc = CRASH_FAULTS
    out = {**sc}
    for tag, ckpt in (("restart", None), ("checkpointed", CheckpointConfig())):
        cl = make_cluster(n_replicas=sc["n_replicas"],
                          policy="join_shortest_queue", steps=sc["steps"],
                          failures=FailureConfig(mtbf=sc["mtbf"],
                                                 recover=True,
                                                 cold_start=sc["cold_start"],
                                                 seed=seed),
                          checkpoint=ckpt, record_timeseries=False)
        m = cl.run(cluster_workload(qps=sc["qps"], duration=sc["duration"],
                                    steps=sc["steps"],
                                    slo_scale=sc["slo_scale"], seed=seed))
        s = m.summary()
        out[tag] = s
        c = s["checkpoint"]
        print(f"ckpt {tag:12s} slo={s['slo_satisfaction']:.3f} "
              f"failed={s['failures']['replicas_failed']} "
              f"requeued={s['failures']['requests_requeued']} "
              f"steps-resumed={c['steps_resumed']} "
              f"write-overhead={c['overhead_s']:.2f}s")
    return out


def zone_outage_trace(seed):
    """Near-capacity fleet over 3 fault domains with recurrent correlated
    zone outages (``simtools.ZONE_FAULTS``): zone-blind dispatch
    (join_shortest_queue, round-robin zone placement — replacements can
    land in a still-down zone and stall until it recovers) vs. the
    fault-domain-aware zone_spread policy (placement balanced across live
    zones, dispatch prefers the least-loaded zone)."""
    sc = ZONE_FAULTS
    out = {**sc}
    for tag, pol in (("zone_blind", "join_shortest_queue"),
                     ("zone_spread", "zone_spread")):
        cl = make_cluster(n_replicas=sc["n_replicas"], policy=pol,
                          failures=FailureConfig(
                              mtbf=None, recover=True,
                              cold_start=sc["cold_start"],
                              zones=sc["zones"],
                              zone_mtbf=sc["zone_mtbf"],
                              zone_downtime=sc["zone_downtime"], seed=seed),
                          record_timeseries=False)
        m = cl.run(cluster_workload(qps=sc["qps"], duration=sc["duration"],
                                    seed=seed))
        s = m.summary()
        out[tag] = s
        f = s["failures"]
        print(f"zone {tag:12s} slo={s['slo_satisfaction']:.3f} "
              f"outages={len(f['zone_outages'])} "
              f"killed={f['replicas_failed']} "
              f"availability={f['zone_availability']}")
    return out


def cachetier_trace(seed):
    """Fleet patch-cache tier on the shared repeat-heavy hybrid-resolution
    scenario (``simtools.CACHE_TIER``): phases concentrate arrivals on one
    end of the resolution ladder and the dominant end flips, so no frozen
    affinity allocation covers every phase while a uniform fleet under
    warmth-directed dispatch retargets each flip. Every run prices the
    same L1 warmth dynamics; the baselines (the PR-4 policies) get no
    fleet L2 (``capacity_bytes=0``), the headline run gets the tier +
    ``cache_affinity`` dispatch. Ablations: ``cache_affinity`` without the
    tier (dispatch-only), ``join_shortest_queue`` with the tier
    (tier-only, thrashes), and the tier at one-third capacity (eviction
    churn). The headline — tier + cache_affinity beats the best no-tier
    PR-4 policy — is asserted in ``main``."""
    sc = CACHE_TIER
    mean_mix = cachetier_mean_mix()
    runs = (
        ("round_robin", "round_robin", 0, None),
        ("join_shortest_queue", "join_shortest_queue", 0, None),
        ("least_slack", "least_slack", 0, None),
        # provisioned at the scenario's arrival-weighted mean mix — the
        # best static allocation the frozen partition could be given (on
        # this regime it coincides with the uniform-mix default, so one
        # run covers both)
        ("resolution_affinity", "resolution_affinity", 0, mean_mix),
        ("cache_affinity(no tier)", "cache_affinity", 0, None),
        ("join_shortest_queue+tier", "join_shortest_queue", None, None),
        ("cache_affinity+tier(small)", "cache_affinity",
         cachetier_config().capacity_bytes // 3, None),
        ("cache_affinity+tier", "cache_affinity", None, None),
    )
    out = {"scenario": {k: (list(map(list, v)) if k == "phases" else v)
                        for k, v in sc.items()},
           "mean_mix": list(mean_mix), "runs": {}}
    for tag, pol, cap, mix0 in runs:
        cl = make_cluster(n_replicas=sc["n_replicas"], policy=pol,
                          steps=sc["steps"], cache=True, initial_mix=mix0,
                          cache_tier=cachetier_config(cap),
                          record_timeseries=False)
        m = cl.run(CACHE_TIER.workload(seed))
        s = m.summary()
        out["runs"][tag] = s
        ct = s["cache_tier"]
        print(f"tier {tag:28s} slo={s['slo_satisfaction']:.3f} "
              f"goodput={s['goodput']:7.2f} "
              f"l1={ct['l1_hit_rate']:.3f} l2={ct['l2_hit_rate']:.3f} "
              f"bytes={ct['tier']['bytes_peak']} "
              f"evict={ct['tier']['evictions']}")
    return out


#: flash-crowd arms, coldest first; ``warmboot_trace`` runs every arm on
#: every seed so the win is per-seed, not an average hiding a loss
WARMBOOT_ARMS = ("cold", "noprefetch", "warm")


def warmboot_trace(seed, n_seeds=3):
    """Warm-boot elastic fleets on the shared flash-crowd spike
    (``simtools.FLASH_CROWD``): a 2-replica fleet sized for the 14 qps
    baseline absorbs a 200 qps / 15 s spike by elastically spawning up to
    6 replicas. Three arms, identical workload and L1 warmth dynamics:
    ``cold`` (no fleet L2 — every spawned replica ramps its reuse
    predictor from scratch, ``warmup_steps=160``), ``noprefetch`` (shared
    tier, spawns still boot with an empty L1 — the ablation), ``warm``
    (tier + ``prefetch_on_spawn``: the spawn path pulls the committed
    entries for the new replica's block during the cold-start window,
    size-dependent transfer time overlapped with boot, and the autoscaler
    prices the shorter effective cold start so predictive spawns trigger
    earlier). The headline — warm beats cold on fleet SLO satisfaction on
    *every* seed — is asserted in ``main`` together with structural
    guards (warm prefetched, the ablations did not, the tier was actually
    written to)."""
    sc = FLASH_CROWD
    out = {"scenario": {k: ([list(p) for p in v] if k == "knots"
                            else (list(v) if isinstance(v, tuple) else v))
                        for k, v in sc.items()},
           "seeds": []}
    for s in range(seed, seed + n_seeds):
        row = {"seed": s}
        for arm in WARMBOOT_ARMS:
            cl = make_cluster(**FLASH_CROWD.cluster_kwargs(arm),
                              record_timeseries=False)
            m = cl.run(FLASH_CROWD.workload(s))
            summ = m.summary()
            ct = summ["cache_tier"]
            tier = ct.get("tier", {})
            row[arm] = {"slo": summ["slo_satisfaction"],
                        "p95": summ["latency_p95"],
                        "goodput": summ["goodput"],
                        "l1_hit_rate": ct.get("l1_hit_rate", 0.0),
                        "prefetches": tier.get("prefetches", 0),
                        "l2_writes": tier.get("writes", 0),
                        "scale_actions": len(cl.autoscaler.actions),
                        "warm_boot_priced": cl.autoscaler.warm_boot}
            print(f"warmboot seed={s} {arm:10s} "
                  f"slo={row[arm]['slo']:.3f} "
                  f"p95={row[arm]['p95']:.3f}s "
                  f"l1={row[arm]['l1_hit_rate']:.3f} "
                  f"prefetch={row[arm]['prefetches']} "
                  f"writes={row[arm]['l2_writes']}")
        out["seeds"].append(row)
    for arm in WARMBOOT_ARMS:
        out[f"mean_slo_{arm}"] = round(
            sum(r[arm]["slo"] for r in out["seeds"]) / n_seeds, 4)
    print(f"warmboot mean slo: warm={out['mean_slo_warm']:.4f} "
          f"noprefetch={out['mean_slo_noprefetch']:.4f} "
          f"cold={out['mean_slo_cold']:.4f}")
    return out


#: gang-batching arms, baseline first; ``batching_trace`` runs all three
#: on the same workload so the ablation isolates the deliberate wait
BATCHING_ARMS = ("per_request", "nowait", "gang")


def batching_trace(seed):
    """Router-side gang batching on the shared knee-load hybrid-resolution
    stream (``simtools.BATCH_MIX``): per-request join_shortest_queue
    dispatch spreads each resolution thin, so every replica steps small
    mixed batches — full per-group overhead and weak patch-cache
    concentration. The batch former stacks patch-compatible requests into
    gangs instead (holding each at most its surplus admission slack, gangs
    sized by the marginal-patch step-cost budget), so replicas step fewer,
    fuller, single-resolution batches. Three arms at equal fleet size:
    ``per_request``, ``nowait`` (former with ``max_wait=0`` — isolates
    grouping-without-waiting; on Poisson arrivals it degenerates to
    per-request, showing the win comes from the deliberate wait) and
    ``gang``. The gang arm also runs traced so the span decomposition —
    now including the ``batch_wait`` component — is checked for
    conservation. The headline (gang beats per_request on fleet SLO
    satisfaction) plus the structural guards are asserted in ``main``."""
    out = {"scenario": {k: (list(v) if isinstance(v, tuple) else v)
                        for k, v in BATCH_MIX.items()}, "runs": {}}
    for arm in BATCHING_ARMS:
        trace = TraceConfig(mode="all", seed=seed) if arm == "gang" else None
        cl = make_cluster(**BATCH_MIX.cluster_kwargs(arm), trace=trace,
                          record_timeseries=False)
        m = cl.run(BATCH_MIX.workload(seed))
        s = m.summary()
        row = {"slo": s["slo_satisfaction"], "p95": s["latency_p95"],
               "goodput": s["goodput"], "utilization": s["utilization"],
               "cache_hit_rate": s["cache_hit_rate"],
               "batching": s.get("batching", {})}
        if trace is not None:
            errs = cl.tracer.conservation_errors()
            row["conservation_max_err"] = max(
                (e for _, e in errs), default=0.0)
            row["batch_wait_total_s"] = round(sum(
                sp.comp["batch_wait"] for sp in cl.tracer.finished), 4)
        out["runs"][arm] = row
        b = row["batching"]
        print(f"batch {arm:12s} slo={row['slo']:.3f} "
              f"p95={row['p95']:.3f}s util={row['utilization']:.2f} "
              f"hit={row['cache_hit_rate']:.3f} "
              f"gangs={b.get('gangs', 0)} "
              f"mean_gang={b.get('mean_gang_size', 0.0):.2f} "
              f"holds={b.get('holds', 0)}")
    return out


#: model-cascade arms, homogeneous baselines first; ``cascade_trace``
#: runs every arm on every seed so the win is per-seed, not an average
CASCADE_ARMS = ("always_cheap", "always_base", "always_big", "cascade")


def cascade_trace(seed, n_seeds=3):
    """Query-aware model cascade on the shared difficulty-tagged stream
    (``simtools.CASCADE_MIX``): four fleets at equal tier-weighted GPU
    cost — three homogeneous (all-lite / all-base / all-max) and the
    heterogeneous cascade (``cascade`` dispatch + confidence-gated
    escalation, escalated work re-entering the frontend priced against
    its remaining slack). Every arm runs under the ``cascade`` policy so
    the only axis is the fleet shape; the homogeneous fleets simply have
    no tier to escalate to. The headline — the cascade beats every
    homogeneous arm on quality-adjusted SLO attainment on *every* seed —
    is asserted in ``main`` with structural guards (equal cost,
    escalations happened, every tier served, traced decomposition with
    the ``escalation`` component conserved)."""
    sc = CASCADE_MIX
    fleets = {"cascade": sc["tiers"], **sc["homogeneous"]}
    out = {"scenario": {
               "qps": sc["qps"], "duration": sc["duration"],
               "steps": sc["steps"], "slo_scale": sc["slo_scale"],
               "difficulties": [list(d) for d in sc["difficulties"]],
               "fleets": {a: dict(f) for a, f in fleets.items()}},
           "fleet_cost": {a: cascade_fleet_cost(f)
                          for a, f in fleets.items()},
           "seeds": []}
    for s in range(seed, seed + n_seeds):
        row = {"seed": s}
        for arm in CASCADE_ARMS:
            # trace one cascade run so the escalation span component is
            # checked for conservation end to end
            trace = TraceConfig(mode="all", seed=s) \
                if arm == "cascade" and s == seed else None
            cl = make_cluster(**CASCADE_MIX.cluster_kwargs(arm),
                              trace=trace, record_timeseries=False)
            m = cl.run(CASCADE_MIX.workload(s))
            summ = m.summary()
            c = summ["cascade"]
            row[arm] = {"slo": summ["slo_satisfaction"],
                        "quality_slo": summ["slo_quality_attainment"],
                        "p95": summ["latency_p95"],
                        "goodput": summ["goodput"],
                        "escalations": c["escalations"],
                        "give_ups": c["give_ups"],
                        "escalation_rate": c["escalation_rate"],
                        "per_tier": c["per_tier"]}
            if trace is not None:
                errs = cl.tracer.conservation_errors()
                row[arm]["conservation_max_err"] = max(
                    (e for _, e in errs), default=0.0)
                row[arm]["escalation_total_s"] = round(sum(
                    sp.comp["escalation"] for sp in cl.tracer.finished), 4)
            print(f"cascade seed={s} {arm:12s} "
                  f"quality_slo={row[arm]['quality_slo']:.3f} "
                  f"slo={row[arm]['slo']:.3f} "
                  f"esc={row[arm]['escalations']} "
                  f"giveup={row[arm]['give_ups']}")
        out["seeds"].append(row)
    for arm in CASCADE_ARMS:
        out[f"mean_quality_slo_{arm}"] = round(
            sum(r[arm]["quality_slo"] for r in out["seeds"]) / n_seeds, 4)
    print("cascade mean quality slo: " + " ".join(
        f"{a}={out[f'mean_quality_slo_{a}']:.4f}" for a in CASCADE_ARMS))
    return out


#: the four --monitor regimes: the quiet control first, then the three
#: incident classes the alert rules must trip on
MONITOR_REGIMES = ("baseline", "crash", "zone", "spike")


def _monitor_run(regime, seed, mcfg):
    """One monitored run of a ``--monitor`` regime (shared scenarios; the
    fleets match the --faults / --warmboot arms they alert on)."""
    if regime == "baseline":
        sc = HEALTHY_BASELINE
        cl = make_cluster(n_replicas=sc["n_replicas"],
                          policy="join_shortest_queue", steps=sc["steps"],
                          monitor=mcfg, record_timeseries=False)
        m = cl.run(cluster_workload(qps=sc["qps"], duration=sc["duration"],
                                    steps=sc["steps"],
                                    slo_scale=sc["slo_scale"], seed=seed))
    elif regime == "crash":
        sc = CRASH_FAULTS
        cl = make_cluster(n_replicas=sc["n_replicas"],
                          policy="join_shortest_queue", steps=sc["steps"],
                          failures=FailureConfig(mtbf=sc["mtbf"],
                                                 recover=True,
                                                 cold_start=sc["cold_start"],
                                                 seed=seed),
                          monitor=mcfg, record_timeseries=False)
        m = cl.run(cluster_workload(qps=sc["qps"], duration=sc["duration"],
                                    steps=sc["steps"],
                                    slo_scale=sc["slo_scale"], seed=seed))
    elif regime == "zone":
        sc = ZONE_FAULTS
        cl = make_cluster(n_replicas=sc["n_replicas"],
                          policy="join_shortest_queue",
                          failures=FailureConfig(
                              mtbf=None, recover=True,
                              cold_start=sc["cold_start"],
                              zones=sc["zones"],
                              zone_mtbf=sc["zone_mtbf"],
                              zone_downtime=sc["zone_downtime"], seed=seed),
                          monitor=mcfg, record_timeseries=False)
        # MONITOR_ZONE_QPS (not sc["qps"]): near capacity, losing a zone
        # always threatens the SLO, so "every incident pages" is testable
        m = cl.run(cluster_workload(qps=MONITOR_ZONE_QPS,
                                    duration=sc["duration"], seed=seed))
    else:
        cl = make_cluster(**FLASH_CROWD.cluster_kwargs("cold"),
                          monitor=mcfg, record_timeseries=False)
        m = cl.run(FLASH_CROWD.workload(seed))
    return cl, m


def monitor_trace(seed, n_seeds=3, trace_dir=None):
    """Streaming fleet health monitor on four shared regimes, >=3 seeds:
    ``baseline`` (``HEALTHY_BASELINE`` — the crash fleet with the failure
    process removed; the burn-rate rules must stay silent), ``crash``
    (``CRASH_FAULTS`` Poisson crashes), ``zone`` (``ZONE_FAULTS``
    correlated outages, zone-blind arm) and ``spike`` (``FLASH_CROWD``
    flash crowd, cold arm). Per run the monitor's streamed alerts are
    checked against ground truth: every alert's ``dominant`` latency
    component must equal the tracer's post-hoc SLO-violation attribution
    recomputed over exactly the alert's evaluation window
    (``dominant_over_spans`` on the same closed bins), every injected
    incident must contain an alert (recall 1.0), the baseline must fire
    nothing, and the spike arm must alert inside the crowd window and
    never before it. All asserted in ``main``. With ``trace_dir`` the
    crash run's health log (``monitor_alerts.jsonl``) and Prometheus
    snapshot (``monitor_prometheus.txt``) are persisted as artifacts."""
    mw = monitor_config()
    knots = FLASH_CROWD["knots"]
    spike_start = max(knots, key=lambda k: k[1])[0]
    spike_end = min((t for t, _ in knots if t > spike_start),
                    default=spike_start)
    out = {"window": mw.window, "slo_target": mw.slo_target,
           "rules": [{"name": r.name, "short_s": r.short_window,
                      "long_s": r.long_window, "burn_rate": r.burn_rate}
                     for r in mw.rules],
           "spike_window": [spike_start, spike_end + mw.incident_horizon],
           "seeds": []}
    for s in range(seed, seed + n_seeds):
        row = {"seed": s}
        for regime in MONITOR_REGIMES:
            mcfg = monitor_config()
            cl, m = _monitor_run(regime, s, mcfg)
            mon = m.monitor
            alerts = cl.monitor.alerts
            mismatches = sum(
                1 for a in alerts
                if a["dominant"] != dominant_over_spans(
                    cl.tracer.finished, a["win"][0], a["win"][1],
                    mcfg.window))
            row[regime] = {
                "slo": m.slo_satisfaction,
                "alerts": len(alerts),
                "alert_times": [round(a["t"], 3) for a in alerts],
                "dominants": sorted({a["dominant"] for a in alerts}),
                "dominant_mismatches": mismatches,
                "incidents": mon["incidents"],
                "precision": mon["precision"],
                "recall": mon["recall"],
                "anomalies": mon["anomalies"],
            }
            if regime == "spike":
                row[regime]["alerts_pre_spike"] = sum(
                    1 for a in alerts if a["t"] < spike_start)
                row[regime]["alerts_in_spike"] = sum(
                    1 for a in alerts if spike_start <= a["t"]
                    <= spike_end + mcfg.incident_horizon)
            if trace_dir is not None and s == seed and regime == "crash":
                tdir = Path(trace_dir)
                tdir.mkdir(parents=True, exist_ok=True)
                n_rec = cl.monitor.write_jsonl(tdir / "monitor_alerts.jsonl")
                (tdir / "monitor_prometheus.txt").write_text(
                    cl.monitor.prometheus_text())
                row[regime]["artifact_records"] = n_rec
                print(f"monitor artifacts: {n_rec} jsonl records -> {tdir}")
            r = row[regime]
            print(f"monitor seed={s} {regime:9s} slo={r['slo']:.3f} "
                  f"alerts={r['alerts']} incidents={r['incidents']} "
                  f"recall={r['recall']:.2f} anomalies={r['anomalies']} "
                  f"dominant={','.join(r['dominants']) or '-'}")
        out["seeds"].append(row)
    return out


def traced_run(trace_dir, mode, seed):
    """One traced regime for ``--trace-dir``: the crash+checkpoint
    scenario under ``least_slack`` dispatch, chosen because it walks the
    nastiest span paths (crash-orphan requeue, checkpoint resume, drops)
    so the exported decomposition shows every component class. Writes
    ``trace.jsonl`` / ``trace_chrome.json`` / ``timeseries.json`` into
    DIR and prints the SLO-violation attribution histogram."""
    tdir = Path(trace_dir)
    tdir.mkdir(parents=True, exist_ok=True)
    cl = make_cluster(n_replicas=3, policy="least_slack",
                      failures=FailureConfig(mtbf=10.0, recover=True,
                                             seed=seed),
                      checkpoint=CheckpointConfig(),
                      trace=TraceConfig(mode=mode, seed=seed),
                      record_timeseries=True)
    m = cl.run(cluster_workload(qps=30.0, duration=12.0, seed=seed))
    s = m.summary(full_timeseries=True)
    n_spans = cl.tracer.write_jsonl(tdir / "trace.jsonl")
    n_chrome = cl.tracer.write_chrome_trace(tdir / "trace_chrome.json")
    (tdir / "timeseries.json").write_text(json.dumps(s, indent=1))
    att = s.get("attribution", {})
    pred = s.get("predictor", {})
    print(f"trace mode={mode}: {n_spans} jsonl records, "
          f"{n_chrome} chrome events -> {tdir}")
    for comp, cnt in sorted(att.get("dominant", {}).items(),
                            key=lambda kv: -kv[1]):
        print(f"  violations dominated by {comp:16s} {cnt}")
    if pred:
        print(f"  predictor n={pred['n']} mae={pred['mae']:.4f}s "
              f"bias={pred['bias']:+.4f}s drift={pred['drift']}")
    return {"mode": mode, "dir": str(tdir), "jsonl_records": n_spans,
            "chrome_events": n_chrome, "attribution": att,
            "predictor": pred}


#: ``cachetier_trace`` runs counted as no-tier PR-4 baselines by the
#: headline assert (cache_affinity and the tier runs are this PR's)
CACHETIER_BASELINES = ("round_robin", "join_shortest_queue", "least_slack",
                       "resolution_affinity")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="3 QPS points, one replica count")
    ap.add_argument("--adaptive", action="store_true",
                    help="add drifting-mix repartitioning + predictive "
                         "autoscaling comparisons (cache-aware surrogate)")
    ap.add_argument("--elastic", action="store_true",
                    help="add elastic-controller comparisons: up/down "
                         "arrival wave (predictive retirement + resize "
                         "repartitioning vs frozen baseline) and Poisson "
                         "replica crashes (recovery vs none)")
    ap.add_argument("--faults", action="store_true",
                    help="add fault-tolerance comparisons: checkpointed "
                         "crash recovery vs restart-from-zero, and "
                         "zone_spread vs zone-blind dispatch under "
                         "correlated zone outages")
    ap.add_argument("--cachetier", action="store_true",
                    help="add the fleet patch-cache-tier comparison: "
                         "tier + cache_affinity dispatch vs every no-tier "
                         "PR-4 policy on the repeat-heavy hybrid-"
                         "resolution scenario (win asserted)")
    ap.add_argument("--warmboot", action="store_true",
                    help="add the warm-boot elastic fleet comparison: "
                         "spawn prefetch from the cache tier vs tier-"
                         "without-prefetch vs no-tier on the flash-crowd "
                         "spike, >=3 seeds (per-seed win asserted)")
    ap.add_argument("--batching", action="store_true",
                    help="add the router-side gang-batching comparison: "
                         "batch-former gang dispatch vs nowait ablation vs "
                         "per-request dispatch on the knee-load hybrid-"
                         "resolution stream (win + eligibility guards "
                         "asserted, traced arm checked for conservation)")
    ap.add_argument("--cascade", action="store_true",
                    help="add the query-aware model-cascade comparison: "
                         "heterogeneous tiered fleet with confidence-gated "
                         "escalation vs all-lite / all-base / all-max "
                         "fleets at equal tier-weighted GPU cost, >=3 "
                         "seeds (per-seed quality-adjusted win asserted)")
    ap.add_argument("--monitor", action="store_true",
                    help="add the fleet-health-monitor validation: "
                         "burn-rate alerting on healthy / crash / zone-"
                         "outage / flash-crowd regimes, >=3 seeds — "
                         "silent baseline, every incident alerted, alert "
                         "dominant components matched against post-hoc "
                         "span attribution (all asserted); with "
                         "--trace-dir also writes monitor_alerts.jsonl + "
                         "monitor_prometheus.txt")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="run one traced regime (crash+checkpoint) and "
                         "write trace.jsonl / trace_chrome.json / "
                         "timeseries.json into DIR")
    ap.add_argument("--trace-mode", default="all",
                    choices=("all", "violations", "sample"),
                    help="per-request event retention for --trace-dir "
                         "(spans/attribution always cover every request)")
    ap.add_argument("--out", default="benchmarks/cluster_results.json")
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    qps_grid = [24.0, 48.0, 96.0] if args.fast \
        else [16.0, 32.0, 48.0, 64.0, 96.0, 128.0]
    replica_grid = [3] if args.fast else [2, 4]
    mix = (0.2, 0.2, 0.6)          # skewed toward High, stresses routing

    results = sweep(qps_grid, replica_grid, args.duration, args.seed, mix)
    scaled = autoscale_trace(qps=48.0, duration=max(args.duration, 40.0),
                             seed=args.seed + 1, mix=mix)

    adaptive = None
    if args.adaptive:
        drift_qps = [96.0, 128.0] if args.fast else [96.0, 128.0, 160.0]
        adaptive = {
            "repartition": adaptive_repartition_trace(
                drift_qps, duration=max(args.duration, 60.0),
                seed=args.seed),
            "autoscale": predictive_autoscale_trace(
                duration=max(args.duration, 35.0), seed=args.seed + 2)}

    elastic = None
    if args.elastic:
        elastic = {"updown": elastic_updown_trace(seed=args.seed + 2),
                   "crash": failure_recovery_trace(seed=args.seed + 4)}

    faults = None
    if args.faults:
        faults = {"checkpoint": checkpoint_recovery_trace(seed=args.seed + 6),
                  "zones": zone_outage_trace(seed=args.seed + 6)}

    cachetier = None
    if args.cachetier:
        cachetier = cachetier_trace(seed=args.seed + 6)

    warmboot = None
    if args.warmboot:
        warmboot = warmboot_trace(seed=args.seed)

    batching = None
    if args.batching:
        batching = batching_trace(seed=args.seed)

    cascade = None
    if args.cascade:
        cascade = cascade_trace(seed=args.seed)

    monitor = None
    if args.monitor:
        monitor = monitor_trace(seed=args.seed, trace_dir=args.trace_dir)

    traced = None
    if args.trace_dir:
        traced = traced_run(args.trace_dir, args.trace_mode,
                            seed=args.seed + 8)

    # headline: SLO-aware / resolution-aware routing must beat round-robin
    # somewhere in the sweep
    wins = []
    by_key = {(r["qps"], r["n_replicas"], r["policy"]):
              r["slo_satisfaction"] for r in results}
    for (qps, n, pol), slo in by_key.items():
        if pol in ("least_slack", "resolution_affinity") \
                and slo > by_key[(qps, n, "round_robin")]:
            wins.append((qps, n, pol, slo,
                         by_key[(qps, n, "round_robin")]))
    out = {"meta": {"duration": args.duration, "seed": args.seed,
                    "mix": list(mix), "qps_grid": qps_grid,
                    "replica_grid": replica_grid},
           "results": results, "autoscaled": scaled,
           "routing_wins_vs_round_robin": [
               {"qps": q, "n_replicas": n, "policy": p,
                "slo": s, "round_robin_slo": rr}
               for q, n, p, s, rr in wins]}
    if adaptive is not None:
        out["adaptive"] = adaptive
        adaptive_wins = [
            row["qps"] for row in adaptive["repartition"]
            if row["adaptive"]["slo_satisfaction"]
            > row["static"]["slo_satisfaction"]]
        out["adaptive"]["repartition_wins_qps"] = adaptive_wins
    if elastic is not None:
        out["elastic"] = elastic
    if faults is not None:
        out["faults"] = faults
    if cachetier is not None:
        out["cachetier"] = cachetier
    if warmboot is not None:
        out["warmboot"] = warmboot
    if batching is not None:
        out["batching"] = batching
    if cascade is not None:
        out["cascade"] = cascade
    if monitor is not None:
        out["monitor"] = monitor
    if traced is not None:
        out["traced"] = traced
    Path(args.out).write_text(json.dumps(out, indent=1))
    print(f"# wrote {args.out} ({len(results)} sweep points, "
          f"{len(wins)} routing wins vs round_robin)", file=sys.stderr)
    if not wins:
        raise SystemExit("no sweep point where SLO/resolution-aware "
                         "routing beat round_robin — policy regression?")
    if adaptive is not None:
        if not adaptive_wins:
            raise SystemExit(
                "no drifting-mix workload where adaptive repartitioning "
                "beat the static affinity partition — adaptation "
                "regression?")
        ra, rr2 = (adaptive["autoscale"]["predictive"],
                   adaptive["autoscale"]["reactive"])
        if ra["slo_satisfaction"] < rr2["slo_satisfaction"]:
            raise SystemExit("predictive autoscaler lost to reactive on "
                             "the ramp workload — forecaster regression?")
    if elastic is not None:
        el, bl = elastic["updown"]["elastic"], elastic["updown"]["baseline"]
        if el["slo_satisfaction"] <= bl["slo_satisfaction"]:
            raise SystemExit(
                "elastic controller lost to the frozen baseline on the "
                "up/down wave — controller regression?")
        if not el["predictive_retirements"]:
            raise SystemExit("elastic controller never retired ahead of "
                             "the ramp-down — predictive-down regression?")
        if el["replicas"]["final"] >= bl["replicas"]["final"]:
            raise SystemExit(
                "elastic controller did not track the ramp-down (final "
                "fleet not smaller than the frozen baseline's)")
        rec = elastic["crash"]["recovery"]
        norec = elastic["crash"]["no_recovery"]
        if rec["failures"]["replicas_failed"] == 0:
            raise SystemExit("crash scenario injected no failures — "
                             "failure-injection regression?")
        if rec["slo_satisfaction"] <= norec["slo_satisfaction"]:
            raise SystemExit(
                "failure recovery lost to no-recovery on the crash "
                "workload — recovery regression?")
    if faults is not None:
        ck, rs = faults["checkpoint"]["checkpointed"], \
            faults["checkpoint"]["restart"]
        if ck["checkpoint"]["steps_resumed"] <= 0:
            raise SystemExit("checkpointed run resumed no denoise steps — "
                             "checkpoint-restore regression?")
        if ck["slo_satisfaction"] <= rs["slo_satisfaction"]:
            raise SystemExit(
                "checkpointed crash recovery lost to restart-from-zero — "
                "checkpointing regression (or write cost swamping the "
                "redone-work savings)?")
        zs, zb = faults["zones"]["zone_spread"], faults["zones"]["zone_blind"]
        if not zs["failures"]["zone_outages"]:
            raise SystemExit("zone scenario injected no outages — "
                             "zone-failure regression?")
        if zs["slo_satisfaction"] <= zb["slo_satisfaction"]:
            raise SystemExit(
                "zone_spread dispatch lost to zone-blind dispatch under "
                "zone outages — fault-domain-awareness regression?")
    if cachetier is not None:
        head = cachetier["runs"]["cache_affinity+tier"]
        best_tag = max(CACHETIER_BASELINES,
                       key=lambda t: cachetier["runs"][t]
                       ["slo_satisfaction"])
        best = cachetier["runs"][best_tag]
        if head["cache_tier"]["l2_hit_rate"] <= 0:
            raise SystemExit("cache tier served no L2 hits — tier "
                             "protocol regression?")
        if head["cache_tier"]["tier"]["writes"] <= 0:
            raise SystemExit("nothing was ever published to the cache "
                             "tier — publish-path regression?")
        # the tier's own contribution: fetches convert cold keys to warm
        # instantly, so the tier run must hold a clearly warmer L1 than
        # the dispatch-only ablation (SLO margins between the two are
        # noise-level on a fixed fleet, but this gap is structural — it
        # collapses if the fetch path stops warming keys)
        abl = cachetier["runs"]["cache_affinity(no tier)"]
        if head["cache_tier"]["l1_hit_rate"] \
                <= abl["cache_tier"]["l1_hit_rate"]:
            raise SystemExit(
                "the tier run's L1 is no warmer than the no-tier "
                "cache_affinity ablation's — fetch-path regression?")
        if head["slo_satisfaction"] <= best["slo_satisfaction"]:
            raise SystemExit(
                f"tier + cache_affinity ({head['slo_satisfaction']:.3f}) "
                f"lost to the best no-tier policy ({best_tag}, "
                f"{best['slo_satisfaction']:.3f}) on the repeat-heavy "
                "hybrid-resolution scenario — cache-tier regression?")
    if warmboot is not None:
        for row in warmboot["seeds"]:
            w, np_, c = row["warm"], row["noprefetch"], row["cold"]
            if w["prefetches"] <= 0:
                raise SystemExit(
                    f"warm arm (seed {row['seed']}) never prefetched on "
                    "spawn — spawn-prefetch path regression?")
            if np_["prefetches"] > 0 or c["prefetches"] > 0:
                raise SystemExit(
                    f"an ablation arm prefetched (seed {row['seed']}) — "
                    "prefetch_on_spawn gating regression?")
            if w["l2_writes"] <= 0:
                raise SystemExit(
                    f"warm arm (seed {row['seed']}) committed nothing to "
                    "the tier — publish-path regression?")
            if not w["warm_boot_priced"]:
                raise SystemExit(
                    "warm arm's autoscaler was not flagged warm-bootable "
                    "— effective-cold-start pricing regression?")
            if w["slo"] <= c["slo"]:
                raise SystemExit(
                    f"tier-warmed elastic fleet ({w['slo']:.3f}) lost to "
                    f"the cold elastic fleet ({c['slo']:.3f}) on the "
                    f"flash-crowd spike (seed {row['seed']}) — warm-boot "
                    "regression?")
    if batching is not None:
        gang = batching["runs"]["gang"]
        pr = batching["runs"]["per_request"]
        nw = batching["runs"]["nowait"]
        gb = gang["batching"]
        if gb.get("gangs", 0) <= 0:
            raise SystemExit("gang arm never formed a gang — batch-former "
                             "grouping regression?")
        mhs = gb.get("min_hold_slack_s")
        if mhs is not None and mhs < BATCH_MIX["max_wait"]:
            raise SystemExit(
                f"a request was held with only {mhs:.4f}s of slack "
                f"(< max_wait={BATCH_MIX['max_wait']}) — tight-SLO work "
                "must dispatch immediately (eligibility regression?)")
        if gb.get("deadline_overshoot_max", 0.0) > 1e-6:
            raise SystemExit(
                f"a hold overshot its eligibility deadline by "
                f"{gb['deadline_overshoot_max']:.2e}s — the driver is not "
                "treating hold deadlines as sim events?")
        if gang.get("conservation_max_err", 0.0) > 1e-9:
            raise SystemExit(
                f"traced gang-arm decomposition broke conservation "
                f"(max err {gang['conservation_max_err']:.2e}) — "
                "batch_wait span accounting regression?")
        if gang.get("batch_wait_total_s", 0.0) <= 0.0:
            raise SystemExit("traced gang arm charged no batch_wait — "
                             "hold spans are not being labeled?")
        if nw["batching"].get("holds", 0) != 0:
            raise SystemExit("nowait ablation deliberately held a request "
                             "— max_wait=0 gating regression?")
        if gang["slo"] <= pr["slo"]:
            raise SystemExit(
                f"gang-batched dispatch ({gang['slo']:.3f}) lost to "
                f"per-request dispatch ({pr['slo']:.3f}) at equal fleet "
                "size on the knee-load stream — batch-former regression?")
    if cascade is not None:
        costs = set(cascade["fleet_cost"].values())
        if len(costs) != 1:
            raise SystemExit(
                f"cascade arms are not cost-matched ({cascade['fleet_cost']})"
                " — the comparison is only fair at equal tier-weighted "
                "GPU cost (fleet spec regression?)")
        for row in cascade["seeds"]:
            cs = row["cascade"]
            if cs["escalations"] <= 0:
                raise SystemExit(
                    f"cascade arm (seed {row['seed']}) never escalated — "
                    "confidence-gate regression?")
            if not 0.0 < cs["escalation_rate"] < 1.0:
                raise SystemExit(
                    f"cascade escalation rate {cs['escalation_rate']} out "
                    f"of (0, 1) (seed {row['seed']}) — gate accounting "
                    "regression?")
            idle = [t for t, pt in cs["per_tier"].items()
                    if pt["completed"] <= 0]
            if idle:
                raise SystemExit(
                    f"cascade tiers {idle} completed nothing (seed "
                    f"{row['seed']}) — tiered dispatch regression?")
            if cs.get("conservation_max_err", 0.0) > 1e-9:
                raise SystemExit(
                    f"traced cascade decomposition broke conservation "
                    f"(max err {cs['conservation_max_err']:.2e}) — "
                    "escalation span accounting regression?")
            for arm in ("always_cheap", "always_base", "always_big"):
                if cs["quality_slo"] <= row[arm]["quality_slo"]:
                    raise SystemExit(
                        f"cascade ({cs['quality_slo']:.3f}) lost to "
                        f"{arm} ({row[arm]['quality_slo']:.3f}) on "
                        f"quality-adjusted SLO attainment at equal fleet "
                        f"cost (seed {row['seed']}) — cascade regression?")
        tr = cascade["seeds"][0]["cascade"]
        if tr.get("escalation_total_s", 0.0) <= 0.0:
            raise SystemExit("traced cascade arm charged no escalation "
                             "time — escalation spans are not being "
                             "labeled?")
    if monitor is not None:
        for row in monitor["seeds"]:
            sd = row["seed"]
            if row["baseline"]["alerts"] != 0:
                raise SystemExit(
                    f"burn-rate rules fired {row['baseline']['alerts']} "
                    f"alert(s) on the healthy baseline (seed {sd}, "
                    f"t={row['baseline']['alert_times']}) — the monitor "
                    "pages on a fleet that is inside budget (threshold "
                    "regression?)")
            for regime in ("crash", "zone"):
                r = row[regime]
                if r["incidents"] <= 0:
                    raise SystemExit(
                        f"{regime} regime injected no incidents (seed "
                        f"{sd}) — failure-injection regression?")
                if r["alerts"] <= 0 or r["recall"] < 1.0:
                    raise SystemExit(
                        f"{regime} regime left an injected incident "
                        f"un-alerted (seed {sd}: {r['alerts']} alerts, "
                        f"recall {r['recall']}) — burn-rate alerting "
                        "regression?")
            sp = row["spike"]
            if sp["alerts_pre_spike"] != 0:
                raise SystemExit(
                    f"monitor alerted before the flash crowd started "
                    f"(seed {sd}, t={sp['alert_times']}) — false page on "
                    "the quiet ramp-up (rule arming regression?)")
            if sp["alerts_in_spike"] <= 0:
                raise SystemExit(
                    f"flash crowd (seed {sd}, window "
                    f"{monitor['spike_window']}) fired no alert — "
                    "burn-rate alerting regression?")
            for regime in MONITOR_REGIMES:
                if row[regime]["dominant_mismatches"]:
                    raise SystemExit(
                        f"{row[regime]['dominant_mismatches']} alert(s) "
                        f"in the {regime} regime (seed {sd}) carried a "
                        "dominant latency component that disagrees with "
                        "the post-hoc span attribution over the same "
                        "window — streamed attribution regression?")


if __name__ == "__main__":
    main()
