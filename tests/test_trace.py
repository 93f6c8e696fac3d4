"""Fleet tracing — per-request spans, the latency-decomposition
conservation invariant (components sum to end-to-end latency, to 1e-9,
including crash-requeue / mid-migration / tier-fetch paths), SLO-violation
attribution, predictor calibration, exporters (JSONL round-trip through
``scripts/trace_report.py``, Chrome-trace structure), event-bus ordering
under batched zone-outage requeues, retention modes, and the zero-cost
guarantee when tracing is off (headline metrics bit-identical, no tracer
method ever reached through the ``NULL_TRACER`` guards)."""
import importlib.util
import json
import time
from pathlib import Path

import pytest

from repro.cluster import (COMPONENTS, CheckpointConfig, Cluster,
                           ClusterConfig, FailureConfig, NullTracer,
                           RepartitionConfig, TraceConfig, Tracer,
                           cachetier_config, cachetier_workload,
                           cluster_workload, phased_workload,
                           sim_engine_factory)
from repro.cluster.simtools import DEFAULT_RES, CacheHitModel

MIX_A = (0.6, 0.3, 0.1)
MIX_B = (0.1, 0.3, 0.6)

#: named regimes covering every span path: steady dispatch, crash-orphan
#: requeue + checkpoint resume, drain-before-switch migration, fleet
#: cache-tier fetch/publish stalls
REGIMES = {
    "steady": dict(policy="least_slack", n=3,
                   wl=dict(qps=30.0, duration=10.0, seed=1)),
    "crash": dict(policy="least_slack", n=3,
                  failures=FailureConfig(mtbf=10.0, recover=True, seed=2),
                  checkpoint=CheckpointConfig(),
                  wl=dict(qps=30.0, duration=12.0, seed=2)),
    "zone": dict(policy="zone_spread", n=4,
                 failures=FailureConfig(mtbf=None, zones=2, zone_mtbf=6.0,
                                        seed=5),
                 checkpoint=CheckpointConfig(),
                 wl=dict(qps=30.0, duration=12.0, seed=5)),
}


def _build(policy="least_slack", n=3, failures=None, checkpoint=None,
           repartition=None, initial_mix=None, cache_tier=None,
           trace=None, cache=False, record=True, wl=None):
    cfg = ClusterConfig(n_replicas=n, policy=policy, failures=failures,
                        checkpoint=checkpoint, repartition=repartition,
                        initial_mix=initial_mix, cache_tier=cache_tier,
                        trace=trace, record_timeseries=record)
    factory = sim_engine_factory(
        DEFAULT_RES, cache=CacheHitModel() if cache else None)
    return Cluster(factory, DEFAULT_RES, cfg)


def _run(regime, trace=TraceConfig(), **over):
    spec = {**REGIMES[regime], **over}
    wl = spec.pop("wl")
    cl = _build(trace=trace, **spec)
    m = cl.run(cluster_workload(**wl))
    return cl, m


def _migration_cluster(trace=TraceConfig()):
    cl = _build(policy="resolution_affinity", n=4,
                repartition=RepartitionConfig(), initial_mix=MIX_A,
                trace=trace)
    m = cl.run(phased_workload([(15.0, 48.0, MIX_A), (15.0, 48.0, MIX_B)],
                               seed=2))
    return cl, m


def _tier_cluster(trace=TraceConfig()):
    cl = _build(policy="cache_affinity", n=3, cache=True,
                cache_tier=cachetier_config(), trace=trace)
    m = cl.run(cachetier_workload(seed=3))
    return cl, m


def _assert_conserved(cl, tol=1e-9):
    errs = cl.tracer.conservation_errors()
    assert errs, "no finished spans"
    bad = [(rid, e) for rid, e in errs if e > tol]
    assert not bad, f"conservation violated: {bad[:5]}"
    return len(errs)


def _component_totals(cl):
    out = dict.fromkeys(COMPONENTS, 0.0)
    for s in cl.tracer.finished:
        for k, v in s.comp.items():
            out[k] += v
    return out


# ---------------- conservation invariant ----------------

@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_conservation(regime):
    cl, m = _run(regime)
    n = _assert_conserved(cl)
    assert n == m.completed + m.dropped


def test_conservation_crash_requeue_components():
    """Crash orphans roll back the in-flight step to the crash instant and
    relabel lost work denoise_lost; checkpoint writes surface as
    checkpoint_wait — and the invariant holds through the rollback.
    (requeue_wait stays 0 here: surviving replicas accept the orphans in
    the same dispatch instant — the zone test covers the stalled case.)"""
    cl, m = _run("crash", wl=dict(qps=60.0, duration=12.0, seed=2))
    _assert_conserved(cl)
    assert m.replicas_failed > 0 and m.requests_requeued > 0
    comp = _component_totals(cl)
    assert comp["denoise_lost"] > 0
    assert comp["checkpoint_wait"] > 0
    requeued = [s for s in cl.tracer.finished if s.requeues > 0]
    assert requeued
    for s in requeued:
        assert abs(s.total() - (s.end - s.arrival)) <= 1e-9


def test_requeue_wait_charged_when_fleet_stalled():
    """When a zone outage leaves requeued orphans with no dispatch target,
    their post-crash queue time is charged to requeue_wait — a component
    distinct from first-arrival frontend_wait."""
    cl, m = _run("zone")
    comp = _component_totals(cl)
    assert comp["requeue_wait"] > 0
    assert comp["frontend_wait"] > 0


def test_conservation_mid_migration():
    """Drain-before-switch repartitioning keeps every resident span
    conserved across the engine swap."""
    cl, m = _migration_cluster()
    assert m.migrations > 0
    _assert_conserved(cl)


def test_conservation_tier_fetch():
    """Fleet cache-tier fetch/publish clock cost shows up as tier_wait and
    the decomposition still sums exactly."""
    cl, m = _tier_cluster()
    _assert_conserved(cl)
    assert _component_totals(cl)["tier_wait"] > 0
    assert m.cache_tier["l2_fetches"] > 0


def test_conservation_property():
    """Property-style sweep: conservation holds across seeds x load levels
    on the crash regime (hypothesis when available, deterministic seed
    loop otherwise — both drive the same invariant check)."""
    def one(seed, qps):
        cl, _ = _run("crash", wl=dict(qps=qps, duration=8.0, seed=seed))
        _assert_conserved(cl)

    try:
        from hypothesis import given, settings
        from hypothesis import strategies as st

        @settings(max_examples=8, deadline=None)
        @given(seed=st.integers(0, 31), qps=st.sampled_from(
            [12.0, 30.0, 60.0]))
        def prop(seed, qps):
            one(seed, qps)

        prop()
    except ImportError:
        for seed in range(4):
            for qps in (12.0, 30.0, 60.0):
                one(seed, qps)


def test_conservation_escalation_component():
    """Cascade escalation keeps the decomposition conserved: the rejected
    cheap completion re-opens the span in the ``escalation`` component,
    which runs until the higher tier admits the re-run — and every span
    still sums to end-to-end latency to 1e-9."""
    from benchmarks.common import make_cluster
    cl = make_cluster(policy="cascade", tiers={"lite": 1, "base": 1},
                      steps=6, trace=TraceConfig(), record_timeseries=False)
    wl = cluster_workload(qps=8.0, duration=8.0, steps=6, slo_scale=50.0,
                          seed=2)
    for r in wl:
        r.difficulty = 0.7             # above lite quality: gate escalates
    m = cl.run(wl)
    assert m.cascade["escalations"] > 0
    n = _assert_conserved(cl)
    assert n == m.completed + m.dropped
    assert "escalation" in COMPONENTS
    comp = _component_totals(cl)
    assert comp["escalation"] > 0
    # only escalated spans ever carry the component (an escalated span
    # can still show 0.0 — the higher tier was idle and admitted the
    # re-run at the same instant)
    esc_rids = {e["rid"] for e in cl.tracer.events()
                if e["kind"] == "escalate"}
    assert esc_rids
    charged = {s.rid for s in cl.tracer.finished
               if s.comp.get("escalation", 0.0) > 0}
    assert charged and charged <= esc_rids
    # tracing is pure observation on the cascade path too
    cl_off = make_cluster(policy="cascade", tiers={"lite": 1, "base": 1},
                          steps=6, record_timeseries=False)
    wl_off = cluster_workload(qps=8.0, duration=8.0, steps=6,
                              slo_scale=50.0, seed=2)
    for r in wl_off:
        r.difficulty = 0.7
    m_off = cl_off.run(wl_off)
    assert _headline(m_off) == _headline(m)
    assert m_off.cascade == m.cascade


# ---------------- disabled path: bit-identical + zero-cost ----------------

def _headline(m):
    return {"slo_satisfaction": m.slo_satisfaction, "goodput": m.goodput,
            "completed": m.completed, "dropped": m.dropped,
            "latencies": sorted(m.latencies)}


@pytest.mark.parametrize("regime", sorted(REGIMES))
def test_disabled_tracer_bit_identical(regime):
    """Tracing must be pure observation: headline metrics are
    bit-identical with the tracer on and off."""
    _, m_off = _run(regime, trace=None)
    _, m_on = _run(regime)
    assert _headline(m_off) == _headline(m_on)
    assert m_off.trace_events == 0 and m_on.trace_events > 0


class _SpyNull(NullTracer):
    """enabled=False tracer that records any method lookup — if a guarded
    call site ever reaches past the ``if tracer.enabled`` check while
    tracing is off, the lookup lands here."""

    calls = []

    def __getattr__(self, name):
        _SpyNull.calls.append(name)
        return super().__getattr__(name)


def test_disabled_tracer_never_called():
    """Structural zero-cost: with tracing off no tracer method is ever
    invoked — every call site is behind the enabled guard."""
    _SpyNull.calls = []
    spec = dict(REGIMES["crash"])
    wl = spec.pop("wl")
    cl = _build(trace=None, **spec)
    spy = _SpyNull()
    cl.tracer = spy
    cl.router.tracer = spy
    if cl.autoscaler is not None:
        cl.autoscaler.tracer = spy
    if cl.cache_tier is not None:
        cl.cache_tier.tracer = spy
    for rep in cl.replicas:
        rep.tracer = spy
    cl.run(cluster_workload(**wl))
    assert _SpyNull.calls == []


def test_disabled_tracer_micro_benchmark():
    """Wall-clock sanity: the disabled path must not pay for tracing.
    Generous 1.5x margin over the enabled run keeps this robust to CI
    timer noise while still catching an unguarded hot path."""
    def timed(trace):
        t0 = time.perf_counter()
        _run("steady", trace=trace)
        return time.perf_counter() - t0

    timed(None)                        # warm imports / JIT-free baseline
    off = min(timed(None) for _ in range(3))
    on = min(timed(TraceConfig()) for _ in range(3))
    assert off <= on * 1.5, (off, on)


# ---------------- event bus ordering ----------------

def test_events_nondecreasing_under_zone_outage():
    """A zone outage kills several replicas in one tick; the exported bus
    stays non-decreasing in sim time and the batched requeues preserve
    arrival order within each instant."""
    cl, m = _run("zone")
    assert len(m.zone_outages) > 0
    ev = cl.tracer.events()
    ts = [e["t"] for e in ev]
    assert ts == sorted(ts)
    by_instant = {}
    for e in ev:
        if e["kind"] == "requeue":
            by_instant.setdefault(e["t"], []).append(e["arrival"])
    assert any(len(v) > 1 for v in by_instant.values()), \
        "zone outage produced no batched requeue instant"
    for arrivals in by_instant.values():
        assert arrivals == sorted(arrivals)


# ---------------- attribution + predictor ----------------

def test_attribution_populated_under_overload():
    cl, m = _run("steady", wl=dict(qps=90.0, duration=10.0, seed=1))
    att = m.attribution
    assert att["requests"] == m.completed + m.dropped
    assert att["missed"] + att["dropped"] > 0
    assert sum(att["dominant"].values()) == att["missed"] + att["dropped"]
    assert set(att["dominant"]) <= set(COMPONENTS)
    assert att["violation_time_by_component"]


def test_predictor_calibration_populated():
    cl, m = _run("crash")
    p = m.predictor
    assert p["n"] > 0
    assert p["mae"] > 0 and p["mae"] >= abs(p["bias"])
    assert p["p95_abs_err"] > 0
    assert isinstance(p["drift"], bool)
    assert p["rolling_window"] <= TraceConfig().predictor_window
    # summary() carries both blocks when tracing is on
    s = m.summary()
    assert s["attribution"]["requests"] == s["completed"] + s["dropped"]
    assert s["predictor"]["n"] == p["n"]
    assert s["trace_events"] > 0


# ---------------- exporters ----------------

def test_chrome_trace_structure(tmp_path):
    cl, m = _run("zone")
    path = tmp_path / "chrome.json"
    n = cl.tracer.write_chrome_trace(path)
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert n == len(evs) > 0
    assert all(e["ph"] in "MXi" for e in evs)
    threads = {(e["pid"], e["args"]["name"]) for e in evs
               if e.get("name") == "thread_name"}
    # every replica got its own named track, spread over >1 zone process
    assert len({name for _, name in threads if name.startswith("replica-")}) \
        >= 4
    assert len({pid for pid, _ in threads}) >= 2
    assert any(e["ph"] == "i" and e["name"] == "zone_outage" for e in evs)
    assert all(e["ts"] >= 0 for e in evs if e["ph"] != "M")
    assert all(e["dur"] >= 0 for e in evs if e["ph"] == "X")


def _load_trace_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report",
        Path(__file__).resolve().parent.parent / "scripts/trace_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_jsonl_roundtrip_matches_live_attribution(tmp_path):
    """scripts/trace_report.py recomputes the attribution histogram from
    the JSONL span records alone and must agree with the live tracer."""
    cl, m = _run("crash")
    path = tmp_path / "trace.jsonl"
    n = cl.tracer.write_jsonl(path)
    assert n == sum(1 for _ in open(path))
    tr = _load_trace_report()
    meta, events, spans = tr.load_records(path)
    assert meta["spans"] == len(spans) == len(cl.tracer.finished)
    assert meta["events"] == len(events)
    offline = tr.attribution_from_spans(spans)
    live = cl.tracer.attribution_summary()
    for k in ("requests", "completed_ok", "missed", "dropped", "dominant"):
        assert offline[k] == live[k], k
    for comp, t in live["violation_time_by_component"].items():
        assert offline["violation_time_by_component"][comp] == \
            pytest.approx(t, abs=1e-3)
    p = tr.predictor_stats(spans)
    assert p["n"] == cl.tracer.predictor_summary()["n"]


def test_summary_full_timeseries_opt_in():
    """The default summary reduces the queue time series to stats but now
    says how many samples that dropped; full_timeseries=True recovers
    them all."""
    cl, m = _run("steady")
    s = m.summary()
    assert "queue_timeseries" not in s
    assert s["queue_ts_points_dropped"] == len(m.queue_ts) > 0
    full = m.summary(full_timeseries=True)
    assert full["queue_ts_points_dropped"] == 0
    rows = full["queue_timeseries"]
    assert len(rows) == len(m.queue_ts)
    assert all(len(r) == 4 for r in rows)


def test_retention_modes():
    """Sampling bounds the retained per-request events, never the spans:
    attribution covers every request in all three modes."""
    runs = {mode: _run("crash", trace=TraceConfig(mode=mode, seed=7))
            for mode in ("all", "violations", "sample")}
    spans = {mode: len(cl.tracer.finished) for mode, (cl, _) in runs.items()}
    assert len(set(spans.values())) == 1      # same requests either way
    atts = [cl.tracer.attribution_summary() for cl, _ in runs.values()]
    assert atts[0] == atts[1] == atts[2]
    n_all = runs["all"][0].tracer.n_events
    n_viol = runs["violations"][0].tracer.n_events
    n_samp = runs["sample"][0].tracer.n_events
    assert n_viol < n_all and n_samp < n_all
    viol_cl = runs["violations"][0]
    viol_rids = {e["rid"] for e in viol_cl.tracer.events()
                 if e["kind"] == "submit"}
    live = viol_cl.tracer.attribution_summary()
    assert len(viol_rids) <= live["missed"] + live["dropped"]


# ---------------- warm-boot elastic fleet ----------------

def test_conservation_warmboot_elastic_fleet():
    """The 1e-9 decomposition conservation extends to the elastic
    warm-boot fleet: spawn prefetch overlaps boot (no span is open on a
    booting replica, so the transfer charges no component and leaks no
    tier_wait), while the size-dependent fetches replicas pay mid-request
    still land in tier_wait — and the prefetches surface as fleet
    ``tier_prefetch`` events."""
    from benchmarks.common import make_cluster
    from repro.cluster.simtools import (flash_crowd_workload,
                                        warmboot_cluster_kwargs)
    cl = make_cluster(**warmboot_cluster_kwargs("warm"),
                      trace=TraceConfig(), record_timeseries=False)
    m = cl.run(flash_crowd_workload(seed=1))
    n = _assert_conserved(cl)
    assert n == m.completed + m.dropped
    assert _component_totals(cl)["tier_wait"] > 0
    pf = [e for e in cl.tracer.events() if e["kind"] == "tier_prefetch"]
    assert pf, "no tier_prefetch events despite prefetch_on_spawn"
    for e in pf:
        assert e["keys"] > 0 and e["nbytes"] > 0
        assert e["transfer"] > 0 and e["ready_at"] >= e["t"]
    assert m.cache_tier["tier"]["prefetches"] > 0


@pytest.mark.parametrize("mode", ("all", "violations", "sample"))
def test_summary_and_jsonl_agree_on_event_counts(mode, tmp_path):
    """``summary()`` and the JSONL exporter must report the same retained
    event count in every retention mode: the shutdown-drain tier commits
    are emitted before the summary snapshots the tracer counters, so
    nothing lands on disk that the summary never counted."""
    cl, m = _tier_cluster(trace=TraceConfig(mode=mode, seed=7))
    # the snapshot took every event the tracer will ever hold
    assert m.trace_events == cl.tracer.n_events
    path = tmp_path / "trace.jsonl"
    cl.tracer.write_jsonl(path)
    tr = _load_trace_report()
    meta, events, spans = tr.load_records(path)
    assert m.summary()["trace_events"] == meta["events"] == len(events)
    assert m.summary(full_timeseries=True)["trace_events"] == meta["events"]
    if mode == "all":      # bulk events are retained only in "all"
        assert any(e["kind"] == "tier_commit" for e in events)


# ---------------- event count ----------------

def test_sim_events_always_recorded():
    """The event-loop count of ``Cluster.run`` is recorded even with
    tracing off."""
    _, m = _run("steady", trace=None)
    assert m.sim_events > 0
    assert m.summary()["sim_events"] == m.sim_events


# ---------------- tracer unit edges ----------------

def test_null_tracer_is_inert():
    nt = NullTracer()
    assert nt.enabled is False
    assert nt.submit(None) is None            # any method, any args
    assert nt.anything(1, 2, k=3) is None
    # dunder probes (copy, pickle, numpy) must miss, not return the no-op;
    # object itself defines __getstate__ since Python 3.11, so probe one
    # that only __getattr__ could answer
    with pytest.raises(AttributeError):
        nt.__deepcopy__


def test_trace_config_validation():
    with pytest.raises(ValueError):
        TraceConfig(mode="everything")
    with pytest.raises(ValueError):
        TraceConfig(sample_rate=1.5)
    with pytest.raises(ValueError):
        TraceConfig(sample_rate=0.0)
    t = Tracer(TraceConfig(mode="sample", sample_rate=1.0))
    assert t.cfg.sample_rate == 1.0


# ---------------- exporter edge cases ----------------

def test_export_zero_event_run(tmp_path):
    """A traced cluster that never saw a request still exports a valid,
    self-describing trace: one meta header, no events, no spans — and
    the offline report loads it without blowing up."""
    cl = _build(trace=TraceConfig())
    cl.run([])
    path = tmp_path / "empty.jsonl"
    n = cl.tracer.write_jsonl(path)
    # meta header + the initial replica_spawn fleet events, nothing else
    assert n == 1 + cl.cfg.n_replicas
    chrome = tmp_path / "empty_chrome.json"
    assert cl.tracer.write_chrome_trace(chrome) >= 0
    json.loads(chrome.read_text())             # still valid JSON
    tr = _load_trace_report()
    meta, events, spans = tr.load_records(path)
    assert meta["spans"] == 0 and spans == []
    assert all(e["kind"] == "replica_spawn" for e in events)
    att = tr.attribution_from_spans(spans)
    assert att["requests"] == att["missed"] == att["dropped"] == 0
    assert att["dominant"] == {}
    assert tr.predictor_stats(spans) == {"n": 0}


def test_violations_mode_without_violations(tmp_path):
    """violations retention on a run where every request makes its SLO:
    nothing per-request survives to disk (no events, no spans — there is
    nothing to debug), the export stays valid, and the live tracer still
    attributes over every request in memory."""
    cl = _build(trace=TraceConfig(mode="violations"))
    m = cl.run(cluster_workload(qps=6.0, duration=8.0, slo_scale=50.0,
                                seed=11))
    assert m.completed > 0 and m.dropped == 0
    assert m.slo_satisfaction == 1.0
    # retained bus events are fleet-level only (no rid)
    assert all(e.get("rid") is None for e in cl.tracer.events())
    # in memory: spans for every request, attribution finds no violations
    assert len(cl.tracer.finished) == m.completed
    live = cl.tracer.attribution_summary()
    assert live["missed"] == live["dropped"] == 0
    assert live["completed_ok"] == m.completed
    path = tmp_path / "clean.jsonl"
    cl.tracer.write_jsonl(path)
    tr = _load_trace_report()
    meta, events, spans = tr.load_records(path)
    assert meta["spans"] == 0 == len(spans)    # only violators export
    assert all(e.get("rid") is None for e in events)
    att = tr.attribution_from_spans(spans)
    assert att["requests"] == 0
    assert att["violation_time_by_component"] == {}


def test_attribution_uses_header_component_list(tmp_path):
    """The offline report keys the violation-time table off the trace's
    own ``trace_meta`` component list, so a trace written by a different
    code version reports under *its* schema; only header-less traces
    fall back to the live import."""
    cl, _ = _run("crash")
    path = tmp_path / "trace.jsonl"
    cl.tracer.write_jsonl(path)
    tr = _load_trace_report()
    meta, _, spans = tr.load_records(path)
    from repro.cluster.trace import COMPONENTS
    assert meta["components"] == list(COMPONENTS)
    # a future tracer with an extra component: the table gains the key
    future = meta["components"] + ["quantum_wait"]
    att = tr.attribution_from_spans(spans, future)
    assert att == tr.attribution_from_spans(spans)  # zero-time keys drop
    # fallback path (no header) matches the live list exactly
    assert tr._live_components() == list(COMPONENTS)
