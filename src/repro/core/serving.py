"""PatchedServe engine — request lifecycle, 3 stages, patch batching, cache,
SLO scheduling (paper Fig. 2).

``PatchedServeEngine`` runs the diffusion model on the device and times each
step on the wall clock. Its request loop (``ServingLoop``: admission, step
accounting, straggler rule, retirement) is shared with engines that price a
step instead of running it, such as the cluster layer's accounting engine.

Per engine iteration (continuous batching at step granularity, no
preemption):
  1. move arrivals into the wait queue; run Algorithm 1 to admit;
  2. Preparation for newly admitted (noise init + prompt-embedding stub);
  3. build the CSP batch from every active request's current latent
     (patch = GCD of active resolutions), run ONE denoising step for all —
     requests at different step indices batch together (Fig. 1);
  4. patch-level cache reuse around every block (optional);
  5. finished requests -> Postprocessing (VAE decode stub), record SLO;
  6. straggler mitigation: if a step ran > straggler_factor x predicted,
     re-estimate active requests and drop newly-hopeless ones.

The engine is **steppable**: an external driver owns the clock by calling
``submit(req)`` and ``tick(now)`` — one engine iteration that returns a
``TickEvents`` record — while ``run()`` is a thin single-engine wrapper around
the same loop.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cache as cache_mod
from repro.core.cache_predictor import ThresholdPredictor
from repro.core.csp import gcd_patch_size
from repro.core.latency_model import (analytic_step_latency, make_features,
                                      resolution_concentration)
from repro.core.patching import merge_by_request, split
from repro.core.requests import Request
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.models import diffusion as dm
from repro.models import sampler as sampler_mod
from repro.models import vae as vae_mod

# Host spans the engine records while a profiler session is open
# (``jax.profiler.TraceAnnotation``; outside a session they record nothing):
# the iteration, Algorithm 1, CSP split, the sampler step, the cache hook's
# own work, a block's dispatch (also on the plain path, in
# ``diffusion.denoise_patched``), CSP merge, the wait on the step's latents,
# and the decode.
SPANS = ("ps.tick", "ps.schedule", "ps.csp_split", "ps.sampler", "ps.cache",
         "ps.block", "ps.csp_merge", "ps.wait", "ps.decode")


@dataclass
class EngineConfig:
    clock: str = "real"                 # only "real" is accepted
    use_cache: bool = False
    cache_tau: float = 5e-3
    cache_capacity: int = 8192
    patch_cap: int = 0                  # 0 = pure GCD (paper default)
    straggler_factor: float = 3.0
    # Composition bucketing (docs/ARCHITECTURE.md §4): per-resolution counts
    # are padded up to this ladder with dummy requests so XLA compiles a
    # small bounded program set. The padding overhead is charged honestly to
    # the latency predictor (a request that fits the current bucket is free).
    bucket_ladder: Tuple[int, ...] = (0, 1, 2, 4, 6, 8, 12)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    seed: int = 0


@dataclass
class Metrics:
    completed: int = 0
    dropped: int = 0
    slo_met: int = 0
    latencies: List[float] = field(default_factory=list)
    step_latencies: List[float] = field(default_factory=list)
    # _predict_step_latency of the batch each step ran; aligns with
    # step_latencies
    step_predicted: List[float] = field(default_factory=list)
    compute_savings: List[float] = field(default_factory=list)
    # blocking waits on the device: device->host reads and
    # block_until_ready, all through PatchedServeEngine._host_sync
    host_syncs: int = 0
    # traces (compiles) of the model step's compiled segments
    # (``diffusion.SEGMENT_TRACES``) during this engine's steps; it stops
    # rising once every batch composition has been seen
    block_traces: int = 0
    # per-step (resolution concentration, step fraction, cache hit rate)
    # triples — the calibration feed for fit_cache_hit_model
    cache_samples: List[Tuple[float, float, float]] = field(
        default_factory=list)
    span: float = 0.0

    @property
    def slo_satisfaction(self) -> float:
        total = self.completed + self.dropped
        return self.slo_met / total if total else 1.0

    @property
    def goodput(self) -> float:
        return self.slo_met / self.span if self.span else 0.0


@dataclass
class TickEvents:
    """What one engine iteration did — the steppable-API return value."""
    now: float                                   # clock at tick start
    admitted: List[Request] = field(default_factory=list)
    dropped: List[Request] = field(default_factory=list)
    completed: List[Request] = field(default_factory=list)
    dt: float = 0.0                              # step duration (0 if idle)
    stepped: bool = False

    @property
    def end(self) -> float:
        return self.now + self.dt


class ServingLoop:
    """The steppable request loop: Algorithm-1 admission, one step for the
    whole active batch, the straggler rule and retirement. A subclass gives
    the stages — ``_prepare(req)``, ``_timed_step(reqs)`` (the step's cache
    savings and its duration) and ``_postprocess(req)`` — and the clock that
    ``drain`` and ``run`` advance."""

    def __init__(self, engine_cfg: EngineConfig,
                 standalone_latency: Dict[Tuple[int, int], float],
                 resolutions: Sequence[Tuple[int, int]]):
        self.cfg = engine_cfg
        self.resolutions = [tuple(r) for r in resolutions]
        self.sa = standalone_latency
        base_patch = gcd_patch_size(self.resolutions, cap=engine_cfg.patch_cap)
        self.patch = base_patch
        self.patches_per_res = [
            (h // base_patch) * (w // base_patch) for h, w in self.resolutions]
        self.scheduler = Scheduler(engine_cfg.scheduler, base_patch,
                                   standalone_latency,
                                   self._predict_step_latency)
        # step-latency surrogate; None prices steps with analytic_step_latency
        self.latency_model = None
        # steppable state (owned here so an external driver can interleave
        # many engines; run() resets metrics but keeps compile/shape caches)
        self.wait: List[Request] = []
        self.active: List[Request] = []
        self.metrics = Metrics()
        self._seen_shapes: set = set()

    # ---------------- latency prediction ----------------

    def _counts(self, reqs: List[Request]) -> List[int]:
        return [sum(1 for r in reqs if r.resolution == res)
                for res in self.resolutions]

    def _bucket(self, n: int) -> int:
        for b in self.cfg.bucket_ladder:
            if n <= b:
                return b
        return n

    def _predict_step_latency(self, reqs: List[Request]) -> float:
        if not reqs:
            return 0.0
        # predict for the *bucketed* composition — what actually executes
        counts = [self._bucket(c) for c in self._counts(reqs)]
        if self.latency_model is None:
            return analytic_step_latency(counts, self.patches_per_res)
        return max(self.latency_model.predict(
            make_features(counts, self.patches_per_res)), 1e-5)

    # ---------------- steppable API ----------------

    def submit(self, req: Request) -> None:
        """Enqueue an arrived request; it is considered by Algorithm 1 on the
        next ``tick``."""
        self.wait.append(req)

    @property
    def has_work(self) -> bool:
        return bool(self.wait or self.active)

    @property
    def queue_depth(self) -> int:
        return len(self.wait) + len(self.active)

    def backlog_estimate(self) -> float:
        """Predicted seconds until this engine drains everything it holds,
        assuming all of it batches together (upper-bounds composition; the
        router only needs a comparable load signal, not an exact forecast)."""
        reqs = self.active + self.wait
        if not reqs:
            return 0.0
        step = self._predict_step_latency(reqs)
        return step * max(r.remaining_steps for r in reqs)

    def reset_metrics(self) -> None:
        """Fresh Metrics; keeps compile/shape caches so warm engines stay
        warm across runs."""
        self.metrics = Metrics()

    def _start_run(self, workload: List[Request]) -> List[Request]:
        """Empty queues and fresh metrics for a self-contained ``run``: drops
        anything a previous max_wall-truncated run (or external submit/tick
        use) left queued. Returns the workload in arrival order."""
        self.wait.clear()
        self.active.clear()
        self.reset_metrics()
        return sorted(workload, key=lambda r: r.arrival)

    def tick(self, now: float) -> TickEvents:
        """One engine iteration at clock time ``now``: admit via Algorithm 1,
        run one denoising step for the active batch, retire completions.
        The caller owns the clock and should advance it by ``events.dt``."""
        with jax.profiler.TraceAnnotation("ps.tick"):
            return self._tick(now)

    def _tick(self, now: float) -> TickEvents:
        ev = TickEvents(now=now)
        m = self.metrics

        with jax.profiler.TraceAnnotation("ps.schedule"):
            admitted, dropped = self.scheduler.schedule(self.wait,
                                                        self.active, now)
        for r in dropped:
            self.wait.remove(r)
            r.state = "dropped"
            m.dropped += 1
            ev.dropped.append(r)
        for r in admitted:
            self.wait.remove(r)
            r.state = "active"
            self._prepare(r)
            self.active.append(r)
            ev.admitted.append(r)
        if not self.active:
            return ev

        # one denoising step for the whole mixed-resolution batch
        step_pred = self._predict_step_latency(self.active)
        comp = tuple(self._bucket(c) for c in self._counts(self.active))
        is_cold = comp not in self._seen_shapes
        self._seen_shapes.add(comp)
        # batch locality features for the cache calibrator, captured before
        # steps_done advances: concentration of the *bucketed* composition
        # (what executes, dummy padding included)
        conc = step_frac = 0.0
        if self.cfg.use_cache:
            conc = resolution_concentration(comp, self.patches_per_res)
            step_frac = float(np.mean([r.steps_done / max(r.total_steps, 1)
                                       for r in self.active]))
        savings, ev.dt = self._timed_step(self.active)
        if savings:
            # measured tensor-path reuse: also feed the hit-model calibrator
            m.compute_savings.append(float(np.mean(savings)))
            m.cache_samples.append((conc, step_frac, float(np.mean(savings))))
        ev.stepped = True
        m.step_latencies.append(ev.dt)
        m.step_predicted.append(step_pred)
        end = ev.end

        # straggler mitigation: a step far over prediction triggers
        # re-estimation; newly hopeless actives are dropped at once.
        # Cold (first-compile) compositions are exempt.
        if (not is_cold
                and ev.dt > self.cfg.straggler_factor * max(step_pred, 1e-9)):
            for r in list(self.active):
                if end + ev.dt * r.remaining_steps > r.slo:
                    self.active.remove(r)
                    r.state = "dropped"
                    m.dropped += 1
                    ev.dropped.append(r)

        # completions
        for r in list(self.active):
            if r.steps_done >= r.total_steps:
                self.active.remove(r)
                self._postprocess(r)
                r.state = "done"
                r.finish = end
                m.completed += 1
                m.latencies.append(end - r.arrival)
                if end <= r.slo:
                    m.slo_met += 1
                ev.completed.append(r)
        return ev


class PatchedServeEngine(ServingLoop):
    """Runs each step's denoising on the device and times it on the wall
    clock; ``drain`` and ``run`` advance by the wall clock too."""

    def __init__(self, model_cfg: dm.DiffusionConfig, params,
                 engine_cfg: EngineConfig,
                 standalone_latency: Dict[Tuple[int, int], float],
                 resolutions: Sequence[Tuple[int, int]]):
        if engine_cfg.clock != "real":
            raise ValueError("PatchedServeEngine runs on the wall clock: "
                             f"clock must be 'real', got {engine_cfg.clock!r}")
        super().__init__(engine_cfg, standalone_latency, resolutions)
        self.mcfg = model_cfg
        self.params = params
        self.vae = vae_mod.init_vae(jax.random.PRNGKey(7),
                                    model_cfg.latent_channels)
        self.rng = np.random.default_rng(engine_cfg.seed)
        self.caches: Dict[str, cache_mod.PatchCache] = {}
        self.predictor = ThresholdPredictor(engine_cfg.cache_tau)
        self._uid_base: Dict[int, int] = {}   # rid -> uid namespace
        self.outputs: Dict[int, np.ndarray] = {}

    # ---------------- calibration (paper §6.1 Throughput Analyzer) ----------

    def calibrate(self, steps_per_probe: int = 2,
                  combos: Optional[List[List[int]]] = None,
                  total_steps_hint: int = 50) -> Dict:
        """Measure real step latencies for probe compositions, fit a linear
        latency model (lat ~ a + b*patches + c*distinct + per-res terms), warm
        the JIT cache, and set standalone latencies. Returns the fit info."""
        if combos is None:
            eye = [[1 if i == j else 0 for j in range(len(self.resolutions))]
                   for i in range(len(self.resolutions))]
            combos = eye + [[1] * len(self.resolutions)] \
                + [[2 if i == j else 0 for j in range(len(self.resolutions))]
                   for i in range(len(self.resolutions))]
        feats, lats = [], []
        for counts in combos:
            reqs = []
            rid = 10_000_000
            for res, c in zip(self.resolutions, counts):
                for _ in range(c):
                    r = Request(rid=rid, resolution=res, arrival=0.0,
                                slo=1e9, total_steps=steps_per_probe)
                    self._prepare(r)
                    reqs.append(r)
                    rid += 1
            if not reqs:
                continue
            lat = None
            for s in range(steps_per_probe):
                _, lat = self._timed_step(reqs)   # keep last (warm) step
            feats.append(np.concatenate([
                np.asarray(counts, np.float64),
                [float(np.sum(np.asarray(counts) > 0)),
                 float(np.sum(np.asarray(counts) * self.patches_per_res))]]))
            lats.append(lat)
        X = np.stack(feats)
        X1 = np.concatenate([X, np.ones((len(X), 1))], axis=1)
        y = np.asarray(lats)
        coef, *_ = np.linalg.lstsq(X1, y, rcond=None)
        self._lin_coef = coef

        class _Lin:
            def __init__(self, coef):
                self.coef = coef

            def predict(self, f):
                f1 = np.concatenate([np.asarray(f, np.float64), [1.0]])
                return float(np.maximum(f1 @ self.coef, 1e-5))

        self.latency_model = _Lin(coef)
        # standalone FULL-request latency per resolution (slack normalizer)
        for i, res in enumerate(self.resolutions):
            f = make_features([1 if j == i else 0
                               for j in range(len(self.resolutions))],
                              self.patches_per_res)
            self.sa[res] = self.latency_model.predict(f) * total_steps_hint
        return {"coef": coef, "probe_latencies": lats}

    # ---------------- stages ----------------

    def _prepare(self, req: Request) -> None:
        self._uid_base[req.rid] = req.rid * (1 << 20)
        if req.latent is None:
            # fresh request; a checkpoint-resumed one arrives with its
            # snapshotted latent and must NOT be re-noised — it continues
            # mid-denoise from the restored state
            h, w = req.resolution
            req.latent = jnp.asarray(
                self.rng.normal(size=(h, w, self.mcfg.latent_channels)),
                jnp.float32)
        if req.text is None:
            req.text = vae_mod.encode_prompt(req.prompt, self.mcfg.n_text,
                                             self.mcfg.d_text)

    def _postprocess(self, req: Request) -> None:
        with jax.profiler.TraceAnnotation("ps.decode"):
            img = vae_mod.vae_decode(self.vae, req.latent[None])[0]
            self.outputs[req.rid] = self._host_sync(np.asarray, img)

    def _host_sync(self, read, x):
        """``read(x)``, a call that blocks on the device (``np.asarray``,
        ``jax.block_until_ready``), counted in ``Metrics.host_syncs``."""
        self.metrics.host_syncs += 1
        return read(x)

    # ---------------- cache plumbing ----------------

    def _block_hook(self, csp, step_frac):
        """Patch-level cache reuse (paper Fig. 10) wired around each block."""
        # uid = request namespace + patch grid position: stable across engine
        # iterations regardless of batch composition
        uids_per_patch = np.array(
            [self._uid_base[int(csp.req_ids[csp.patch_req[j]])]
             + int(csp.patch_rc[j, 0]) * 4096 + int(csp.patch_rc[j, 1])
             for j in range(csp.total)], np.int64)
        savings = []

        def cached_block(name, kind, fn, x):
            key = f"{name}:{tuple(x.shape[1:])}"
            c = self.caches.get(key)
            if c is None:
                c = cache_mod.PatchCache(self.cfg.cache_capacity)
                self.caches[key] = c
            sync = c.sync(uids_per_patch.tolist())
            mask = self._host_sync(np.asarray,
                                   c.reuse_mask(x, sync, self.predictor))
            if mask.all():
                y = c.cached_outputs(sync)
            else:
                if mask.any():
                    # context blocks: fill masked inputs with the cached
                    # inputs from the previous step (paper §5.1), run dense,
                    # then restore cached outputs for masked patches.
                    x_in = jnp.where(
                        jnp.asarray(mask).reshape((-1,) + (1,) * (x.ndim - 1)),
                        c.cached_inputs(sync).astype(x.dtype), x)
                else:
                    x_in = x
                with jax.profiler.TraceAnnotation("ps.block"):
                    y_full = fn(x_in)
                if mask.any():
                    y = jnp.where(
                        jnp.asarray(mask).reshape(
                            (-1,) + (1,) * (y_full.ndim - 1)),
                        c.cached_outputs(sync).astype(y_full.dtype), y_full)
                else:
                    y = y_full
            c.update(sync, x, y, jnp.asarray(~mask))
            savings.append(float(mask.mean()))
            return y

        def hook(name, kind, fn, x):
            with jax.profiler.TraceAnnotation("ps.cache"):
                return cached_block(name, kind, fn, x)

        return hook, savings

    # ---------------- clock ----------------

    def drain(self, now: float = 0.0,
              max_wall: float = 1e9) -> Tuple[float, List[TickEvents]]:
        """Tick until both queues are empty (or no progress is possible).
        Returns the clock time at idle and the event trail."""
        t0 = time.perf_counter()
        start_now = now
        events: List[TickEvents] = []
        while self.has_work:
            ev = self.tick(now)
            events.append(ev)
            now = start_now + (time.perf_counter() - t0)
            if not (ev.stepped or ev.admitted or ev.dropped):
                break                      # starved: nothing admissible
            if time.perf_counter() - t0 > max_wall:
                break
        return now, events

    def run(self, workload: List[Request], max_wall: float = 1e9) -> Metrics:
        """Serve ``workload`` with arrivals released on the wall clock."""
        pending = self._start_run(workload)
        m = self.metrics
        t_start = time.perf_counter()
        while pending or self.has_work:
            t = time.perf_counter() - t_start
            while pending and pending[0].arrival <= t:
                self.submit(pending.pop(0))
            if not self.has_work:
                if pending:
                    time.sleep(max(pending[0].arrival - t, 0))
                continue
            self.tick(t)
            if time.perf_counter() - t_start > max_wall:
                break
        m.span = time.perf_counter() - t_start
        return m

    # ---------------- device step ----------------

    DUMMY_BASE = 1 << 40

    def _dummy(self, res: Tuple[int, int], slot: int) -> Request:
        key = (res, slot)
        pool = getattr(self, "_dummy_pool", None)
        if pool is None:
            pool = self._dummy_pool = {}
        r = pool.get(key)
        if r is None:
            h, w = res
            r = Request(rid=self.DUMMY_BASE + hash(key) % (1 << 30),
                        resolution=res, arrival=0.0, slo=1e18, total_steps=1)
            r.latent = jnp.zeros((h, w, self.mcfg.latent_channels), jnp.float32)
            r.text = jnp.zeros((self.mcfg.n_text, self.mcfg.d_text), jnp.float32)
            self._uid_base[r.rid] = r.rid * (1 << 20) % (1 << 62)
            pool[key] = r
        return r


    def _timed_step(self, reqs: List[Request]) -> Tuple[List[float], float]:
        """One denoising step and its wall time. Dispatch is asynchronous,
        so the clock stops only once the new latents exist on the device."""
        t0 = time.perf_counter()
        savings = self._denoise_step(reqs)
        with jax.profiler.TraceAnnotation("ps.wait"):
            self._host_sync(jax.block_until_ready, [r.latent for r in reqs])
        return savings, time.perf_counter() - t0

    def _denoise_step(self, active: List[Request]) -> List[float]:
        with jax.profiler.TraceAnnotation("ps.csp_split"):
            # bucket-pad per resolution so XLA sees a bounded shape lattice
            padded = list(active)
            for res, c in zip(self.resolutions, self._counts(active)):
                for j in range(self._bucket(c) - c):
                    padded.append(self._dummy(tuple(res), j))
            csp, patches = split([r.latent for r in padded],
                                 patch=self.patch,
                                 req_ids=[r.rid for r in padded])
        by_rid = {r.rid: r for r in padded}
        step_req = jnp.asarray([by_rid[int(rid)].steps_done
                                for rid in csp.req_ids], jnp.int32)
        text = jnp.stack([by_rid[int(rid)].text for rid in csp.req_ids])
        total_steps = active[0].total_steps

        savings: List[float] = []
        hook = None
        if self.cfg.use_cache:
            frac = float(np.mean([r.steps_done for r in active])) / total_steps
            hook, savings = self._block_hook(csp, frac)

        traces = dm.segment_traces()
        with jax.profiler.TraceAnnotation("ps.sampler"):
            new_patches = sampler_mod.sampler_step(
                self.mcfg, self.params, csp, patches, step_req,
                total_steps, text, block_hook=hook)
        self.metrics.block_traces += dm.segment_traces() - traces
        with jax.profiler.TraceAnnotation("ps.csp_merge"):
            outs = merge_by_request(csp, new_patches)
            for r in active:            # dummies' outputs are discarded
                r.latent = outs[r.rid]
                r.steps_done += 1
        return savings
