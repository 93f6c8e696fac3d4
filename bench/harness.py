"""One run of one benchmark cell on the served path.

Set-up makes the weights on the device from the configuration's
`weights_seed`, builds the engine, fits its latency model on the cell's own
batch compositions (which compiles and warms them), drives each branch of
the patch cache's hook once, and warms the decoder. The traffic kind's module
(`bench/traffic/<kind>.py`) then runs a lead-in that fills the batch and the
window, which drives `PatchedServeEngine.submit` and `tick` on the real
clock. Afterwards a sample of the requests finished in the window, drawn
from the seed, is served again by the plain reference (`bench/reference.py`)
and compared.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import time
from functools import partial
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, weights

ROOT = Path(__file__).resolve().parents[1]
TRAFFIC_DIR = ROOT / "bench" / "traffic"
SLO_NONE = 1e9          # closed-loop requests carry no deadline
# The program's float32 products run at the TPU's default matmul precision
# (one bfloat16 pass); the reference computes at the same precision.
REFERENCE_PRECISION = "default"


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """(cell, configuration dict, traffic mix dict) for a cell name."""
    bench = load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == name)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((TRAFFIC_DIR / f"{cell['traffic']}.json").read_text())
    return cell, cfg, mix


def traffic_kind(kind: str):
    """The module of a traffic kind, `bench/traffic/<kind>.py`."""
    return load_module(TRAFFIC_DIR / f"{kind}.py", f"bench_traffic_{kind}")


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per purpose, from any whole-number seed."""
    words = [int(seed) & 0xFFFFFFFF, int(seed) >> 32] + [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def gcd_patch(resolutions) -> int:
    g = 0
    for h, w in resolutions:
        g = math.gcd(g, math.gcd(h, w))
    return g


def ref_config(cfg: dict, res) -> dict:
    """The reference's view of a configuration at one resolution."""
    p = gcd_patch(cfg["resolutions"])
    return dict(cfg["model"], grid=(res[0] // p, res[1] // p))


@dataclasses.dataclass
class TickRecord:
    start: float
    end: float
    dt: float                 # the engine's own step time (0 if idle)
    request_steps: int        # real (non-padding) request-steps run
    composition: tuple        # real requests per resolution
    savings: Optional[float]  # mean reused share of patch-blocks this step


@dataclasses.dataclass
class Window:
    """What the per-layer readers and the end-to-end metrics read."""
    cfg: dict
    start: float = 0.0
    end: float = 0.0
    ticks: List[TickRecord] = dataclasses.field(default_factory=list)
    kernel_calls: Dict[str, list] = dataclasses.field(default_factory=dict)
    trace: Optional[dict] = None
    peaks: Optional[dict] = None
    setup_s: float = 0.0

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def request_steps(self) -> int:
        return sum(t.request_steps for t in self.ticks)


class Recorder:
    """Keeps the program's reuse deltas of every step (device arrays, no
    extra sync) and, in traced runs, puts host spans around each layer."""

    def __init__(self, eng, stack: contextlib.ExitStack, annotate: bool):
        self.steps: List[dict] = []
        self._cur = None
        self._block = None
        inner_pred = eng.predictor

        def predictor(delta):
            if self._cur is not None:
                self._cur["deltas"].append((self._block, delta))
            return inner_pred(delta)

        inner_hook = eng._block_hook

        def block_hook(csp, frac):
            hook, savings = inner_hook(csp, frac)
            rec = {"req_ids": [int(r) for r in csp.req_ids],
                   "offsets": np.asarray(csp.request_offset), "deltas": []}
            self._cur = rec
            self.steps.append(rec)

            def run(name, kind, fn, x):
                self._block = name
                if not annotate:
                    return hook(name, kind, fn, x)
                with jax.profiler.TraceAnnotation("cache_hook"):
                    return hook(name, kind, _annotated("block", fn), x)

            return run, savings

        stack.enter_context(_swap(eng, "predictor", predictor))
        stack.enter_context(_swap(eng, "_block_hook", block_hook))


def _annotated(name, fn):
    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


@contextlib.contextmanager
def _swap(obj, attr, value):
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, old)


def _trace_spans(eng, stack, window: Window, instruments):
    """Host spans around each layer's entry, for traced runs, and the
    metrics' own instruments: each `instrument()` gives (module, attribute,
    shape_of), and every call of that function records its shape under the
    metric's kernel name."""
    from repro.core import serving
    from repro.models import sampler

    for mod, attr, span in ((serving, "split", "csp_split"),
                            (serving, "merge_by_request", "csp_merge"),
                            (sampler, "sampler_step", "sampler_step")):
        stack.enter_context(_swap(mod, attr, _annotated(span,
                                                        getattr(mod, attr))))
    stack.enter_context(_swap(eng.scheduler, "schedule", _annotated(
        "schedule", eng.scheduler.schedule)))
    stack.enter_context(_swap(eng, "_postprocess", _annotated(
        "postprocess", eng._postprocess)))

    def counted(name, fn, shape_of):
        def wrapped(*a, **k):
            window.kernel_calls.setdefault(name, []).append(shape_of(a))
            return fn(*a, **k)
        return wrapped

    for name, instrument in instruments:
        mod, attr, shape_of = instrument()
        stack.enter_context(_swap(mod, attr, counted(
            name, getattr(mod, attr), shape_of)))


class CompileCounter:
    """Backend compiles (or persistent-cache loads) JAX reports while on."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.on = False
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, secs, **_):
        if self.on and event == self.EVENT:
            self.count += 1
            self.seconds += secs


def build_engine(cfg: dict, params, vae):
    from repro.core.scheduler import SchedulerConfig
    from repro.core.serving import EngineConfig, PatchedServeEngine
    from repro.models.diffusion import DiffusionConfig

    m = {k: tuple(v) if isinstance(v, list) else v
         for k, v in cfg["model"].items()}
    e = dict(cfg["engine"])
    e["bucket_ladder"] = tuple(e["bucket_ladder"])
    e["scheduler"] = SchedulerConfig(**e["scheduler"])
    eng = PatchedServeEngine(
        DiffusionConfig(name=cfg["name"], steps=cfg["steps"], **m), params,
        EngineConfig(clock="real", **e), {},
        [tuple(r) for r in cfg["resolutions"]])
    eng.vae = vae
    return eng


def _inputs_fn(cfg):
    """inputs(key, rid, (h, w)) -> (noise (h, w, C), prompt embedding): the
    request's own stream of the seed, made on the device."""
    m = cfg["model"]

    @partial(jax.jit, static_argnums=2)
    def make(key, rid, res):
        k1, k2 = jax.random.split(jax.random.fold_in(key, rid))
        noise = jax.random.normal(k1, (*res, m["latent_channels"]),
                                  jnp.float32)
        text = jax.random.normal(k2, (m["n_text"], m["d_text"]),
                                 jnp.float32) * 0.3
        return noise, text

    return lambda key, rid, res: make(key, jnp.uint32(rid), tuple(res))


class _Forced:
    """A reuse predictor that ignores the delta: none, alternate or all."""

    def __init__(self, mode):
        self.mode = mode

    def __call__(self, delta):
        n = delta.shape[0]
        if self.mode == "none":
            return jnp.zeros((n,), bool)
        if self.mode == "all":
            return jnp.ones((n,), bool)
        return jnp.arange(n) % 2 == 0


def warm_up(eng, cfg, compositions, inputs, key):
    """Compile and warm every program the window runs: the engine's own
    calibration over the cell's compositions, each branch of the cache hook
    on the fullest one, and the decoder at each resolution."""
    from repro.core.requests import Request

    steps = cfg["steps"]
    eng.calibrate(steps_per_probe=2, combos=[list(c) for c in compositions],
                  total_steps_hint=steps)
    res = [tuple(r) for r in cfg["resolutions"]]
    reqs, rid = [], 30_000_000
    for i, c in enumerate(compositions[-1]):
        for _ in range(c):
            r = Request(rid=rid, resolution=res[i], arrival=0.0, slo=SLO_NONE,
                        total_steps=steps)
            r.latent, r.text = inputs(key, rid, res[i])
            eng._prepare(r)
            reqs.append(r)
            rid += 1
    real = eng.predictor
    for mode in ("none", "half", "all", "none"):
        eng.predictor = _Forced(mode)
        eng._timed_step(reqs)
    eng.predictor = real
    for r in reqs:
        if r.resolution in res:
            eng._postprocess(r)
            res.remove(r.resolution)
    eng.outputs.clear()


class _Serve:
    """What a traffic module drives: sends requests and runs engine ticks on
    the real clock."""

    def __init__(self, eng, res, steps, inputs, key, win, annotate):
        self.eng, self.res, self.steps = eng, res, steps
        self.inputs, self.key, self.win = inputs, key, win
        self.annotate = annotate
        self.t0 = time.perf_counter()
        self.rid = 0
        self.live: Dict[int, object] = {}
        self.finished: Dict[int, dict] = {}

    def clock(self) -> float:
        return time.perf_counter() - self.t0

    def send(self, res_i: int):
        from repro.core.requests import Request
        rid = self.rid
        self.rid += 1
        now = self.clock()
        r = Request(rid=rid, resolution=self.res[res_i], arrival=now,
                    slo=now + SLO_NONE, total_steps=self.steps)
        r.latent, r.text = self.inputs(self.key, rid, self.res[res_i])
        self.live[rid] = r
        self.eng.submit(r)

    def tick(self, in_window: bool) -> List[int]:
        if in_window and self.annotate:
            with jax.profiler.TraceAnnotation("tick"):
                return self._tick(in_window)
        return self._tick(in_window)

    def _tick(self, in_window: bool) -> List[int]:
        eng, res = self.eng, self.res
        before = {rid: r.steps_done for rid, r in self.live.items()}
        n_sav = len(eng.metrics.compute_savings)
        start = self.clock()
        ev = eng.tick(start)
        end = self.clock()
        sav = eng.metrics.compute_savings[n_sav:]
        stepped = [r for r in self.live.values()     # completions still here
                   if r.steps_done > before.get(r.rid, r.steps_done)]
        if in_window:
            self.win.ticks.append(TickRecord(
                start, end, ev.dt, len(stepped),
                tuple(sum(1 for r in stepped if r.resolution == x)
                      for x in res),
                sav[0] if sav else None))
        done = []
        for r in ev.completed:
            del self.live[r.rid]
            self.finished[r.rid] = {"res": res.index(r.resolution),
                                    "latent": r.latent,
                                    "in_window": in_window}
            done.append(res.index(r.resolution))
        for r in ev.dropped:
            self.live.pop(r.rid, None)
            self.finished[r.rid] = {"res": res.index(r.resolution),
                                    "dropped": True, "in_window": in_window}
        return done


def run_cell(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
             trace: bool, t_process: float, log=print, control: bool = False,
             instruments=()) -> dict:
    """Run one cell once; returns the result line's dict (and extra keys
    prefixed with an underscore for the caller's diagnostics). With
    `control`, the plain reference in bfloat16 is put in the program's place
    for the check, which then decides `correct`. `instruments` are
    (kernel name, instrument) pairs from the per-layer metrics, applied in
    traced runs."""
    res = [tuple(r) for r in cfg["resolutions"]]
    steps = cfg["steps"]
    check = cfg["check"]
    traffic = traffic_kind(mix["kind"])
    # One checkpoint per configuration, as a deployment serves: the weights
    # come from the configuration's seed, the requests from the run's.
    k_in = weights.seed_key(seed)
    params, vae = weights.make_weights(
        cfg["model"], weights.seed_key(cfg["weights_seed"]))
    inputs = _inputs_fn(cfg)
    for r in res:                                   # warm the input maker
        inputs(k_in, 0, r)
    eng = build_engine(cfg, params, vae)
    comps = sorted({tuple(eng._bucket(c) for c in cs)
                    for cs in traffic.counts(mix, len(res), steps)})
    warm_up(eng, cfg, comps, inputs, k_in)

    win = Window(cfg=cfg)
    counter = CompileCounter()
    serve = _Serve(eng, res, steps, inputs, k_in, win, trace)
    opened = {}

    with contextlib.ExitStack() as stack:
        rec = Recorder(eng, stack, annotate=trace)

        def open_window():
            if trace:
                _trace_spans(eng, stack, win, instruments)
            jax.block_until_ready(params)
            opened["setup_s"] = time.perf_counter() - t_process
            if trace:
                import tempfile
                opened["trace_dir"] = tempfile.mkdtemp(prefix="bench_trace_")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(opened["trace_dir"],
                                         profiler_options=opts)
            counter.on = True
            win.start = serve.clock()

        # a traced window is one request's duration, every wave's requests
        # at every step once; an untraced one lasts `seconds`
        def stop(ticks):
            if trace:
                return ticks >= steps
            return serve.clock() - win.start >= seconds

        traffic.drive(serve, mix, len(res), steps, open_window, stop)
        win.end = serve.clock()
        counter.on = False
        if trace:
            jax.profiler.stop_trace()
    devices = jax.local_devices()[: cell.get("chips", 1)]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    finished = serve.finished
    done = [rid for rid, f in finished.items()
            if f["in_window"] and not f.get("dropped")]
    dropped = [rid for rid, f in finished.items()
               if f["in_window"] and f.get("dropped")]
    per_res = [sum(1 for rid in done if finished[rid]["res"] == i)
               for i in range(len(res))]
    log(f"window: {win.seconds:.3f} s, {len(win.ticks)} ticks, "
        f"{win.request_steps} request-steps, finished per resolution "
        f"{per_res}, dropped {len(dropped)}")
    log(f"compiles in window: {counter.count} ({counter.seconds:.3f} s)")
    log(f"peak_bytes_in_use: {peak}")

    # --- the check: a seeded sample of the finished requests -------------
    rng = rng_for(seed, "check")
    sample = []
    for i in range(len(res)):
        ids = sorted(rid for rid in done if finished[rid]["res"] == i)
        take = min(check["per_resolution"], len(ids))
        sample += [int(x) for x in rng.choice(ids, take, replace=False)]
    history: Dict[int, List[dict]] = {}
    for st in rec.steps:
        for rid in st["req_ids"]:
            history.setdefault(rid, []).append(st)
    served = {}
    for rid in sample:
        r = res[finished[rid]["res"]]
        served[rid] = {"res": r,
                       "latent": np.asarray(finished[rid]["latent"]),
                       "image": eng.outputs[rid],
                       "deltas": _program_deltas(history.get(rid, []), rid,
                                                 ref_config(cfg, r)["grid"])}
    eng.caches.clear()
    eng.outputs.clear()
    del eng, rec, serve
    gc.collect()
    checks = compare(cfg, params, vae, k_in, inputs, served, log=log)
    if control:
        log("above: the program; below: the bfloat16 control in its place")
        served = control_serve(cfg, params, vae, k_in, inputs, served)
        checks = compare(cfg, params, vae, k_in, inputs, served, log=log,
                         label="control")
    ok = all(c["value"] <= c["limit"] for c in checks.values()) \
        and len(sample) == len(res) * check["per_resolution"]
    return {"correct": bool(ok), "attempted": len(done) + len(dropped),
            "failed": len(dropped),
            "_window": win, "_setup_s": opened["setup_s"], "_peak": peak,
            "_checks": checks, "_trace_dir": opened.get("trace_dir"),
            "_compiles": counter.count}


def _program_deltas(steps_seen: List[dict], rid: int, grid):
    """The program's reuse deltas for one request, step by step:
    [{block: (gh, gw)}]; a block absent from a step had no cached entry."""
    n = grid[0] * grid[1]
    out = []
    for st in steps_seen:
        off = int(st["offsets"][st["req_ids"].index(rid)])
        out.append({name: np.asarray(delta)[off:off + n].reshape(grid)
                    for name, delta in st["deltas"]})
    return out


def _decide(deltas, names, grid, tau):
    """Reuse decisions from deltas; a block not tested (no cached entry
    yet) was computed."""
    return [{n: jnp.asarray(d[n] < tau) if n in d else jnp.zeros(grid, bool)
             for n in names} for d in deltas]


def _rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf")
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def control_serve(cfg, params, vae, k_in, inputs, served) -> dict:
    """The plain reference in bfloat16 in the program's place: each sampled
    request served from its noise with the control's own stores and reuse
    decisions, and its last latent decoded."""
    steps, tau = cfg["steps"], cfg["engine"]["cache_tau"]
    p16, v16 = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                                      (params, vae))
    out = {}
    for rid, s in served.items():
        rcfg = ref_config(cfg, s["res"])
        noise, text = inputs(k_in, rid, s["res"])
        names = list(reference.empty_stores(rcfg, p16, noise[None]))
        own = [{n: jnp.zeros(rcfg["grid"], bool) for n in names}] * steps
        lats, stats = reference.serve_steps(rcfg, steps, tau, 0.0, p16, text,
                                            noise, own, REFERENCE_PRECISION)
        out[rid] = {"res": s["res"], "latent": lats[-1],
                    "image": reference.decode(v16, lats[-1],
                                              REFERENCE_PRECISION),
                    "deltas": [{} if k == 0 else dict(st["delta"])
                               for k, st in enumerate(stats)]}
    return out


def compare(cfg, params, vae, k_in, inputs, served, log=print,
            label="check") -> dict:
    """Serve each sampled request again in the plain reference, following
    the served side's reuse decisions within the band, and compare the last
    latent and the decoded image. Returns {name: {value, limit}}, the worst
    over the sample."""
    steps, tau = cfg["steps"], cfg["engine"]["cache_tau"]
    band, limits = cfg["check"]["band"], cfg["check"]["limits"]
    worst = {"latent_rel_err": 0.0, "image_rel_err": 0.0}
    in_band = flipped = outside = 0
    for rid, s in served.items():
        rcfg = ref_config(cfg, s["res"])
        noise, text = inputs(k_in, rid, s["res"])
        names = list(reference.empty_stores(rcfg, params, noise[None]))
        dec = _decide((s["deltas"] + [{}] * steps)[:steps], names,
                      rcfg["grid"], tau)
        lats, stats = reference.serve_steps(rcfg, steps, tau, band, params,
                                            text, noise, dec,
                                            REFERENCE_PRECISION)
        e_lat = _rel(s["latent"], lats[-1])
        e_img = _rel(s["image"], reference.decode(vae, lats[-1],
                                                  REFERENCE_PRECISION))
        nb = nf = 0
        for k, (st, d) in enumerate(zip(stats, dec)):
            for n, ref_d in st["delta"].items():
                near = (np.abs(np.asarray(ref_d) / tau - 1) < band) & (k > 0)
                nb += int(near.sum())
                nf += int((near & (np.asarray(d[n]) != (ref_d < tau))).sum())
        nd = int(sum(int(v) for st in stats for v in st["disagree"].values()))
        in_band, flipped, outside = in_band + nb, flipped + nf, outside + nd
        log(f"{label} rid {rid} {s['res']}: latent_rel_err {e_lat:.4e} "
            f"image_rel_err {e_img:.4e} decisions_in_band {nb} "
            f"in_band_against_own {nf} out_of_band_disagreements {nd}")
        worst["latent_rel_err"] = max(worst["latent_rel_err"], e_lat)
        worst["image_rel_err"] = max(worst["image_rel_err"], e_img)
    log(f"{label}: reuse decisions within the band {in_band}, of them "
        f"against the reference's own {flipped}; outside it, disagreements "
        f"{outside}")
    return {k: {"value": v, "limit": limits[k]} for k, v in worst.items()}
