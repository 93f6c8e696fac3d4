"""Smoke run of the PatchedServe serving path on one TPU chip.

Drives the engine the way ``repro.launch.serve`` does: the SDXL-lite preset
(seeded random weights, Pallas kernels compiled by Mosaic) behind a
``PatchedServeEngine`` that calibrates, then serves a seeded Poisson workload
of 512, 768 and 1024 px requests (64, 96 and 128 latents, GCD patch 32) with
the patch cache on. Checks, in order; any failure exits non-zero:

1. the first device is a TPU — there is no CPU fallback;
2. the kernels run compiled: ``ops._on_tpu()`` holds and the lowered model
   step contains ``tpu_custom_call``;
3. one ``denoise_patched`` step with the kernels agrees with the jnp path
   (``use_kernels=False``) on a batch holding all three resolutions;
4. the served run completes every request, drops none, and every decoded
   image is finite.

Diagnostics come first; the last line of stdout is one JSON object. Every
phase runs in this one process, which holds the chip.

Run from the repository root: ``python chip_smoke.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

RES = [(64, 64), (96, 96), (128, 128)]     # latents of 512/768/1024 px images
STEPS = 8                                  # denoising steps per request
SEED = 0                                   # weights and agreement inputs
WORKLOAD = dict(qps=1.0, duration=6.0, seed=6)   # 6 requests, 2 per size
SLO_SCALE = 100.0                          # generous: nothing should drop
# Agreement of the kernel path with the jnp path, both at "highest" matmul
# precision: max |kernel - jnp| over max |jnp| for one full UNet step. Under
# "highest" the attention kernel keeps the MXU's f32 format (its default is
# one bfloat16 pass, as XLA's own dots), so the two paths differ only in
# summation order (online vs full softmax, fused vs separate normalisation),
# and this stays a few ulps of f32 times the depth.
AGREE_RTOL = 1e-4


class SmokeFailure(Exception):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def phase(name: str, times: dict):
    t0 = time.perf_counter()
    yield
    times[name] = time.perf_counter() - t0
    print(f"phase {name}: {times[name]:.3f} s", flush=True)


def agreement_inputs(cfg, res, seed: int):
    """Seeded latents (one request per resolution), timesteps and text."""
    import jax
    import numpy as np

    from repro.core.patching import split
    rng = np.random.default_rng(seed)
    lats = [jax.numpy.asarray(rng.normal(size=(h, w, cfg.latent_channels)),
                              jax.numpy.float32) for h, w in res]
    csp, patches = split(lats)
    t_req = jax.numpy.asarray(
        np.linspace(999, 1, len(res)).round(), jax.numpy.float32)
    text = jax.numpy.asarray(
        rng.normal(size=(len(res), cfg.n_text, cfg.d_text)) * 0.3,
        jax.numpy.float32)
    return csp, patches, t_req, text


def check_compiled(cfg, params, inputs) -> int:
    """Check 2: the kernels take the Mosaic path inside the model step."""
    import jax

    from repro.kernels import ops
    from repro.models import diffusion as dm
    require(ops._on_tpu(), "kernels would run in interpret mode")
    csp, patches, t_req, text = inputs
    hlo = jax.jit(lambda p, x, t, c: dm.denoise_patched(cfg, p, csp, x, t, c)
                  ).lower(params, patches, t_req, text).as_text()
    n = hlo.count("tpu_custom_call")
    require(n > 0, "no tpu_custom_call in the lowered model step")
    return n


def check_agreement(cfg, params, inputs) -> float:
    """Check 3: one step, kernel path vs jnp path; returns the rel. error."""
    import jax
    import numpy as np

    from repro.models import diffusion as dm
    csp, patches, t_req, text = inputs
    ref_cfg = dataclasses.replace(cfg, use_kernels=False)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(dm.denoise_patched(cfg, params, csp, patches,
                                            t_req, text))
        want = np.asarray(dm.denoise_patched(ref_cfg, params, csp, patches,
                                             t_req, text))
    require(got.shape == want.shape == patches.shape,
            f"step output shape {got.shape} != {patches.shape}")
    require(np.isfinite(got).all(), "kernel step is not finite")
    err = float(np.max(np.abs(got - want)))
    scale = float(np.max(np.abs(want)))
    print(f"agreement: max_abs_err={err:.3e} max_abs_ref={scale:.3e} "
          f"rel={err / scale:.3e} (tolerance {AGREE_RTOL:.0e})", flush=True)
    require(err <= AGREE_RTOL * scale,
            f"kernel step differs from jnp step by {err / scale:.3e}")
    return err / scale


def serve(cfg, params, res, steps: int, workload: dict, times: dict):
    """Check 4: calibrate, then serve a seeded Poisson workload."""
    import numpy as np

    from repro.core.requests import Request, poisson_workload
    from repro.core.serving import EngineConfig, PatchedServeEngine
    eng = PatchedServeEngine(
        cfg, params,
        EngineConfig(clock="real", use_cache=True, cache_tau=0.05,
                     cache_capacity=512),
        dict.fromkeys(map(tuple, res), 1.0), res)
    with phase("calibrate", times):
        eng.calibrate(total_steps_hint=steps)
    # A probe's second step can still compile the cache hook's reuse path,
    # so time a composition calibrate compiled (one request of each size)
    # after two more steps have run every path.
    reqs = [Request(rid=20_000_000 + i, resolution=r, arrival=0.0, slo=1e9,
                    total_steps=steps) for i, r in enumerate(res)]
    for r in reqs:
        eng._prepare(r)
    with phase("warm_step", times):
        for _ in range(2):
            eng._timed_step(reqs)
        warm = [eng._timed_step(reqs)[1] for _ in range(3)]
    print(f"warm step (one request per size): median "
          f"{np.median(warm) * 1e3:.2f} ms of {len(warm)}", flush=True)
    wl = poisson_workload(workload["qps"], workload["duration"], res,
                          SLO_SCALE, eng.sa, steps=steps,
                          seed=workload["seed"])
    print(f"serving {len(wl)} requests: {[r.resolution[0] for r in wl]}",
          flush=True)
    with phase("serve", times):
        m = eng.run(wl, max_wall=900)
    steps_ms = np.asarray(m.step_latencies) * 1e3
    print(f"served: completed={m.completed} dropped={m.dropped} "
          f"slo_met={m.slo_met} steps={len(steps_ms)} "
          f"median_step={np.median(steps_ms):.2f} ms", flush=True)
    require(m.dropped == 0, f"{m.dropped} requests dropped")
    require(m.completed == len(wl),
            f"completed {m.completed} of {len(wl)} requests")
    for r in wl:
        img = eng.outputs.get(r.rid)
        require(img is not None, f"request {r.rid} has no decoded image")
        h, w = r.resolution
        require(img.shape == (8 * h, 8 * w, 3),
                f"request {r.rid} image shape {img.shape}")
        require(bool(np.isfinite(img).all()),
                f"request {r.rid} image is not finite")
    return m


def main() -> int:
    cache_dir = enable_compile_cache()
    import jax

    from repro.models import diffusion as dm
    times: dict = {}
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform}; "
              "refusing to fall back", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x{len(devices)}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)

    cfg = dm.SDXL_LITE
    try:
        with phase("init", times):
            params = dm.init_diffusion(cfg, jax.random.PRNGKey(SEED))
            inputs = agreement_inputs(cfg, RES, SEED)
        with phase("lower", times):
            n = check_compiled(cfg, params, inputs)
        print(f"kernels compiled: {n} tpu_custom_call in the model step",
              flush=True)
        with phase("agreement", times):
            check_agreement(cfg, params, inputs)
        serve(cfg, params, RES, STEPS, WORKLOAD, times)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}",
          flush=True)
    print(f"total {sum(times.values()):.3f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
