"""A whole run of the harness on the CPU at a tiny size of each model kind
(`data/tiny-<kind>.json`), past the look for a chip: sound, with the control
in the program's place, and with the timed path broken underneath in each
way a one-chip serving cell can break."""
import io
import json
import time
from contextlib import redirect_stdout
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import harness, run

DATA = Path(__file__).resolve().parent / "data"
MIX = {"kind": "closed", "clients_per_resolution": 4, "start_spread": 1.0}
CHIP = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 77
KINDS = sorted(p.stem for p in DATA.glob("tiny-*.json"))


def cfg(name):
    return json.loads((DATA / f"{name}.json").read_text())


def execute(name, trace=False, control=False):
    """Run past the chip probe; returns the parsed result line."""
    bench = harness.load_benchmark()
    cell = {"name": f"{name}.closed-mixed", "config": name,
            "traffic": "closed-mixed", "chips": 1}
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.execute(bench, cell, cfg(name), MIX, SEED, 2.0, trace,
                         {"platform": "cpu", "kind": "cpu"}, CHIP,
                         t_process=time.perf_counter(), control=control)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.fixture(scope="module", params=KINDS)
def sound(request):
    return request.param, execute(request.param)


def test_sound_run_is_correct(sound):
    _, out = sound
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"latent_rel_err", "image_rel_err"}
    for c in out["checks"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_reports_layers():
    out = execute("tiny-dit", trace=True)
    assert out["correct"] is True
    assert {"step_ms", "step_mfu", "reuse_pct"} <= set(out["metrics"])
    assert out["device"]["window_s"] > 0
    assert "device_ops" in out["breakdown"] and "idle_gaps" in out["breakdown"]


@pytest.mark.parametrize("name", KINDS)
def test_control_is_not_correct(name):
    """The reference in bfloat16 in the program's place fails the check."""
    out = execute(name, control=True)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


def _unchanged(real):
    return lambda cfg, params, csp, patches, *a, **k: patches


def _half_batch(real):
    def step(cfg, params, csp, patches, *a, **k):
        out = real(cfg, params, csp, patches, *a, **k)
        n = patches.shape[0] // 2
        return jnp.concatenate([out[:n], patches[n:]])
    return step


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_makes_run_incorrect(sound, fault, monkeypatch):
    from repro.models import sampler, vae
    if fault == "state_unchanged":
        monkeypatch.setattr(sampler, "sampler_step",
                            _unchanged(sampler.sampler_step))
    elif fault == "half_batch":
        monkeypatch.setattr(sampler, "sampler_step",
                            _half_batch(sampler.sampler_step))
    else:
        real = vae.vae_decode
        monkeypatch.setattr(vae, "vae_decode", lambda p, z: real(p, z).at[
            :, :8].multiply(-1.0))
    assert execute(sound[0])["correct"] is False


def test_window_arithmetic():
    c = cfg("tiny-unet")
    w = harness.Window(cfg=c, start=1.0, end=3.0)
    w.ticks = [harness.TickRecord(1.0, 1.5, 0.5, 12, (4, 4, 4), 0.25),
               harness.TickRecord(1.5, 3.0, 1.0, 12, (4, 4, 4), None)]
    w.peaks = CHIP
    assert run.read_metric("images_per_s", w) == pytest.approx(24 / 4 / 2.0)
    assert run.read_metric("step_ms", w) == pytest.approx(750.0)
    assert run.read_metric("reuse_pct", w) == pytest.approx(25.0)
    from bench import flops
    work = 2 * 4 * sum(flops.step_flops(c["model"], *r)
                       for r in c["resolutions"])
    assert run.read_metric("step_mfu", w) == pytest.approx(
        100 * work / (1.5 * CHIP["flops_bf16"]))
    assert run.read_metric("device_idle_pct", w) is None     # no trace
    assert run.read_metric("patch_attention_roofline", w) is None
