"""The benchmark's yardstick on the CPU: operation counts, the traffic
module, the model kinds' modules, the weights' layout and the plain
reference. Each model kind is tested through its tiny configuration,
`data/tiny-<kind>.json`."""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, harness, models, reference, weights

DATA = Path(__file__).resolve().parent / "data"
TINY = sorted(p.stem for p in DATA.glob("tiny-*.json"))


def tiny(name, **over):
    """The model section of a tiny configuration, with `over` applied."""
    m = json.loads((DATA / f"{name}.json").read_text())["model"]
    return dict(m, **over)


def _program_config(m):
    from repro.models import diffusion as dm
    return dm.DiffusionConfig(**{k: tuple(v) if isinstance(v, list) else v
                                 for k, v in m.items()})


def _flops_checks():
    for name in TINY:
        cases = json.loads((DATA / f"{name}.json").read_text())["flops_checks"]
        for case in cases:
            case = dict(case)
            res = tuple(case.pop("res"))
            yield pytest.param(name, case, res, id=f"{name}-{res[0]}x{res[1]}")


# XLA counts elementwise work, which step_flops leaves out (a few percent,
# more at tiny widths), and skips the zero padding of convolutions, which
# step_flops counts (border rows are a larger share of a tiny latent).
@pytest.mark.parametrize("name,over,res", list(_flops_checks()))
def test_step_flops_against_xla(name, over, res):
    m = tiny(name, **over)
    P, _ = jax.eval_shape(lambda: weights.make_weights(m, jax.random.PRNGKey(0)))
    fwd = models.kind(m["kind"]).forward
    f = jax.jit(lambda p, x, t, c: fwd(m, p, x, t, c, lambda n, fn, z: fn(z)))
    ca = f.lower(
        P, jax.ShapeDtypeStruct((1, *res, m["latent_channels"]), jnp.float32),
        jax.ShapeDtypeStruct((1,), jnp.float32),
        jax.ShapeDtypeStruct((1, m["n_text"], m["d_text"]), jnp.float32)
    ).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert 0.85 < flops.step_flops(m, *res) / ca["flops"] < 1.15


def _pixart():
    return json.loads((harness.ROOT / "bench" / "configs"
                       / "pixart-alpha-dit.json").read_text())["model"]


# The integers `step_mfu` divides by in `pixart.closed-mixed`: a change here
# would move the metric with no change to the program.
@pytest.mark.parametrize("res,want", [((32, 32), 640_012_976_128),
                                      ((48, 48), 1_590_644_113_408),
                                      ((64, 64), 3_276_667_813_888)])
def test_step_flops_pinned(res, want):
    assert flops.step_flops(_pixart(), *res) == want


def test_model_spec_pinned():
    """Paths, shapes and inits in the order the weights are drawn: the same
    seed gives the same weights only while this list stays as it was."""
    spec = weights.model_spec(_pixart())
    assert len(spec) == 208
    assert hashlib.sha256(repr(spec).encode()).hexdigest() == (
        "4fd50449b462bc297adbe502bf1062b67915bdeedb27a3566d35bdb10560be98")


def test_models_import_nothing_of_the_program():
    names = sorted(p.stem for p in Path(models.__file__).parent.glob("*.py")
                   if p.stem != "__init__")
    code = ("import importlib, sys\n"
            f"for n in {names!r}:\n"
            "    importlib.import_module('bench.models.' + n)\n"
            "print(sorted(m for m in sys.modules\n"
            "             if m == 'repro' or m.startswith('repro.')))\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=harness.ROOT, capture_output=True,
        text=True, timeout=120, check=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(harness.ROOT)))
    assert out.stdout.strip().splitlines()[-1] == "[]"


def test_kind_lookup():
    files = [harness.ROOT / c["file"]
             for c in harness.load_benchmark()["configs"]]
    files += [DATA / f"{n}.json" for n in TINY]
    for f in files:
        mod = models.kind(json.loads(f.read_text())["model"]["kind"])
        assert mod.SAMPLER in ("ddim", "flow")
        for fn in ("weight_spec", "forward", "step_flops", "kernel_shapes"):
            assert callable(getattr(mod, fn))
    with pytest.raises(LookupError, match="bench/models/no-such-kind.py"):
        models.kind("no-such-kind")


def test_kernel_counts_by_hand():
    # (B, H, S, D) = (2, 3, 5, 7): QK^T and PV are 2 * 2*S*S*D per head
    f, b = flops.patch_attention((2, 3, 5, 7))
    assert f == 2 * 3 * (2 * 5 * 5 * 7) * 2
    assert b == 4 * 4 * (2 * 3 * 5 * 7)
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000, 10, peaks) == (10.0, "compute")
    assert flops.roofline_seconds(10, 1000, peaks) == (100.0, "memory")


def test_published_sizes():
    for conf in harness.load_benchmark()["configs"]:
        cfg = json.loads((harness.ROOT / conf["file"]).read_text())
        assert weights.param_count(cfg["model"]) == cfg["params"]


@pytest.mark.parametrize("steps,starts", [(8, [0, 2, 4, 6]),
                                          (20, [0, 5, 10, 15])])
def test_closed_waves_spread_evenly(steps, starts):
    closed = harness.traffic_kind("closed")
    mix = {"kind": "closed", "clients_per_resolution": 4, "start_spread": 1.0}
    waves = closed.waves(mix, 3, steps)
    assert [s for s, _ in waves] == starts
    assert all(c == [0, 1, 2] for _, c in waves)       # one client per size
    assert closed.lead_in_ticks(mix, steps) == steps   # the lead-in
    assert closed.counts(mix, 3, steps) == [(1,) * 3, (2,) * 3, (3,) * 3,
                                            (4,) * 3]


class _FakeServe:
    """Completes each request on the `steps`-th tick after it was sent."""

    def __init__(self, steps):
        self.steps, self.ticks, self.live, self.window = steps, 0, [], []

    def send(self, i):
        self.live.append([self.steps, i])

    def tick(self, in_window):
        if in_window:
            self.window.append(sorted(i for _, i in self.live))
        for r in self.live:
            r[0] -= 1
        done = [i for left, i in self.live if left == 0]
        self.live = [r for r in self.live if r[0] > 0]
        self.ticks += 1
        return done


def test_closed_loop_keeps_every_client_in_flight():
    closed = harness.traffic_kind("closed")
    mix = {"kind": "closed", "clients_per_resolution": 4, "start_spread": 1.0}
    serve, opened = _FakeServe(8), []
    closed.drive(serve, mix, 3, 8, lambda: opened.append(serve.ticks),
                 lambda ticks: ticks >= 24)
    assert opened == [8]                                # after the lead-in
    assert len(serve.window) == 24
    assert all(w == [0] * 4 + [1] * 4 + [2] * 4 for w in serve.window)


@pytest.mark.parametrize("name", TINY)
def test_weights_match_program_layout(name):
    from repro.models import diffusion as dm
    m = tiny(name)
    cfg = _program_config(m)
    want = jax.eval_shape(lambda: dm.init_diffusion(cfg, jax.random.PRNGKey(0)))
    got, vae = jax.eval_shape(lambda: weights.make_weights(m, jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
    from repro.models import vae as vm
    v = jax.eval_shape(lambda: vm.init_vae(jax.random.PRNGKey(7),
                                           m["latent_channels"]))
    assert jax.tree_util.tree_structure(v) == jax.tree_util.tree_structure(vae)


@pytest.mark.parametrize("name", TINY)
def test_reference_matches_program_step(name):
    """Without reuse, one reference step equals the program's patched step
    on its plain path."""
    from repro.core.patching import merge_by_request, split
    from repro.models import sampler as sm
    m = tiny(name, use_kernels=False)
    cfg = _program_config(m)
    params, _ = weights.make_weights(m, weights.seed_key(3))
    res = [(16, 16), (24, 24), (32, 32)]
    rng = np.random.default_rng(0)
    lats = [jnp.asarray(rng.normal(size=(h, w, m["latent_channels"])),
                        jnp.float32) for h, w in res]
    texts = [jnp.asarray(rng.normal(size=(m["n_text"], m["d_text"])) * 0.3,
                         jnp.float32) for _ in res]
    csp, patches = split(lats, patch=8, req_ids=[0, 1, 2])
    ks = [3, 0, 5]
    step_req = jnp.asarray(ks, jnp.int32)[jnp.asarray(csp.req_ids)]
    text = jnp.stack([texts[int(r)] for r in csp.req_ids])
    outs = merge_by_request(csp, sm.sampler_step(cfg, params, csp, patches,
                                                 step_req, 8, text))
    for i, (h, w) in enumerate(res):
        rc = dict(m, grid=(h // 8, w // 8))
        st = reference.empty_stores(rc, params, lats[i][None])
        dec = {n: jnp.zeros(rc["grid"], bool) for n in st}
        x, _, _ = reference.cached_step(rc, 8, 0.05, 0.5, params, lats[i][None],
                                        texts[i][None], jnp.int32(ks[i]), st, dec)
        got, want = np.asarray(outs[i]), np.asarray(x[0])
        assert np.linalg.norm(got - want) / np.linalg.norm(want) < 1e-5
