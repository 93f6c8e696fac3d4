"""Multi-device behaviours validated in a subprocess with forced host devices
(the main test process must keep the default single-device backend)."""
import subprocess
import sys
import textwrap
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 4) -> str:
    prog = ("import os\n"
            f"os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count={devices}'\n"
            + textwrap.dedent(code))
    # forced host devices only exist on the CPU platform; pin it in the
    # child too — without it JAX may hang probing for accelerator backends
    # that the sandbox advertises but cannot serve
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root", "JAX_PLATFORMS": "cpu"},
        timeout=480)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_pipeline_parallel_matches_sequential():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipelined_apply

    mesh = jax.make_mesh((4,), ("stage",))
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(size=(4, 8, 8)) * 0.3, jnp.float32)

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jnp.asarray(rng.normal(size=(6, 2, 8)), jnp.float32)  # 6 microbatches
    got = pipelined_apply(stage_fn, mesh, W, x)

    want = x
    for s in range(4):
        want = jnp.tanh(want @ W[s])
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 1e-5, err
    print("PP-OK", err)
    """)
    assert "PP-OK" in out


def test_quantized_psum_multi_device():
    out = _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import quantized_psum

    mesh = jax.make_mesh((4,), ("d",))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 128)), jnp.float32)
    got = jax.shard_map(lambda v: quantized_psum(v[0], "d"), mesh=mesh,
                        in_specs=P("d"), out_specs=P(), check_vma=False)(x)
    want = jnp.sum(x, axis=0)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < 0.2, err
    print("QPSUM-OK", err)
    """)
    assert "QPSUM-OK" in out


def test_moe_shard_map_matches_local():
    """EP shard_map path == single-device local path (same routing)."""
    out = _run("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs import ARCHS
    from repro.launch import context as ctx
    from repro.models import moe as moe_mod
    from repro.models.layers import ParamBuilder

    cfg = dataclasses.replace(ARCHS["mixtral-8x7b"].reduced(),
                              n_experts=4, moe_top_k=2, capacity_factor=8.0,
                              n_shared_experts=0, fsdp=True)
    b = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
    moe_mod.init_moe(cfg, b, cfg.d_model, cfg.d_ff)
    p = b.params
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 8, cfg.d_model)), jnp.float32)

    y_local, _ = moe_mod.apply_moe(cfg, p, x)

    mesh = jax.make_mesh((2, 2), ("data", "model"))
    with mesh, ctx.use_mesh(mesh):
        # E=4 on a 2x2 mesh -> 2D-EP weight-gather path (train shapes)
        y_dist, _ = jax.jit(lambda pp, xx: moe_mod.apply_moe(cfg, pp, xx))(p, x)
    err = float(jnp.max(jnp.abs(y_local - y_dist)))
    assert err < 1e-4, err
    print("MOE-EP-OK", err)
    """)
    assert "MOE-EP-OK" in out


def test_moe_token_gather_decode_path():
    """2D-EP token-gather (decode) == local path."""
    out = _run("""
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import ARCHS
    from repro.launch import context as ctx
    from repro.models import moe as moe_mod
    from repro.models.layers import ParamBuilder

    cfg = dataclasses.replace(ARCHS["mixtral-8x7b"].reduced(),
                              n_experts=4, moe_top_k=2, capacity_factor=8.0,
                              n_shared_experts=0, fsdp=True)
    b = ParamBuilder(jax.random.PRNGKey(0), jnp.float32)
    moe_mod.init_moe(cfg, b, cfg.d_model, cfg.d_ff)
    p = b.params
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 1, cfg.d_model)), jnp.float32)

    y_local, _ = moe_mod.apply_moe(cfg, p, x)
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    with mesh, ctx.use_mesh(mesh):
        y_dist, _ = jax.jit(lambda pp, xx: moe_mod.apply_moe(cfg, pp, xx))(p, x)
    err = float(jnp.max(jnp.abs(y_local - y_dist)))
    assert err < 1e-4, err
    print("MOE-TG-OK", err)
    """)
    assert "MOE-TG-OK" in out


def test_elastic_remesh_resume(tmp_path):
    out = _run(f"""
    import jax, jax.numpy as jnp, numpy as np
    from repro.checkpoint import CheckpointManager
    from repro.configs import ARCHS
    from repro.data import TokenPipeline
    from repro.distributed.elastic import ElasticConfig, ElasticTrainer
    from repro.launch.steps import make_train_step
    from repro.models import lm
    from repro.optim import opt_init

    cfg = ARCHS["internlm2-1.8b"].reduced()
    params, _ = lm.init_model(cfg, jax.random.PRNGKey(0))
    opt = opt_init(cfg, params)
    pipe = TokenPipeline(cfg.vocab_size, 2, 16)
    ckpt = CheckpointManager({str(tmp_path)!r}, keep=2, async_mode=False)
    tr = ElasticTrainer(
        make_mesh=lambda n: jax.make_mesh((min(n, 2),), ("data",)),
        build_step=lambda mesh: jax.jit(make_train_step(cfg)),
        ckpt=ckpt, cfg=ElasticConfig(ckpt_every=3))
    batches = [next(pipe) for _ in range(10)]
    params, opt, step, metrics = tr.run(params, opt, batches,
                                        fail_at={{5: 2}})
    assert any(e["event"] == "remesh" for e in tr.events), tr.events
    assert np.isfinite(float(metrics["loss"]))
    print("ELASTIC-OK", step, float(metrics["loss"]))
    """)
    assert "ELASTIC-OK" in out
