"""Compile the Pallas kernels for a described TPU v5e at every shape the
configurations in `BENCHMARK.json` call them with, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py

Prints one line per shape with the compiled program's memory; a shape Mosaic
refuses is printed with the error and makes the exit code 1.
"""
from __future__ import annotations

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def kernel_shapes(cfg, patches_total):
    """{kernel: {shape}} a batch of `patches_total` patches (4 requests per
    resolution) calls, read off the configuration by its model kind's
    module (`bench/models/<kind>.py`)."""
    from bench.models import kind
    return kind(cfg["model"]["kind"]).kernel_shapes(cfg, patches_total)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.groupnorm_stitch import groupnorm_stitch
    from repro.kernels.patch_attention import patch_attention

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    bad = 0
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for conf in bench["configs"]:
        name = conf["name"]
        cfg = json.loads((ROOT / conf["file"]).read_text())
        for kernel, shapes in kernel_shapes(cfg, 116).items():
            for shape in sorted(shapes):
                try:
                    if kernel == "patch_attention":
                        x = sds(shape)
                        c = jax.jit(lambda q, k, v: patch_attention(
                            q, k, v, interpret=False)).lower(x, x, x).compile()
                    else:
                        P, p, _, C = shape
                        c = jax.jit(lambda *a: groupnorm_stitch(
                            *a, interpret=False)).lower(
                            sds(shape), sds((P, 8), jnp.int32), sds((P, C)),
                            sds((P, C)), sds((C,)), sds((C,))).compile()
                    mem = c.memory_analysis()
                    print(f"{name} {kernel} {shape}: ok, temp "
                          f"{getattr(mem, 'temp_size_in_bytes', '?')} B",
                          flush=True)
                except Exception as e:              # noqa: BLE001
                    bad += 1
                    print(f"{name} {kernel} {shape}: REFUSED {e}"[:2000],
                          flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
