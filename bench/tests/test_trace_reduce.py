"""The reduction from trace events to busy time, idle gaps and kernel time."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def test_synthetic_trace_by_hand():
    device = [("fusion.1", 100, 50), ("patch_attention.3", 140, 60),
              ("convolution", 400, 100), ("outside", 5000, 10)]
    host = [("tick", 100, 600), ("sampler_step", 150, 300),
            ("cache_hook", 210, 150), ("postprocess", 600, 100),
            ("tick", 800, 300)]
    device.append(("late", 800, 100))
    programs = [("jit_einsum", 100, 100), ("jit_conv", 400, 100)]
    r = trace_reduce.reduce(device, programs, host,
                            kernels=("patch_attention",))
    ns = 1e-9
    assert r["window_s"] == pytest.approx(1000 * ns)
    # busy [100,200), [400,500), [800,900)
    assert r["busy_s"] == pytest.approx(300 * ns)
    gaps = dict(r["idle_gaps"])
    assert gaps["cache_hook"] == pytest.approx(200 * ns)   # [200,400)
    # [500,800) is one gap; its midpoint falls in postprocess
    assert gaps["postprocess"] == pytest.approx(300 * ns)
    assert "harness" not in gaps
    assert gaps["tick"] == pytest.approx(200 * ns)         # [900,1100)
    assert r["kernel_s"]["patch_attention"] == pytest.approx(60 * ns)
    assert dict(r["device_ops"])["jit_conv"] == pytest.approx(100 * ns)
