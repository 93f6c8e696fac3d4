"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

`load` reads the `.xplane.pb` that `jax.profiler` writes and keeps three
lists of (name, start_ns, duration_ns): the operations on the first device's
"XLA Ops" line, the programs on its "XLA Modules" line (an eager op is one
program, named after the function it runs: `jit_einsum`, `jit_softmax`, ...),
and the host's spans whose names the harness put there with
`jax.profiler.TraceAnnotation`. All are on the profiler's one clock.
`reduce` works on those lists alone, so it can be checked on a recorded,
trimmed trace without a chip.

- The traced window runs from the first host span named `tick` to the end of
  the last one.
- Busy time is the union of the device operations' intervals in the window;
  idle is the rest.
- Each idle gap is put down to what the host was doing at its midpoint: the
  innermost harness span open there ("tick" for the engine's own code between
  layers), else "harness".
- A kernel's time is the sum of the device operations whose name contains the
  kernel's name. The breakdown's device time is summed by program name.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start ns, duration ns

HOST_SPANS = ("tick", "schedule", "csp_split", "sampler_step", "cache_hook",
              "block", "csp_merge", "postprocess")


def load(trace_dir: str) -> Tuple[List[Event], List[Event], List[Event]]:
    """(device operations, device programs, harness host spans) of the
    first device that ran anything."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[0])
    ops: List[Event] = []
    programs: List[Event] = []
    host: List[Event] = []
    for p in sorted(pd.planes, key=lambda p: p.name):
        lines = {line.name: list(line.events) for line in p.lines}
        if p.name.startswith("/device:") and lines.get("XLA Ops") and not ops:
            ops = [(e.name[:200], int(e.start_ns), int(e.duration_ns))
                   for e in lines["XLA Ops"]]
            programs = [(e.name.split("(")[0], int(e.start_ns),
                         int(e.duration_ns))
                        for e in lines.get("XLA Modules", ())]
        elif p.name.startswith("/host:"):
            for evs in lines.values():
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in evs if e.name in HOST_SPANS]
    return ops, programs, host


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _innermost(host: Sequence[Event]):
    """Cut the host's nested spans into segments, each labelled with the
    innermost span open over it ("harness" where none is): (starts, labels)."""
    starts, labels = [], []
    stack: List[Tuple[int, str]] = []

    def emit(a, label):
        starts.append(a)
        labels.append(label)

    for n, s, d in sorted(host, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= s:
            end, _ = stack.pop()
            emit(end, stack[-1][1] if stack else "harness")
        emit(s, n)
        stack.append((s + d, n))
    while stack:
        end, _ = stack.pop()
        emit(end, stack[-1][1] if stack else "harness")
    return starts, labels


def reduce(device: Sequence[Event], programs: Sequence[Event],
           host: Sequence[Event], kernels: Sequence[str] = ()) -> Dict:
    ticks = [(s, s + d) for n, s, d in host if n == "tick"]
    if not ticks:
        raise ValueError("no tick spans in the trace")
    w0, w1 = min(a for a, _ in ticks), max(b for _, b in ticks)
    clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in device
               if s < w1 and s + d > w0]
    busy = _union(clipped)
    busy_ns = sum(b - a for a, b in busy)
    gaps, prev = [], w0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if w1 > prev:
        gaps.append((prev, w1))
    starts, labels = _innermost(host)
    by_host: Dict[str, int] = defaultdict(int)
    for a, b in gaps:
        i = bisect.bisect_right(starts, (a + b) // 2) - 1
        by_host[labels[i] if i >= 0 else "harness"] += b - a
    ops: Dict[str, int] = defaultdict(int)
    for n, s, d in programs:
        if s < w1 and s + d > w0:
            ops[n] += d
    kernel_ns = {k: sum(d for n, s, d in device
                        if k in n and s < w1 and s + d > w0)
                 for k in kernels}
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy_ns * ns,
        "device_ops": sorted(([n, d * ns] for n, d in ops.items()),
                             key=lambda x: -x[1])[:10],
        "idle_gaps": sorted(([n, d * ns] for n, d in by_host.items()),
                            key=lambda x: -x[1])[:10],
        "kernel_s": {k: v * ns for k, v in kernel_ns.items()},
    }
