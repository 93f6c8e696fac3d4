"""Closed-loop traffic: a mix file of kind `closed` names this module.

`clients_per_resolution` clients for each resolution of the configuration.
Each sends its next request when its previous image is decoded. The clients
come in waves of one per resolution; the waves send their first requests at
ticks spaced evenly over `start_spread` request durations, so that requests
sit at mixed steps in one batch (paper Fig. 1). The spacing is the same for
every seed: every seed serves the same sizes at the same ticks, and the seed
draws only the requests' noise and prompts. Where the waves' steps fall
decides how often a whole block is reused, so a spacing drawn from the seed
would change the work from seed to seed.
"""
from __future__ import annotations

from typing import List, Tuple


def lead_in_ticks(mix: dict, steps: int) -> int:
    """Ticks within which every wave has sent its first request."""
    return max(int(round(float(mix["start_spread"]) * steps)),
               int(mix["clients_per_resolution"]))


def waves(mix: dict, n_res: int, steps: int) -> List[Tuple[int, List[int]]]:
    """[(start tick, [resolution index of each client in the wave])]."""
    n = int(mix["clients_per_resolution"])
    spread = lead_in_ticks(mix, steps)
    return [(w * spread // n, list(range(n_res))) for w in range(n)]


def counts(mix: dict, n_res: int, steps: int) -> List[Tuple[int, ...]]:
    """Every count of requests in flight per resolution the mix reaches."""
    n = int(mix["clients_per_resolution"])
    return [(w,) * n_res for w in range(1, n + 1)]


def drive(serve, mix: dict, n_res: int, steps: int, open_window, stop):
    """Lead-in, then the window. `serve.send(i)` submits a request at
    resolution i; `serve.tick(in_window)` runs one engine tick and returns the
    resolution indices of the requests it completed. `open_window()` is
    called once the lead-in has filled the batch; `stop(ticks)` says when the
    window, `ticks` ticks long so far, has lasted long enough."""
    plan = waves(mix, n_res, steps)
    for tick in range(lead_in_ticks(mix, steps)):
        for start, clients in plan:
            if start == tick:
                for i in clients:
                    serve.send(i)
        for i in serve.tick(False):
            serve.send(i)
    open_window()
    ticks = 0
    while not stop(ticks):
        for i in serve.tick(True):
            serve.send(i)
        ticks += 1
