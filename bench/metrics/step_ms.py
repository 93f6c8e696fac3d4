"""Mean engine step time in the window, from the engine's own
`Metrics.step_latencies` (`PatchedServeEngine._timed_step`: host clock,
stopped once the step's latents exist on the device)."""


def read(run):
    dts = [t.dt for t in run.ticks if t.dt > 0]
    return 1e3 * sum(dts) / len(dts) if dts else None
