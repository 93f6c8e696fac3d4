"""Images per second: every real (non-padding) request-step the window ran,
over the configuration's steps per image, over the window's seconds. Partial
progress counts, so the rate is not quantised to whole images."""


def read(run):
    if run.seconds <= 0 or not run.request_steps:
        return None
    return run.request_steps / run.cfg["steps"] / run.seconds
