"""Device time, in us, of the expert layers (named scope ``moe`` of the
compiled decode step) per traced engine step."""

from harness.step_scopes import us_per_step


def read(run):
    return us_per_step(run, "moe")
