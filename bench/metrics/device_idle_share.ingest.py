"""Percent of the traced window in which no operation ran on the device
(mean over the run's devices)."""
from harness.reduce import Context, idle_share


def read(ctx: Context) -> float | None:
    return idle_share(ctx)
