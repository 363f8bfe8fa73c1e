"""Host ms per micro-batch of the service's own work: inside its spans and
not waiting for the device. Caps docs_per_s once the device is faster."""
from harness.phases import host_busy_ms
from harness.reduce import Context


def read(ctx: Context) -> float | None:
    return host_busy_ms(ctx)
