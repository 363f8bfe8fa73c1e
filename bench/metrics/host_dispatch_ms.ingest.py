"""Host ms per micro-batch in the executor's two dispatches, less the
device waits inside them: signature prep, padding upload, level sampling
and the programs' dispatch."""
from harness.phases import host_dispatch_ms
from harness.reduce import Context


def read(ctx: Context) -> float | None:
    return host_dispatch_ms(ctx)
