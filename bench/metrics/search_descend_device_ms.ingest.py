"""Device self time per micro-batch in the greedy descent through the upper
levels (fold.search.descend)."""
from harness.phases import scope_ms_per_batch
from harness.reduce import Context


def read(ctx: Context) -> float | None:
    return scope_ms_per_batch(ctx, "fold.search.descend")
