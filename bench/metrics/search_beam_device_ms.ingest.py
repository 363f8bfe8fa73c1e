"""Device self time per micro-batch in the level-0 beam and the top-k masking
(fold.search.beam)."""
from harness.phases import scope_ms_per_batch
from harness.reduce import Context


def read(ctx: Context) -> float | None:
    return scope_ms_per_batch(ctx, "fold.search.beam")
