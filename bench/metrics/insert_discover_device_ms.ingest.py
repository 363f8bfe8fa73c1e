"""Device self time per micro-batch in phase A, candidate discovery against the
pre-batch graph (fold.insert.discover)."""
from harness.phases import scope_ms_per_batch
from harness.reduce import Context


def read(ctx: Context) -> float | None:
    return scope_ms_per_batch(ctx, "fold.insert.discover")
