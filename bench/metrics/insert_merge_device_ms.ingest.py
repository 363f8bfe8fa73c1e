"""Device self time per micro-batch in the batch distances, the slot writes and
the candidate merge (fold.insert.merge)."""
from harness.phases import scope_ms_per_batch
from harness.reduce import Context


def read(ctx: Context) -> float | None:
    return scope_ms_per_batch(ctx, "fold.insert.merge")
