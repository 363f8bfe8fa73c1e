"""Device self time per micro-batch in phase B, the scan that writes rows and
back-links (fold.insert.commit)."""
from harness.phases import scope_ms_per_batch
from harness.reduce import Context


def read(ctx: Context) -> float | None:
    return scope_ms_per_batch(ctx, "fold.insert.commit")
