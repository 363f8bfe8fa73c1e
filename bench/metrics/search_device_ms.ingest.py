"""Device ms per micro-batch in the batched HNSW search."""
from harness.reduce import Context, module_ms_per_batch


def read(ctx: Context) -> float | None:
    return module_ms_per_batch(ctx, ("hnsw_search",))
