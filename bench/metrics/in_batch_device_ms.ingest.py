"""Device ms per micro-batch in in-batch cleanup: the bitmap-Jaccard
kernel and the greedy-leader sweep."""
from harness.reduce import Context, module_ms_per_batch


def read(ctx: Context) -> float | None:
    return module_ms_per_batch(ctx, ("bitmap_jaccard_matrix",
                                     "_greedy_sweep"))
