"""Device ms per micro-batch in the MinHash kernel and bitmap packing."""
from harness.reduce import Context, module_ms_per_batch


def read(ctx: Context) -> float | None:
    return module_ms_per_batch(ctx, ("minhash_kernel_signatures",
                                     "pack_bitmaps"))
