"""Public jit'd entry points for the Pallas kernels.

`use_kernel` selects the Pallas path ('SIMD' in the paper's ablation, Fig. 8)
vs the pure-jnp oracle; `interpret` runs the kernel body in Python. Left
unset, it follows the backend the arrays live on: interpreted on CPU,
compiled Mosaic everywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.bitmap_jaccard import bitmap_jaccard_matrix, hamming_matrix
from repro.kernels.minhash import minhash_kernel_signatures

__all__ = ["bitmap_jaccard", "hamming", "minhash", "default_interpret"]


def default_interpret(x=None) -> bool:
    """True iff a kernel over `x` must run interpreted, i.e. on CPU.

    A concrete device array answers with its own platform; host arrays and
    tracers run on the default backend."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        platform = next(iter(x.devices())).platform
    else:
        platform = jax.default_backend()
    return platform == "cpu"


def bitmap_jaccard(qs: jnp.ndarray, db: jnp.ndarray,
                   pq: jnp.ndarray | None = None,
                   pb: jnp.ndarray | None = None,
                   *, cached: bool = True, use_kernel: bool = True,
                   interpret: bool | None = None) -> jnp.ndarray:
    """(Q, W) x (N, W) packed bitmaps -> (Q, N) f32 similarity matrix."""
    if not use_kernel:
        if not cached:
            pq = pb = None  # force on-the-fly popcounts (ablation arm)
        return ref.bitmap_jaccard_ref(qs, db, pq, pb)
    itp = default_interpret(qs) if interpret is None else interpret
    return bitmap_jaccard_matrix(qs, db, pq, pb, cached=cached, interpret=itp)


def hamming(qs: jnp.ndarray, db: jnp.ndarray, *, use_kernel: bool = True,
            interpret: bool | None = None) -> jnp.ndarray:
    if not use_kernel:
        return ref.hamming_ref(qs, db)
    itp = default_interpret(qs) if interpret is None else interpret
    return hamming_matrix(qs, db, interpret=itp)


def minhash(shingles: jnp.ndarray, seeds: jnp.ndarray, *,
            use_kernel: bool = True, interpret: bool | None = None) -> jnp.ndarray:
    if not use_kernel:
        return ref.minhash_ref(shingles, seeds)
    itp = default_interpret(shingles) if interpret is None else interpret
    return minhash_kernel_signatures(shingles, seeds, interpret=itp)
