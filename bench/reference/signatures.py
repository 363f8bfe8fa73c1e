"""Plain FOLD signatures: shingle hashes, MinHash lanes, folded bitmaps.

The semantics of the signature stage written out once more, in plain
jax.numpy, so that the benchmark's reference does not move when the
program's kernels do:

* shingle i of a document is the 5-gram tokens[i:i+n], hashed with a
  polynomial roll (multiplier 0x01000193, +1 per token) and the murmur3
  finaliser; a document shorter than n has one whole-document shingle;
* lane h of the MinHash signature is the least fmix32((s ^ seed_h) * phi
  + seed_h) over the document's shingles, with seed_h =
  fmix32(h * phi + base_seed);
* the bitmap sets bit (lane mod T) for every lane, packed 32 bits a word
  with bit b of word w standing for position 32 w + b.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["doc_bitmaps", "seeds"]

_PHI = np.uint32(0x9E3779B9)
_POLY = np.uint32(0x01000193)
_MAX = np.uint32(0xFFFFFFFF)


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def seeds(num_hashes: int, base_seed: int) -> jnp.ndarray:
    """(H,) uint32 seeds of the MinHash family."""
    h = jnp.arange(num_hashes, dtype=jnp.uint32)
    return _fmix32(h * _PHI + jnp.uint32(base_seed))


@functools.partial(jax.jit, static_argnames=("n", "T"))
def doc_bitmaps(tokens: jnp.ndarray, lengths: jnp.ndarray, lane_seeds,
                *, n: int, T: int) -> jnp.ndarray:
    """(R, L) uint32 tokens, zero past each length -> (R, T/32) bitmaps."""
    R, L = tokens.shape
    pos = jnp.arange(L)
    sh = jnp.zeros((R, L), jnp.uint32)
    for k in range(n):
        nxt = jnp.where(pos + k < L, jnp.take(tokens, pos + k, axis=1,
                                              mode="clip"), 0)
        sh = sh * _POLY + nxt.astype(jnp.uint32) + jnp.uint32(1)
    sh = _fmix32(sh)
    count = jnp.where(lengths >= n, lengths - n + 1, jnp.minimum(lengths, 1))
    live = pos[None, :] < count[:, None]

    def lane(seed):
        v = _fmix32((sh ^ seed) * _PHI + seed)
        return jnp.min(jnp.where(live, v, _MAX), axis=1)      # (R,)

    sig = jax.lax.map(lane, lane_seeds).T                     # (R, H)
    bit = sig % jnp.uint32(T)
    word = (bit // 32).astype(jnp.int32)                      # (R, H)
    one = jnp.uint32(1) << (bit % 32)
    W = T // 32
    hit = word[:, :, None] == jnp.arange(W, dtype=jnp.int32)  # (R, H, W)
    return jax.lax.reduce(jnp.where(hit, one[:, :, None], jnp.uint32(0)),
                          jnp.uint32(0), jax.lax.bitwise_or, (1,))
