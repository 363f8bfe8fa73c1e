"""Pallas TPU kernel: MinHash signature min-reduction.

Computes sig[b, h] = min over shingles l of F_h(shingle[b, l]) with the
seeded murmur-mix hash family from core.hashing. Signature generation is the
single largest stage in the paper's breakdown (Fig. 7: ~48 s per 100K docs),
so it earns a kernel.

Tiling: grid (B/TB, H/TH, L/TL) with the shingle dim innermost so the output
tile acts as a VMEM accumulator: at l==0 it is initialized to the largest key
and every l-step folds a (TB, TH, TL) hashed block into a running minimum.
TB=8, TH=128, TL=128 -> the hashed intermediate is (8,128,128) u32 = 512 KiB.

Note on dtypes: the min-reduction must be *unsigned*, but Mosaic implements
no reduction over unsigned integers. The kernel therefore reduces order-
preserving int32 keys (the hash with its sign bit flipped, bitcast): signed
order on the keys is unsigned order on the hashes, so the result is
bit-identical once the wrapper flips the bit back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["minhash_kernel_signatures", "TB", "TH", "TL"]

TB = 8    # docs per tile
TH = 128  # hash functions per tile
TL = 128  # shingles per tile

# numpy scalars (not jnp) so the kernel body does not capture traced consts.
UINT32_MAX = np.uint32(0xFFFFFFFF)
_SIGN = np.uint32(0x80000000)
_KEY_MAX = np.int32(0x7FFFFFFF)   # the key of UINT32_MAX
_GOLDEN = np.uint32(0x9E3779B9)
_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)


def _fmix32(x):
    x = x ^ (x >> 16)
    x = x * _C1
    x = x ^ (x >> 13)
    x = x * _C2
    x = x ^ (x >> 16)
    return x


def _minhash_kernel(sh_ref, seed_ref, out_ref):
    l_idx = pl.program_id(2)

    @pl.when(l_idx == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _KEY_MAX)

    sh = sh_ref[...]                    # (TB, TL) uint32
    seeds = seed_ref[...]               # (TH, 1)  uint32
    valid = sh != UINT32_MAX            # (TB, TL)
    # (TB, TH, TL): hash every shingle under every seed in the tile.
    expanded = sh[:, None, :] ^ seeds.reshape(1, -1, 1)
    hashed = _fmix32(expanded * _GOLDEN + seeds.reshape(1, -1, 1))
    hashed = jnp.where(valid[:, None, :], hashed, UINT32_MAX)
    keys = jax.lax.bitcast_convert_type(hashed ^ _SIGN, jnp.int32)
    tile_min = jnp.min(keys, axis=-1)    # (TB, TH)
    out_ref[...] = jnp.minimum(out_ref[...], tile_min)


def _pad_to(x, mult, axis, fill):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=jnp.asarray(fill, dtype=x.dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def minhash_kernel_signatures(shingles: jnp.ndarray, seeds: jnp.ndarray, *,
                              interpret: bool = False) -> jnp.ndarray:
    """(B, L) uint32 shingle hashes (UINT32_MAX = pad) x (H,) seeds
    -> (B, H) uint32 signatures. Matches kernels.ref.minhash_ref."""
    B, L = shingles.shape
    H = seeds.shape[0]
    sh_p = _pad_to(shingles.astype(jnp.uint32), TB, 0, int(UINT32_MAX))
    sh_p = _pad_to(sh_p, TL, 1, int(UINT32_MAX))
    seeds_p = _pad_to(seeds.astype(jnp.uint32), TH, 0, 0)
    Bp, Lp = sh_p.shape
    Hp = seeds_p.shape[0]
    grid = (Bp // TB, Hp // TH, Lp // TL)
    out = pl.pallas_call(
        _minhash_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((TB, TL), lambda b, h, l: (b, l)),
                  pl.BlockSpec((TH, 1), lambda b, h, l: (h, 0))],
        out_specs=pl.BlockSpec((TB, TH), lambda b, h, l: (b, h)),
        out_shape=jax.ShapeDtypeStruct((Bp, Hp), jnp.int32),
        interpret=interpret,
    )(sh_p, seeds_p[:, None])
    return jax.lax.bitcast_convert_type(out[:B, :H], jnp.uint32) ^ _SIGN
