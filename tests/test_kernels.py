"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.hashing import hash_seeds
from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("q,n,w", [(1, 1, 4), (8, 128, 128), (13, 201, 128),
                                   (5, 7, 64), (128, 256, 32), (3, 130, 16)])
@pytest.mark.parametrize("cached", [True, False])
def test_bitmap_jaccard_matches_ref(q, n, w, cached):
    qs = jnp.asarray(RNG.integers(0, 2**32, (q, w), dtype=np.uint32))
    db = jnp.asarray(RNG.integers(0, 2**32, (n, w), dtype=np.uint32))
    out = ops.bitmap_jaccard(qs, db, cached=cached, interpret=True)
    exp = ref.bitmap_jaccard_ref(qs, db)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-6)


def test_bitmap_jaccard_sparse_and_empty():
    # empty-vs-empty bitmaps must score 1.0 (identical empty sets)
    qs = jnp.zeros((4, 16), jnp.uint32)
    db = jnp.zeros((6, 16), jnp.uint32)
    out = np.asarray(ops.bitmap_jaccard(qs, db, interpret=True))
    np.testing.assert_allclose(out, 1.0)
    # identical non-empty -> 1.0; disjoint -> 0.0
    a = jnp.asarray([[0b1010, 0, 0, 0]], jnp.uint32)
    b = jnp.asarray([[0b0101, 0, 0, 0]], jnp.uint32)
    self_sim = np.asarray(ops.bitmap_jaccard(a, a, interpret=True))[0, 0]
    cross = np.asarray(ops.bitmap_jaccard(a, b, interpret=True))[0, 0]
    assert self_sim == 1.0 and cross == 0.0


@pytest.mark.parametrize("q,n,w", [(8, 128, 128), (9, 33, 16), (1, 1, 4)])
def test_hamming_matches_ref(q, n, w):
    qs = jnp.asarray(RNG.integers(0, 2**32, (q, w), dtype=np.uint32))
    db = jnp.asarray(RNG.integers(0, 2**32, (n, w), dtype=np.uint32))
    out = ops.hamming(qs, db, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.hamming_ref(qs, db)), rtol=1e-6)


@pytest.mark.parametrize("b,l,h", [(1, 4, 7), (5, 300, 112), (16, 128, 128),
                                   (9, 513, 64), (2, 16, 1)])
def test_minhash_matches_ref(b, l, h):
    sh = RNG.integers(0, 2**32, (b, l), dtype=np.uint32)
    sh[0, l // 2:] = 0xFFFFFFFF  # padded shingles
    seeds = hash_seeds(h)
    out = ops.minhash(jnp.asarray(sh), seeds, interpret=True)
    exp = ref.minhash_ref(jnp.asarray(sh), seeds)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))
    assert out.dtype == jnp.uint32


def test_minhash_all_padded_row():
    sh = np.full((3, 32), 0xFFFFFFFF, dtype=np.uint32)
    seeds = hash_seeds(8)
    out = np.asarray(ops.minhash(jnp.asarray(sh), seeds, interpret=True))
    assert (out == 0xFFFFFFFF).all()   # empty docs keep the sentinel


def test_kernel_vs_jnp_paths_agree():
    """ops.* with use_kernel=False (jnp oracle) equals the kernel path."""
    qs = jnp.asarray(RNG.integers(0, 2**32, (12, 128), dtype=np.uint32))
    db = jnp.asarray(RNG.integers(0, 2**32, (40, 128), dtype=np.uint32))
    a = ops.bitmap_jaccard(qs, db, use_kernel=True, interpret=True)
    b = ops.bitmap_jaccard(qs, db, use_kernel=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_interpret_mode_follows_cpu_backend():
    """Unset `interpret` resolves from the arrays' backend: on CPU the
    kernels must run interpreted (Mosaic only compiles for a TPU)."""
    import jax
    assert jax.default_backend() == "cpu"
    assert ops.default_interpret() is True
    assert ops.default_interpret(jnp.zeros((8, 128), jnp.uint32)) is True
    assert ops.default_interpret(np.zeros((8, 128), np.uint32)) is True
    # and the default path still matches the oracle bit for bit
    sh = jnp.asarray(RNG.integers(0, 2**32, (8, 128), dtype=np.uint32))
    seeds = hash_seeds(16)
    np.testing.assert_array_equal(np.asarray(ops.minhash(sh, seeds)),
                                  np.asarray(ref.minhash_ref(sh, seeds)))
