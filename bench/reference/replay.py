"""Exact online admission over the run's own micro-batches.

The plain reference for a dedup run. It replays, in order, the
micro-batches the service dispatched (their composition depends on timing,
so it is recorded, not re-derived) and decides every document exactly:

* in-batch cleanup: greedy leader in row order; a row is a batch
  duplicate when an earlier kept row of the batch has similarity >= tau;
* index check: a row is an index duplicate when any document the reference
  admitted in an earlier batch has similarity >= tau, found by scanning
  all of them in chunks on the device (no approximate search);
* admitted = not a batch duplicate and not an index duplicate.

Similarity is bitmap-Jaccard, I / U = (pa + pb - px) / (pa + pb + px) with
popcounts pa, pb and px = popcount(a xor b). The cut "float32(I/U) >=
float32(tau)" is decided in integers through a table: thr[u] is the least
2I for which the correctly rounded float32 quotient reaches float32(tau),
so no device division takes part.

Nothing here imports the program. `signatures.doc_bitmaps` makes the
bitmaps from the documents the benchmark itself submitted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["threshold_table", "replay", "pair_sims", "popcount_rows",
           "row_buckets"]

CHUNK = 512          # admitted rows scanned per loop step
CAP_STEP = 1 << 14   # the admitted buffer grows in these steps (few shapes)


def threshold_table(tau: float, max_union2: int) -> np.ndarray:
    """thr[u] = least i in [0, u] with f32(i) / f32(u) >= f32(tau), u + 1
    when there is none; thr[0] = 0 (two empty bitmaps are identical)."""
    t = np.float32(tau)
    thr = np.zeros(max_union2 + 1, np.int32)
    for u in range(1, max_union2 + 1):
        i = max(int(np.floor(float(t) * u)) - 2, 0)
        while i <= u and np.float32(i) / np.float32(u) < t:
            i += 1
        thr[u] = i
    return thr


def popcount_rows(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words).sum(axis=-1, dtype=np.int32)


def pair_sims(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Exact float32 bitmap-Jaccard of row pairs (..., W) x (..., W)."""
    pa, pb = popcount_rows(a), popcount_rows(b)
    px = popcount_rows(a ^ b)
    u2 = (pa + pb + px).astype(np.float32)
    i2 = (pa + pb - px).astype(np.float32)
    with np.errstate(divide="ignore", invalid="ignore"):
        return np.where(u2 > 0, i2 / np.maximum(u2, 1), np.float32(1.0))


def row_buckets(n: int) -> int:
    """Rows a batch of n is padded to (powers of two from 8)."""
    b = 8
    while b < n:
        b *= 2
    return b


def _pc(x):
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def _dup(q, qpc, rows, rpc, thr):
    """(Q, R) bool: pair similarity reaches the cut."""
    px = _pc(q[:, None, :] ^ rows[None, :, :])
    s = qpc[:, None] + rpc[None, :]
    return (s - px) >= thr[s + px]


@jax.jit
def _judge(adm, adm_pc, count, q, thr):
    """Index duplicates of q against the first `count` admitted rows, and
    q's own pairwise duplicate matrix."""
    qpc = _pc(q)

    def body(c, acc):
        rows = jax.lax.dynamic_slice_in_dim(adm, c * CHUNK, CHUNK)
        rpc = jax.lax.dynamic_slice_in_dim(adm_pc, c * CHUNK, CHUNK)
        live = c * CHUNK + jnp.arange(CHUNK) < count
        return acc | jnp.any(_dup(q, qpc, rows, rpc, thr) & live[None],
                             axis=1)

    n_chunks = (count + CHUNK - 1) // CHUNK
    index_dup = jax.lax.fori_loop(0, n_chunks, body,
                                  jnp.zeros(q.shape[0], bool))
    return index_dup, _dup(q, qpc, q, qpc, thr)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _append(adm, adm_pc, count, q, keep):
    slot = jnp.where(keep, count + jnp.cumsum(keep) - 1, adm.shape[0])
    adm = adm.at[slot].set(q, mode="drop")
    adm_pc = adm_pc.at[slot].set(_pc(q), mode="drop")
    return adm, adm_pc, count + jnp.sum(keep, dtype=jnp.int32)


def greedy(dup: np.ndarray) -> np.ndarray:
    """Greedy leader over an (n, n) duplicate matrix, rows in order."""
    n = dup.shape[0]
    keep = np.zeros(n, bool)
    for i in range(n):
        keep[i] = not (dup[i, :i] & keep[:i]).any()
    return keep


def replay(bitmaps: np.ndarray, batches: list[np.ndarray], tau: float
           ) -> tuple[np.ndarray, np.ndarray]:
    """Exact verdicts for every document of `batches`.

    bitmaps: (N, W) uint32, row d the bitmap of document d.
    batches: document ids of each micro-batch, in dispatch order and row
    order. Returns (admitted (N,), batch_kept (N,)) booleans; documents in
    no batch read False in both."""
    N, W = bitmaps.shape
    thr = jnp.asarray(threshold_table(tau, 4 * 32 * W))
    cap = -(-max(N, 1) // CAP_STEP) * CAP_STEP
    adm = jnp.zeros((cap, W), jnp.uint32)
    adm_pc = jnp.zeros((cap,), jnp.int32)
    count = jnp.int32(0)
    admitted = np.zeros(N, bool)
    batch_kept = np.zeros(N, bool)
    for ids in batches:
        n = len(ids)
        if n == 0:
            continue
        q = np.zeros((row_buckets(n), W), np.uint32)
        q[:n] = bitmaps[ids]
        q = jnp.asarray(q)
        index_dup, dup = _judge(adm, adm_pc, count, q, thr)
        kept = greedy(np.asarray(dup)[:n, :n])
        keep = kept & ~np.asarray(index_dup)[:n]
        batch_kept[ids] = kept
        admitted[ids] = keep
        pad = np.zeros(q.shape[0], bool)
        pad[:n] = keep
        adm, adm_pc, count = _append(adm, adm_pc, count, q, jnp.asarray(pad))
    return admitted, batch_kept
