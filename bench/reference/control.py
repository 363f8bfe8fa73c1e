"""The control: the reference put in the program's place, in bfloat16.

It serves the same micro-batches as the program did and answers in the
program's own form (kept, batch-kept, top-k neighbour slots and their
similarities), but computes every similarity as bf16(2I) / bf16(2U) in
bfloat16, the precision a later change might reach for, and cuts at
bf16(tau). The quotient is rounded to bfloat16 with `reduce_precision`,
which XLA may not drop as excess precision (a plain bfloat16 divide that
is cast back to float32 came out within 1.3e-5 of float32 on the chip).
Its search is an exact scan, so only the precision differs. The benchmark's comparison has
to find it not correct; `bench/tests/test_bench_control.py` keeps that so,
and `bench/readings.py --control` reads it on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference.replay import CAP_STEP, CHUNK, greedy, row_buckets

__all__ = ["control_outcomes"]


def _pc(x):
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def _sim16(q, qpc, rows, rpc):
    px = _pc(q[:, None, :] ^ rows[None, :, :])
    s = qpc[:, None] + rpc[None, :]
    u = (s + px).astype(jnp.bfloat16)
    i = (s - px).astype(jnp.bfloat16)
    q = jax.lax.reduce_precision(i / jnp.maximum(u, jnp.bfloat16(1)),
                                 exponent_bits=8, mantissa_bits=7)
    return jnp.where(u > 0, q, jnp.bfloat16(1))


@jax.jit
def _serve(adm, adm_pc, count, q, k_probe):
    """Top-k admitted neighbours of q in bf16, and q's pairwise bf16 sims."""
    k = k_probe.shape[0]
    qpc = _pc(q)
    B = q.shape[0]

    def body(c, best):
        bs, bi = best
        rows = jax.lax.dynamic_slice_in_dim(adm, c * CHUNK, CHUNK)
        rpc = jax.lax.dynamic_slice_in_dim(adm_pc, c * CHUNK, CHUNK)
        slot = c * CHUNK + jnp.arange(CHUNK, dtype=jnp.int32)
        s = jnp.where(slot[None] < count,
                      _sim16(q, qpc, rows, rpc).astype(jnp.float32), -jnp.inf)
        cs = jnp.concatenate([bs, s], axis=1)
        ci = jnp.concatenate([bi, jnp.broadcast_to(slot, (B, CHUNK))], axis=1)
        top, at = jax.lax.top_k(cs, k)
        return top, jnp.take_along_axis(ci, at, axis=1)

    init = (jnp.full((B, k), -jnp.inf, jnp.float32),
            jnp.full((B, k), -1, jnp.int32))
    n_chunks = (count + CHUNK - 1) // CHUNK
    sims, ids = jax.lax.fori_loop(0, n_chunks, body, init)
    ids = jnp.where(jnp.isfinite(sims), ids, -1)
    return ids, sims, _sim16(q, qpc, q, qpc).astype(jnp.float32)


@jax.jit
def _append(adm, adm_pc, count, q, keep):
    slot = jnp.where(keep, count + jnp.cumsum(keep) - 1, adm.shape[0])
    adm = adm.at[slot].set(q, mode="drop")
    adm_pc = adm_pc.at[slot].set(_pc(q), mode="drop")
    return adm, adm_pc, count + jnp.sum(keep, dtype=jnp.int32)


def control_outcomes(bitmaps: np.ndarray, batches: list[np.ndarray],
                     tau: float, k: int) -> list[dict]:
    """One outcome record per batch, in the form the harness records the
    program's: doc_ids, keep, batch_kept, ids (slots), sims."""
    N, W = bitmaps.shape
    cut = np.float32(jnp.bfloat16(tau))
    cap = -(-max(N, 1) // CAP_STEP) * CAP_STEP
    adm = jnp.zeros((cap, W), jnp.uint32)
    adm_pc = jnp.zeros((cap,), jnp.int32)
    count = jnp.int32(0)
    probe = jnp.zeros((k,), jnp.int32)
    out = []
    for ids in batches:
        n = len(ids)
        if n == 0:
            continue
        q = np.zeros((row_buckets(n), W), np.uint32)
        q[:n] = bitmaps[ids]
        q = jnp.asarray(q)
        nb, ns, inner = _serve(adm, adm_pc, count, q, probe)
        kept = greedy(np.asarray(inner)[:n, :n] >= cut)
        ns = np.asarray(ns)[:n]
        keep = kept & ~(ns >= cut).any(axis=1)
        out.append({"doc_ids": np.asarray(ids), "keep": keep,
                    "batch_kept": kept, "ids": np.asarray(nb)[:n],
                    "sims": ns})
        pad = np.zeros(q.shape[0], bool)
        pad[:n] = keep
        adm, adm_pc, count = _append(adm, adm_pc, count, q, jnp.asarray(pad))
    return out
