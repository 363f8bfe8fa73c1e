#!/usr/bin/env python3
"""Bring-up smoke test: the online dedup service on a TPU at real index size.

    python chip_smoke.py                # one chip (the default)
    python chip_smoke.py --four-chips   # the 4-chip sharded path only

One chip: a `DedupService` over the default "hnsw" backend, with the default
signature widths (112 hashes, T=4096 -> 128-word bitmaps, 5-gram shingles),
the Pallas kernels and a 2^21-slot index, ingests a seeded Common Crawl
shaped stream (`SyntheticCorpus`, 40.66% near-duplicates) through
submit / flush / results and the service's own micro-batcher. The run
passes only if:

  * on a batch from the stream, the MinHash kernel equals `minhash_ref`
    exactly and the bitmap-Jaccard kernel (cached and uncached) matches
    `bitmap_jaccard_ref` to 1e-6;
  * the kernels the service calls are compiled (`tpu_custom_call` in the
    compiled programs), not interpreted;
  * on the stream's prefix, the service's verdicts agree with the exact
    "brute" backend (same stream, same FoldConfig): recall >= 0.85 and
    false-positive rate <= 0.01;
  * read-only probes (`pipeline.query`) on admitted documents each find
    themselves;
  * the index never grows.

Four chips: the same stream through `ServiceConfig(shards=4)` (the
"hnsw_sharded" backend, 2^21 slots per shard) against one-chip "hnsw" in
the same process; verdict agreement must be >= 0.98 and each shard's state
must live on its own device.

Observations (compile seconds, docs/s, peak device bytes, jit cache counts)
go to earlier lines. The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
Without a TPU, or when any check fails, the script exits non-zero and
prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

CAPACITY = 1 << 21       # index slots (one chip; per shard with --four-chips)
DOCS = 1 << 17           # documents ingested on one chip
PREFIX = 1 << 14         # documents checked against brute; the 4-chip stream
SEED = 0                 # of the synthetic stream
CHUNK = 1024             # documents per submit (a multiple of max_batch)
N_PROBES = 16
MIN_RECALL, MAX_FP, MIN_AGREE = 0.85, 0.01, 0.98


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print(*parts, flush=True)


def fold_config(capacity: int):
    from repro.core.dedup import FoldConfig
    return FoldConfig(capacity=capacity, threshold_space="minhash",
                      use_kernel=True)


def make_stream(n: int):
    """(tokens (n, L) uint32, lengths (n,) int32) of the seeded Common
    Crawl preset, made in one bulk call."""
    from repro.data import DATASET_PRESETS, SyntheticCorpus
    cfg = dataclasses.replace(DATASET_PRESETS["common_crawl"], seed=SEED)
    tokens, lengths, _ = SyntheticCorpus(cfg).next_batch(n)
    return tokens, lengths


def padded(tokens, lengths, L: int):
    """Rows re-padded to length L (the service's largest length bucket)."""
    out = np.zeros((tokens.shape[0], L), np.uint32)
    w = min(L, tokens.shape[1])
    out[:, :w] = tokens[:, :w]
    return out, np.minimum(lengths, L).astype(np.int32)


# --------------------------------------------------------------- kernels
def check_kernels(fold, tokens, lengths) -> None:
    """Kernel outputs vs kernels/ref.py on one service-shaped batch."""
    import jax.numpy as jnp
    from repro.core import bitmap as bm
    from repro.core.hashing import hash_seeds
    from repro.core.shingle import shingle_hashes
    from repro.kernels import ops, ref

    sh = shingle_hashes(jnp.asarray(tokens), jnp.asarray(lengths),
                        fold.shingle_n)
    seeds = hash_seeds(fold.num_hashes, fold.seed)
    sig_k = np.asarray(ops.minhash(sh, seeds))
    sig_r = np.asarray(ref.minhash_ref(sh, seeds))
    n_bad = int((sig_k != sig_r).sum())
    say(f"kernel minhash {sig_k.shape}: {n_bad} lanes differ from minhash_ref")
    check(n_bad == 0, "MinHash kernel differs from minhash_ref")

    bitmaps = bm.pack_bitmaps(jnp.asarray(sig_r), T=fold.T)
    pcs = bm.popcount(bitmaps)
    want = np.asarray(ref.bitmap_jaccard_ref(bitmaps, bitmaps))
    for cached in (True, False):
        got = np.asarray(ops.bitmap_jaccard(
            bitmaps, bitmaps, pcs if cached else None,
            pcs if cached else None, cached=cached))
        err = float(np.abs(got - want).max())
        say(f"kernel bitmap_jaccard cached={cached} {got.shape}: "
            f"max |kernel - ref| = {err!r}")
        check(err <= 1e-6, f"bitmap-Jaccard kernel (cached={cached}) "
                           f"differs from bitmap_jaccard_ref by {err}")


def check_compiled(svc, tokens, lengths) -> None:
    """The kernels run compiled, not interpreted, as the service calls
    them: its signature stage and in-batch similarity, traced through the
    service's own code and compiled, contain Mosaic custom calls, and the
    arrays its eager signature stage makes resolve to compiled kernels."""
    import jax
    from repro.kernels import ops
    pipe = svc.pipeline
    sig = pipe.signatures(tokens, lengths)
    eager = not ops.default_interpret(sig.sigs)
    say(f"service arrays on {next(iter(sig.sigs.devices()))} resolve to "
        f"compiled kernels: {eager}")
    check(eager, "the service's arrays resolve to interpreted kernels")
    programs = {
        "signatures (MinHash)": jax.jit(pipe.signatures).lower(tokens,
                                                               lengths),
        "batch_sim (bitmap-Jaccard)": jax.jit(pipe.backend.batch_sim)
        .lower(sig),
    }
    for name, lowered in programs.items():
        has = "tpu_custom_call" in lowered.compile().as_text()
        say(f"compiled service {name} contains tpu_custom_call: {has}")
        check(has, f"service {name} program has no tpu_custom_call")


# ----------------------------------------------------------------- ingest
def ingest(svc, tokens, lengths, *, label: str, marks=()):
    """Stream documents through submit / flush / results in CHUNK-sized
    submits. At each mark (a multiple of CHUNK) everything so far is
    flushed and the elapsed time printed. Returns (admitted (n,) bool,
    {mark: seconds since start}, with n itself as the last mark); every
    time is bounded by block_until_ready on the index state."""
    import jax
    n = len(lengths)
    tickets, times = [], {}
    t0 = time.perf_counter()

    def settle(at: int) -> None:
        svc.flush()
        jax.block_until_ready(index_state(svc))
        times[at] = time.perf_counter() - t0
        say(f"{label}: {at} docs done at {times[at]!r} s")

    for s in range(0, n, CHUNK):
        if s in marks:
            settle(s)
        e = min(s + CHUNK, n)
        tickets.append(svc.submit(tokens[s:e], lengths[s:e]))
        if e % (16 * CHUNK) == 0:       # progress only: nothing waits here
            say(f"{label}: {e} docs submitted at "
                f"{time.perf_counter() - t0:.1f} s")
    settle(n)
    admitted = np.concatenate([[v.admitted for v in svc.results(t)]
                               for t in tickets])
    return admitted, times


def index_state(svc):
    """The backend's device state (None for host-side backends)."""
    be = svc.pipeline.backend
    return getattr(be, "state", getattr(be, "states", None))


def recall_fp(ref_admitted, admitted):
    ref_dup, dup = ~ref_admitted, ~admitted
    recall = float((dup & ref_dup).sum() / max(ref_dup.sum(), 1))
    fp = float((dup & ~ref_dup).sum() / max((~ref_dup).sum(), 1))
    return recall, fp


def check_against_brute(fold, tokens, lengths, admitted) -> None:
    from repro.service import DedupService, ServiceConfig
    ref_svc = DedupService(ServiceConfig(fold=fold, backend="brute"))
    ref_admitted, _ = ingest(ref_svc, tokens, lengths, label="brute")
    recall, fp = recall_fp(ref_admitted, admitted)
    say(f"vs brute on the first {len(lengths)} docs: recall={recall!r} "
        f"fp_rate={fp!r} (brute admitted {int(ref_admitted.sum())}, "
        f"hnsw admitted {int(admitted.sum())})")
    check(recall >= MIN_RECALL, f"recall {recall} < {MIN_RECALL}")
    check(fp <= MAX_FP, f"false-positive rate {fp} > {MAX_FP}")


def check_probes(svc, tokens, lengths, admitted) -> None:
    """Read-only queries on admitted documents: each must be flagged a
    duplicate of a stored vector identical to its own bitmap."""
    import jax.numpy as jnp
    rows = np.flatnonzero(admitted)[:N_PROBES]
    check(len(rows) == N_PROBES, "too few admitted documents to probe")
    toks, lens = padded(tokens[rows], lengths[rows],
                        svc.batcher.len_buckets[-1])
    res = svc.pipeline.query(toks, lens)
    sig = svc.pipeline.signatures(toks, lens)
    stored = np.asarray(svc.pipeline.backend.state.vectors[
        jnp.asarray(np.maximum(res.ids[:, 0], 0))])
    found = (res.is_dup & (res.ids[:, 0] >= 0) & (res.sims[:, 0] == 1.0)
             & (stored == np.asarray(sig.bitmaps)).all(axis=1))
    say(f"probes: {int(found.sum())} of {len(rows)} admitted docs found "
        f"themselves (top-1 sims {res.sims[:, 0].tolist()})")
    check(bool(found.all()), "a probe did not come back as itself")


# ----------------------------------------------------------------- phases
def one_chip(*, docs: int = DOCS, prefix: int = PREFIX,
             capacity: int = CAPACITY, compiled: bool = True) -> None:
    """The one-chip phase. A CPU rehearsal passes small sizes and
    `compiled=False`, which skips the compiled-kernel check."""
    import jax
    from repro.service import DedupService, ServiceConfig

    fold = fold_config(capacity)
    t0 = time.perf_counter()
    tokens, lengths = make_stream(docs)
    say(f"stream: {docs} common_crawl docs (seed {SEED}) made in "
        f"{time.perf_counter() - t0!r} s")
    svc = DedupService(ServiceConfig(fold=fold))
    B, L = svc.cfg.max_batch, svc.batcher.len_buckets[-1]
    batch = padded(tokens[:B], lengths[:B], L)
    check_kernels(fold, *batch)
    if compiled:
        check_compiled(svc, *batch)

    before = dict(svc.stats()["batching"]["compiled_programs"])
    warm = min(prefix, docs)
    admitted, times = ingest(svc, tokens, lengths, label="hnsw",
                             marks=(CHUNK, warm))
    stats = svc.stats()
    say(f"compile + warm-up: first {CHUNK} docs took {times[CHUNK]!r} s")
    n_steady = docs - warm
    if n_steady:
        secs = times[docs] - times[warm]
        say(f"steady state: docs {warm}..{docs} took {secs!r} s = "
            f"{n_steady / secs!r} docs/s")
    say(f"index: {stats['index']['count']} admitted of {docs} docs, "
        f"capacity {stats['index']['capacity']}, "
        f"grow events {stats['index']['grow_events']}")
    say(f"jit caches: compiled_programs "
        f"{stats['batching']['compiled_programs']} (before ingest "
        f"{before}), batch shapes {stats['batching']['compiled_shapes']}")
    check(stats["index"]["grow_events"] == 0, "the index grew during the run")
    check(stats["index"]["capacity"] == capacity, "capacity changed")

    check_against_brute(fold, tokens[:warm], lengths[:warm], admitted[:warm])
    check_probes(svc, tokens, lengths, admitted)
    mem = jax.devices()[0].memory_stats() or {}
    say(f"device memory: peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
        f"bytes_limit={mem.get('bytes_limit')}")


def four_chips(*, docs: int = PREFIX, capacity: int = CAPACITY) -> None:
    """The four-chip phase; `capacity` is per shard."""
    import jax
    from repro.service import DedupService, ServiceConfig

    check(len(jax.devices()) >= 4, f"--four-chips needs 4 devices, "
                                   f"found {len(jax.devices())}")
    fold = fold_config(capacity)
    tokens, lengths = make_stream(docs)
    one = DedupService(ServiceConfig(fold=fold))
    admitted1, times1 = ingest(one, tokens, lengths, label="hnsw (1 chip)")
    del one                  # free chip 0's index before its shard lands
    gc.collect()
    four = DedupService(ServiceConfig(fold=fold, shards=4))
    states = four.pipeline.backend.states
    devices = {sh.device for sh in states.vectors.addressable_shards}
    say(f"hnsw_sharded state on {len(devices)} devices "
        f"(device_set {len(states.vectors.sharding.device_set)}): "
        f"{sorted(str(d) for d in devices)}")
    check(len(states.vectors.sharding.device_set) == 4 and len(devices) == 4,
          "shard states do not live on 4 distinct devices")
    admitted4, times4 = ingest(four, tokens, lengths, label="hnsw_sharded")
    agree = float((admitted1 == admitted4).mean())
    say(f"{docs} docs: 1-chip hnsw admitted {int(admitted1.sum())} in "
        f"{times1[docs]!r} s; 4-chip hnsw_sharded admitted "
        f"{int(admitted4.sum())} in {times4[docs]!r} s (compiles included); "
        f"verdict agreement {agree!r}")
    check(agree >= MIN_AGREE, f"verdict agreement {agree} < {MIN_AGREE}")
    check(four.stats()["index"]["grow_events"] == 0, "the index grew")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded phase")
    args = ap.parse_args(argv)

    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev[0].platform}); "
              f"refusing to run on another backend", file=sys.stderr)
        return 2
    say(f"devices: {len(dev)} x {dev[0].device_kind}; compile cache {cache}")
    (four_chips if args.four_chips else one_chip)()
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
