"""One benchmark run of one cell: set-up, the measured window, the check.

    set-up   make the stream from the seed, build the service, prefill the
             index, warm every shape the mix can emit (all of it setup_s)
    window   the mix offers load for `seconds`; verdict times come from an
             outcome hook; nothing compiles (asserted)
    after    read peak device memory, flush, free the service, replay the
             run's micro-batches through the plain reference and compare

`run_cell` takes sizes as arguments (`overrides`), so the tests rehearse a
whole run on the CPU at a tiny size; `bench/run.py` is the chip entry.
"""
from __future__ import annotations

import copy
import dataclasses
import gc
import statistics
import sys
import time
from typing import Callable

import numpy as np

from corpus import CorpusConfig, SyntheticCorpus
from harness import traffic
from harness.spans import Spans
from harness.spec import Cell
from reference import compare as cmp
from reference import replay as ref_replay
from reference import signatures as ref_sig

__all__ = ["run_cell", "Run", "say", "merged_config"]

BLOCK = 256                     # rows per reference signature call
TRACE_AFTER_S = 1.0             # traced window starts this far in


def say(*parts) -> None:
    print(*parts, flush=True)


def merged_config(config: dict, overrides: dict | None) -> dict:
    """config with each group of `overrides` laid over it."""
    out = copy.deepcopy(config)
    for key, val in (overrides or {}).items():
        if isinstance(val, dict):
            out.setdefault(key, {}).update(val)
        else:
            out[key] = val
    return out


def bitmap_tau(fold: dict) -> float:
    """The similarity cut in bitmap space (the configuration's tau, in
    MinHash space, calibrated as tau / (2 - tau))."""
    tau = fold["tau"]
    if fold["threshold_space"] == "minhash":
        return tau / (2.0 - tau)
    return tau


class _Compiles:
    """Counts lowerings and backend compiles while `on`."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.on = False
        self.count = 0
        names = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT,
                 dispatch.BACKEND_COMPILE_EVENT}

        def listen(event, _secs, **_kw):
            if self.on and event in names:
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


_COMPILES: _Compiles | None = None


def _compile_counter() -> _Compiles:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = _Compiles()
    _COMPILES.on, _COMPILES.count = False, 0
    return _COMPILES


@dataclasses.dataclass
class Run:
    """What one run gathered; `result` is the benchmark's last line."""
    result: dict
    numbers: dict
    batches: list
    bitmaps: np.ndarray
    ref_admitted: np.ndarray
    tau: float
    window: dict


class _Recorder:
    """Outcome hook: one record per materialised micro-batch."""

    def __init__(self):
        self.batches: list[dict] = []

    def __call__(self, out) -> None:
        t = time.perf_counter()
        mb = out.batch
        v = mb.valid
        self.batches.append({
            "t": t, "B": int(mb.tokens.shape[0]), "L": int(mb.tokens.shape[1]),
            "doc_ids": np.asarray(mb.doc_ids[v]), "keep": out.keep[v],
            "batch_kept": out.keep_in_batch[v], "ids": out.ids[v],
            "sims": out.sims[v]})


def _service(cfg: dict):
    from repro.core.dedup import FoldConfig
    from repro.service import DedupService, ServiceConfig
    return DedupService(ServiceConfig(fold=FoldConfig(**cfg["fold"],
                                                      capacity=cfg["capacity"]),
                                      **cfg["service"]))


def _closed_chunks(svc, stream: traffic.Stream, n: int, chunk: int) -> None:
    left = n
    while left:
        s, e = stream.take(min(chunk, left))
        svc.submit(stream.tokens[s:e], stream.lengths[s:e])
        left -= e - s
    svc.flush()


def _device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": max((p for p in peak if p is not None),
                                     default=None)}


def _p99(values: list[float]) -> float:
    return statistics.quantiles(values, n=100, method="inclusive")[98] \
        if len(values) > 1 else values[0]


def reference_bitmaps(stream: traffic.Stream, n: int, fold: dict
                      ) -> np.ndarray:
    """Reference bitmaps of documents 0..n-1, as they were submitted."""
    import jax.numpy as jnp
    seeds = ref_sig.seeds(fold["num_hashes"], fold["seed"])
    out = np.zeros((n, fold["T"] // 32), np.uint32)
    for s in range(0, n, BLOCK):
        e = min(s + BLOCK, n)
        tok = np.zeros((BLOCK, stream.tokens.shape[1]), np.uint32)
        ln = np.zeros(BLOCK, np.int32)
        tok[:e - s] = stream.tokens[s:e]
        ln[:e - s] = stream.lengths[s:e]
        bm = ref_sig.doc_bitmaps(jnp.asarray(tok), jnp.asarray(ln), seeds,
                                 n=fold["shingle_n"], T=fold["T"])
        out[s:e] = np.asarray(bm)[:e - s]
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, overrides: dict | None = None,
             prepare: Callable | None = None) -> Run:
    """One run. `prepare(svc)` (tests only) may break the service before
    the prefill; `overrides` lays sizes over the configuration."""
    import jax
    cfg = merged_config(cell.config, overrides)
    mix = cell.mix
    fold = cfg["fold"]
    tau = bitmap_tau(fold)
    compiles = _compile_counter()

    # ------------------------------------------------------------ set-up
    t0 = time.perf_counter()
    prefill = cfg["prefill_docs"]
    if cfg["corpus"]["max_len"] > cfg["service"]["max_len"]:
        raise ValueError("documents longer than the service's max_len would "
                         "be truncated by it and not by the reference")
    svc = _service(cfg)
    n_warm = traffic.warm_docs(svc, mix)
    t_service = time.perf_counter() - t0
    n_stream = prefill + n_warm + traffic.window_docs(mix, seconds)
    corpus = CorpusConfig(**cfg["corpus"], seed=seed)
    tokens, lengths, _ = SyntheticCorpus(corpus).next_batch(n_stream)
    stream = traffic.Stream(tokens, lengths)
    n_refetch = traffic.refetch(stream, mix, seed, prefill + n_warm)
    say(f"service built in {t_service!r} s; stream of {n_stream} "
        f"{corpus.name} docs (seed {seed}, {n_refetch} exact re-fetches) "
        f"made in {time.perf_counter() - t0 - t_service!r} s")
    if prepare is not None:
        prepare(svc)
    spans = Spans()
    spans.wrap(svc.pipeline, "signatures", "signatures")
    spans.wrap(svc.pipeline, "dedup_step", "dedup_step")
    rec = _Recorder()
    svc.outcome_hooks.append(rec)

    t1 = time.perf_counter()
    _closed_chunks(svc, stream, prefill, mix.get("chunk_docs", 1024))
    t_prefill = time.perf_counter() - t1
    warmed = traffic.warm(svc, stream, mix)
    jax.block_until_ready(jax.tree.leaves(svc.pipeline.backend.state))
    t_warm = time.perf_counter() - t1 - t_prefill
    say(f"prefill: {prefill} docs in {t_prefill!r} s; warm-up of "
        f"{len(warmed)} extra shapes in {t_warm!r} s")
    stats0 = svc.stats()["batching"]
    shapes0 = set(svc.batcher.emitted_shapes)
    caches0 = dict(stats0["compiled_programs"])
    n_before = len(rec.batches)

    # ------------------------------------------------------------ window
    cap = None
    if trace:
        from harness.trace import Capture
        cap = Capture()
    trace_s = min(mix["trace_seconds"], max(seconds - 2 * TRACE_AFTER_S,
                                            0.5))
    w0 = time.perf_counter()
    setup_s = time.time() - t_start
    compiles.on = True

    def tick(now: float) -> None:
        if cap is None:
            return
        if not cap.started and now >= w0 + TRACE_AFTER_S:
            cap.start()
        elif cap.started and not cap.stopped and now >= cap.t0 + trace_s:
            cap.stop()

    reqs = traffic.drive(svc, stream, mix, seed, w0, seconds, spans, tick)
    w1 = time.perf_counter()
    if cap is not None and cap.started and not cap.stopped:
        cap.stop()
    compiles.on = False
    backlog = svc.backlog()
    device = _device_info(cell.chips)
    say(f"window: {w1 - w0!r} s, {len(reqs)} requests, backlog at close "
        f"{backlog} docs; peak_bytes_in_use {device['memory_peak_bytes']}")
    svc.flush()
    t_flushed = time.perf_counter()

    # ------------------------------------------- nothing compiled inside
    stats1 = svc.stats()["batching"]
    grew = (set(svc.batcher.emitted_shapes) != shapes0
            or dict(stats1["compiled_programs"]) != caches0)
    say(f"compiles in window: {compiles.count}; compiled_programs "
        f"{caches0} -> {dict(stats1['compiled_programs'])}; batch shapes "
        f"{sorted(shapes0)} -> {sorted(svc.batcher.emitted_shapes)}")
    if grew or compiles.count:
        raise RuntimeError("a program compiled inside the measured window")

    # ------------------------------------------------- end-to-end numbers
    done = np.full(stream.cursor, np.nan)
    for b in rec.batches:
        done[b["doc_ids"]] = b["t"]
    lat = [float(np.max(done[s:e]) - sched) for sched, s, e, _ in reqs
           if not np.isnan(done[s:e]).any()]
    late = [sub - sched for sched, _, _, sub in reqs]
    first = reqs[0][1] if reqs else stream.cursor
    in_window = done[first:stream.cursor]
    n_done = int(np.sum((in_window >= w0) & (in_window <= w0 + seconds)))
    win_batches = rec.batches[n_before:]
    t_done = [b["t"] - w0 for b in win_batches]
    say(f"verdict batches at {[round(t, 4) for t in t_done]} s from the "
        f"window's open ({len(t_done)} batches)")
    fill = (sum(len(b["doc_ids"]) for b in win_batches)
            / max(sum(b["B"] for b in win_batches), 1))
    if lat:
        say(f"requests: {len(lat)}; latency p50 "
            f"{statistics.median(lat) * 1e3!r} ms, p99 {_p99(lat) * 1e3!r}"
            f" ms, max {max(lat) * 1e3!r} ms; generator lateness p50 "
            f"{statistics.median(late) * 1e3!r} ms, max "
            f"{max(late) * 1e3!r} ms; flush after close "
            f"{t_flushed - w1!r} s; batch fill {fill!r}")

    # ------------------------------------------------ free, then reference
    index_count = svc.stats()["index"]["count"]
    svc.outcome_hooks.clear()
    del svc
    gc.collect()
    t2 = time.perf_counter()
    n_docs = stream.cursor
    bitmaps = reference_bitmaps(stream, n_docs, fold)
    order = [b["doc_ids"] for b in rec.batches]
    ref_adm, _ = ref_replay.replay(bitmaps, order, tau)
    numbers = cmp.compare(rec.batches, n_docs, bitmaps, ref_adm, tau)
    t_ref = time.perf_counter() - t2
    say(f"reference: {n_docs} docs in {len(order)} batches in {t_ref!r} s;"
        f" service admitted {index_count}, reference {int(ref_adm.sum())};"
        f" false_drop_rate {numbers['false_drop_rate']!r}")
    correct, checks = cmp.judge(numbers, cell.limits)

    values = {"setup_s": setup_s,
              "docs_per_s": n_done / seconds,
              "verdict_p99_ms": _p99(lat) * 1e3 if lat else None,
              "recall": numbers["recall"],
              "false_drop_rate": numbers["false_drop_rate"]}
    result: dict = {"correct": correct,
                    "attempted": int(sum(e - s for _, s, e, _ in reqs)),
                    "failed": int(np.isnan(in_window).sum()),
                    "metrics": {}, "device": device}
    if not trace:
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        from harness import reduce as red
        from harness.trace import load
        try:
            tr = load(cap.path())
        finally:
            cap.close()
        ctx = red.Context(trace=tr, batches=win_batches, mix=mix,
                          config=cfg)
        for entry, reader in cell.per_layer:
            v = reader(ctx)
            if v is not None:
                result["metrics"][entry["name"]] = {"value": v,
                                                    "unit": entry["unit"]}
        busy = red.busy_s(ctx)
        device["busy_s"] = busy
        device["window_s"] = tr.window_s
        result["breakdown"] = red.breakdown(ctx)
    result["checks"] = checks
    window = {"backlog_at_close": backlog, "requests": len(reqs),
              "docs_done": n_done, "flush_s": t_flushed - w1,
              "lateness_max_ms": max(late) * 1e3 if late else None,
              "reference_s": t_ref, "batch_fill": fill, **values}
    return Run(result=result, numbers=numbers, batches=rec.batches,
               bitmaps=bitmaps, ref_admitted=ref_adm, tau=tau,
               window=window)


def print_checks(checks: dict) -> None:
    """The compared numbers beside their limits, as the last stderr lines."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} {c['need']} {c['limit']!r}",
              file=sys.stderr, flush=True)
