"""The comparison that decides `correct`.

Inputs are the run's answers as the service produced them, one record per
materialised micro-batch in dispatch order (doc ids, kept, batch-kept,
top-k neighbour slots and their similarities), the reference's bitmaps of
every submitted document, and the reference's exact verdicts from
`replay.replay` over the same micro-batches. Every answered document is
compared. The numbers:

recall            of the documents the reference drops, the share the
                  service drops (limit: the configuration's guarantee)
false_drop_rate   of the documents the reference admits, the share the
                  service drops (reported, not judged)
batch_flips       documents whose in-batch verdict breaks the greedy leader
                  rule on the same batch: kept although an earlier kept row
                  of the batch is a duplicate of it, or dropped although
                  none is. Exact similarities decide; a pair whose exact
                  similarity is within TIE_ULPS float32 steps of the cut
                  may count either way, since the device's float32
                  division need not round as the host's does. (Distinct
                  similarities I/U with U <= 2 x 112 lie far more than
                  that apart, so only exact ties are affected.)
sim_gap           largest |reported similarity - exact bitmap-Jaccard| over
                  every reported neighbour, the neighbour being the document
                  the service admitted into that slot
bad_neighbours    reported slots that held no document admitted before the
                  query's batch
inconsistent      documents whose verdict does not follow from their own
                  batch verdict and reported similarities
unanswered        submitted documents that never got a verdict

Slots are the service's admission order: with nothing deleted, the index
gives admitted documents consecutive slots in batch and row order.
"""
from __future__ import annotations

import numpy as np

from reference.replay import pair_sims

__all__ = ["compare", "judge", "TIE_ULPS"]

TIE_ULPS = 4


def _greedy_breaks(kept: np.ndarray, sims: np.ndarray, cut: np.float32
                   ) -> int:
    """Rows of one batch whose kept flag breaks the greedy leader rule
    under the exact similarities `sims`, ties at the cut counting either
    way."""
    band = TIE_ULPS * np.spacing(cut)
    n = len(kept)
    earlier_kept = np.tril(np.ones((n, n), bool), -1) & kept[None, :]
    surely = (earlier_kept & (sims > cut + band)).any(axis=1)
    maybe = (earlier_kept & (sims >= cut - band)).any(axis=1)
    return int((kept & surely).sum() + (~kept & ~maybe).sum())


def compare(batches: list[dict], n_docs: int, bitmaps: np.ndarray,
            ref_admitted: np.ndarray, tau: float) -> dict:
    """The numbers above, as floats."""
    answered = np.zeros(n_docs, bool)
    keep = np.zeros(n_docs, bool)
    slot_doc, slot_batch = [], []
    cut = np.float32(tau)
    inconsistent = flips = 0
    for b, rec in enumerate(batches):
        ids = rec["doc_ids"]
        answered[ids] = True
        keep[ids] = rec["keep"]
        bm = bitmaps[ids]
        flips += _greedy_breaks(rec["batch_kept"],
                                pair_sims(bm[:, None, :], bm[None, :, :]),
                                cut)
        want = rec["batch_kept"] & ~(rec["sims"] >= cut).any(axis=1)
        inconsistent += int((want != rec["keep"]).sum())
        slot_doc.append(ids[rec["keep"]])
        slot_batch.append(np.full(int(rec["keep"].sum()), b))
    slot_doc = np.concatenate(slot_doc) if slot_doc else np.zeros(0, int)
    slot_batch = (np.concatenate(slot_batch) if slot_batch
                  else np.zeros(0, int))

    gap, bad = 0.0, 0
    for b, rec in enumerate(batches):
        slots = rec["ids"]
        used = slots >= 0
        ok = used & (slots < len(slot_doc))
        ok[ok] = slot_batch[slots[ok]] < b
        bad += int((used & ~ok).sum())
        if ok.any():
            rows = np.broadcast_to(rec["doc_ids"][:, None], slots.shape)
            exact = pair_sims(bitmaps[rows[ok]], bitmaps[slot_doc[slots[ok]]])
            gap = max(gap, float(np.abs(rec["sims"][ok] - exact).max()))

    a = answered
    ref_drop = ~ref_admitted & a
    ref_keep = ref_admitted & a
    drop = ~keep & a
    return {
        "recall": float((drop & ref_drop).sum() / max(ref_drop.sum(), 1)),
        "false_drop_rate": float((drop & ref_keep).sum()
                                 / max(ref_keep.sum(), 1)),
        "batch_flips": float(flips),
        "sim_gap": gap,
        "bad_neighbours": float(bad),
        "inconsistent": float(inconsistent),
        "unanswered": float(n_docs - a.sum()),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit", "need"}}) over the limits given;
    a limit is {"max": x} or {"min": x}."""
    checks, ok = {}, True
    for name, lim in limits.items():
        v = numbers[name]
        if "min" in lim:
            good, need, bound = v >= lim["min"], ">=", lim["min"]
        else:
            good, need, bound = v <= lim["max"], "<=", lim["max"]
        ok = ok and bool(good)
        checks[name] = {"value": v, "limit": bound, "need": need}
    return ok, checks
