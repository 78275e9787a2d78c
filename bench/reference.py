"""The plain reference that decides ``correct``: host numpy, independent
of the program (it imports nothing from ``repro``) and of the benchmark's
device code.

For one query it encodes the spectrum by Eq. 1 straight from the
codebooks, finds its candidates by the window rule over the library's
precursors, scores every candidate by XOR+popcount, and keeps the top k
with the deployment's tie order: the bank is laid out as the decoy block
then the target block, each sorted by precursor (stable), and a tie goes
to the lower position in that layout. Rows are reported in the original
numbering (decoy i is row i, target j is row N + j). Target-decoy FDR is
then applied over each batch as the server dispatched it.

The control is the same search on the first half of every hypervector
(scores doubled): the "fewer dimensions" shortcut that an approximate
search would take. It breaks the exact-top-k guarantee; put in the
program's place, its answers have to come out as not correct.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses

import numpy as np

from work import window_ranges

SENTINEL = np.iinfo(np.int32).min


def encode(levels: np.ndarray, id_hvs: np.ndarray, level_hvs: np.ndarray
           ) -> np.ndarray:
    """Eq. 1 for one spectrum's (F,) levels: sign of the sum over present
    peaks of ID_f * LV[level_f] (a zero sum gives -1), packed to uint32
    words with dimension 32w+j in bit j of word w."""
    present = np.nonzero(levels)[0]
    acc = (id_hvs[present].astype(np.int32)
           * level_hvs[levels[present]].astype(np.int32)).sum(axis=0)
    return np.packbits(acc > 0, bitorder="little").view(np.uint32)


@dataclasses.dataclass
class Answer:
    indices: np.ndarray   # (k,) original rows
    scores: np.ndarray    # (k,)
    has_candidate: bool


class Reference:
    def __init__(self, lib, *, tol: float, open_tol: float, k: int,
                 dim: int, half: bool = False):
        self.lib, self.tol, self.open_tol, self.k = lib, tol, open_tol, k
        self.dim = dim
        self.half = half
        self.n = lib.num_rows
        self.order = np.argsort(lib.precursor, kind="stable")
        self.sorted_prec = lib.precursor[self.order]

    def _scores(self, block: np.ndarray, rows: np.ndarray, q: np.ndarray):
        words = q.shape[0] // 2 if self.half else q.shape[0]
        data = block[rows, :words]
        dist = np.bitwise_count(data ^ q[None, :words]).sum(
            axis=1, dtype=np.int64)
        d = 32 * words
        scores = d - 2 * dist
        return scores * (self.dim // d)

    def search(self, levels: np.ndarray, prec: float) -> Answer:
        q = encode(levels, self.lib.id_hvs, self.lib.level_hvs)
        s, e = window_ranges(self.sorted_prec, np.asarray([prec]),
                             self.tol, self.open_tol)
        rows = self.order[int(s[0]):int(e[0])]
        pos, score, orig = [], [], []
        # layout: decoy block [0, N), target block [N, 2N); same order
        for offset, block in ((0, self.lib.decoys), (self.n, self.lib.targets)):
            pos.append(offset + np.arange(int(s[0]), int(e[0])))
            score.append(self._scores(block, rows, q))
            orig.append(offset + rows)
        pos, score, orig = map(np.concatenate, (pos, score, orig))
        top = np.lexsort((pos, -score))[:self.k]
        idx = orig[top].astype(np.int64)
        val = score[top].astype(np.int64)
        if top.shape[0] < self.k:  # window narrower than k
            pad = self.k - top.shape[0]
            idx = np.concatenate([idx, np.full(pad, -1)])
            val = np.concatenate([val, np.full(pad, SENTINEL)])
        return Answer(idx, val, bool(e[0] > s[0]))


def fdr_accept(top_scores: np.ndarray, is_target: np.ndarray,
               valid: np.ndarray, fdr: float) -> np.ndarray:
    """Target-decoy FDR over one batch: sort by score (stable, best first),
    accept the longest prefix whose decoy/target ratio stays within
    ``fdr``, keep the targets in it. Queries with no candidate count for
    neither side and are never accepted. Ratios in float32, as served."""
    order = np.argsort(-top_scores.astype(np.float32), kind="stable")
    tgt = is_target[order]
    ok_rows = valid[order]
    n_tgt = np.cumsum(tgt & ok_rows)
    n_dec = np.cumsum(~tgt & ok_rows)
    ratio = (n_dec.astype(np.float32)
             / np.maximum(n_tgt, 1).astype(np.float32))
    ok = ratio <= np.float32(fdr)
    kept = int(np.max(np.where(ok, np.arange(ok.shape[0]) + 1, 0)))
    acc_sorted = (np.arange(ok.shape[0]) < kept) & tgt & ok_rows
    accept = np.zeros_like(acc_sorted)
    accept[order] = acc_sorted
    return accept


def answer_batches(ref: Reference, batches, pool, fdr: float,
                   threads: int = 8) -> dict:
    """The reference's answers to whole dispatched batches: ``batches`` is a
    list of request-id lists in dispatch order, ``pool`` maps request id ->
    (levels, precursor). Returns request id -> answer in the served form
    (indices, scores, accept, match, has_candidate)."""
    rids = [r for b in batches for r in b]
    with concurrent.futures.ThreadPoolExecutor(threads) as ex:
        answers = dict(zip(rids, ex.map(
            lambda r: ref.search(*pool(r)), rids)))
    nd = ref.n
    out = {}
    for batch in batches:
        a = [answers[r] for r in batch]
        top_idx = np.array([x.indices[0] for x in a])
        top_val = np.array([x.scores[0] for x in a])
        valid = np.array([x.has_candidate for x in a])
        is_target = (top_idx >= nd) & valid
        accept = fdr_accept(top_val, top_idx >= nd, valid, fdr)
        match = np.where(accept & is_target, top_idx - nd, -1)
        for i, r in enumerate(batch):
            out[r] = {"indices": a[i].indices, "scores": a[i].scores,
                      "accept": bool(accept[i]), "match": int(match[i]),
                      "has_candidate": bool(valid[i])}
    return out


def compare(want: dict, got: dict) -> dict:
    """Mismatch counts of the served answers ``got`` against the
    reference's ``want`` (both request id -> answer), over ``want``'s
    requests. Ranks past the window's last candidate carry no row."""
    topk = fdr_bad = 0
    for r, a in want.items():
        g = got[r]
        ok_scores = np.array_equal(np.asarray(g["scores"]), a["scores"])
        live = a["scores"] != SENTINEL
        ok_idx = np.array_equal(np.asarray(g["indices"])[live],
                                a["indices"][live])
        topk += not (ok_scores and ok_idx)
        fdr_bad += not (bool(g["accept"]) == a["accept"]
                        and int(g["match"]) == a["match"]
                        and bool(g["has_candidate"]) == a["has_candidate"])
    return {"topk_mismatch": topk, "fdr_mismatch": fdr_bad,
            "checked_requests": len(want)}


def check_bank(lib) -> int:
    """Library rows (targets and their decoys) kept for the check that
    differ from this module's own encoding of their levels."""
    bad = 0
    for row, levels in zip(lib.checked_rows, lib.checked_levels):
        lv = levels.astype(np.int64)
        bad += not np.array_equal(
            lib.targets[row], encode(lv, lib.id_hvs, lib.level_hvs))
        bad += not np.array_equal(
            lib.decoys[row], encode(lv[::-1], lib.id_hvs, lib.level_hvs))
    return bad
