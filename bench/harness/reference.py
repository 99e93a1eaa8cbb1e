"""Plain references: what a correct answer is, from the generated data alone.

Nothing here imports the system under test or reads anything it built.
The answers follow the semantics the configuration files state:

* boolean AND: the docIDs present in every list of the query, ascending;
* BM25 top-k: for each document in the union of the query's lists,
  ``sum over distinct terms of m * idf(t) * (tf * (k1 + 1)) / (tf + K_hat(d))``
  with ``m`` the term's multiplicity in the query, ``idf(t) =
  f32(ln(1 + (N - df + 0.5) / (df + 0.5)))`` over the N documents of
  non-zero length, and ``K_hat(d)`` the document's length norm ``k1 * (1 -
  b + b * dl / avgdl)`` quantized to 256 linear levels over the collection's
  range and read back in float32.  Each contribution is computed in
  float32; contributions are summed in float64.  The k best by (score
  descending, docID ascending).

``topk(..., accumulate=np.float32)`` is the control: the same reference
summing in float32, the next precision below the stated one.
"""

from __future__ import annotations

import numpy as np


def intersect(lists, query) -> np.ndarray:
    """Conjunctive answer: filter the shortest list by each other list."""
    terms = sorted(set(query), key=lambda t: len(lists[t]))
    out = np.asarray(lists[terms[0]], np.int64)
    for t in terms[1:]:
        other = lists[t]
        pos = np.minimum(np.searchsorted(other, out), len(other) - 1)
        out = out[other[pos] == out]
    return out


def intersect_two_shortest(lists, query) -> np.ndarray:
    """The AND control: the conjunction of the two shortest lists only --
    a superset of the answer wherever a third list would filter it."""
    terms = sorted(set(query), key=lambda t: len(lists[t]))
    return intersect(lists, terms[:2])


class BM25:
    """Exhaustive BM25 over the generated lists and term frequencies."""

    def __init__(self, lists, freqs, k1: float, b: float, levels: int = 256):
        docs = np.concatenate(lists)
        tfs = np.concatenate(freqs).astype(np.float64)
        self.n_universe = int(docs.max()) + 1
        dl = np.bincount(docs, weights=tfs, minlength=self.n_universe)
        real = dl > 0
        n_real = int(real.sum())
        avg_dl = float(dl.sum()) / n_real
        k = k1 * (1.0 - b + b * dl / avg_dl)
        kmin, kmax = float(k[real].min()), float(k[real].max())
        kmin32 = np.float32(kmin)
        kstep32 = np.float32((kmax - kmin) / (levels - 1))
        if float(kstep32) == 0.0:
            q = np.zeros(len(dl), np.int64)
        else:
            q = np.clip(np.rint((k - float(kmin32)) / float(kstep32)), 0,
                        levels - 1).astype(np.int64)
        table = (kmin32 + kstep32 * np.arange(levels, dtype=np.float32)).astype(np.float32)
        self.k_hat = table[q]  # f32 per document
        df = np.array([len(x) for x in lists], np.float64)
        self.idf = np.log1p((n_real - df + 0.5) / (df + 0.5)).astype(np.float32)
        self.k1p1 = np.float32(k1 + 1.0)
        self.lists, self.freqs = lists, freqs
        self._contrib: dict[int, np.ndarray] = {}

    def contrib(self, t: int) -> np.ndarray:
        """float32 contribution of each posting of list t."""
        c = self._contrib.get(t)
        if c is None:
            tf = self.freqs[t].astype(np.float32)
            c = self.idf[t] * ((tf * self.k1p1) / (tf + self.k_hat[self.lists[t]]))
            c = self._contrib[t] = c.astype(np.float32)
        return c

    def topk(self, query, k: int, accumulate=np.float64):
        """(docIDs, scores) of the k best documents of ``query``."""
        terms, mult = np.unique(np.asarray(query, np.int64), return_counts=True)
        if not len(terms):
            return np.zeros(0, np.int64), np.zeros(0, np.float64)
        docs = np.unique(np.concatenate([self.lists[t] for t in terms]))
        acc = np.zeros(len(docs), accumulate)
        for t, m in zip(terms, mult):
            pos = np.searchsorted(docs, self.lists[t])
            acc[pos] += accumulate(m) * self.contrib(int(t)).astype(accumulate)
        acc = acc.astype(np.float64)
        if len(docs) > k:
            kth = np.partition(acc, len(acc) - k)[len(acc) - k]
            keep = acc >= kth
            docs, acc = docs[keep], acc[keep]
        order = np.lexsort((docs, -acc))[:k]
        return docs[order], acc[order]


def same_topk(got, want) -> bool:
    """Exactly the reference's documents, in its order, with its scores."""
    gd, gs = (np.asarray(x) for x in got)
    wd, ws = want
    return (gd.shape == wd.shape and np.array_equal(gd.astype(np.int64), wd)
            and np.array_equal(np.asarray(gs, np.float64), ws))


def same_and(got, want) -> bool:
    return np.array_equal(np.asarray(got, np.int64), want)
