"""Plain reference for every answer the benchmark checks.

A straightforward host BFS (level-synchronous, one bit per source) over a
CSR built from the benchmark's own edge list, independent of the program
under test: no code, weights or tables of
it are used. Each query kind's answer follows from one level array:

* ``levels``: hop distance from the source, ``UNREACHED`` where none;
* ``reachability``: ``levels != UNREACHED``;
* ``distance_limited``: ``levels`` where ``<= max_depth``, else
  ``UNREACHED``;
* ``multi_target``: ``{target: levels[target]}``.

``UNREACHED`` is the value the served answers use for "no path" (2**30),
so answers compare exactly, array for array.

Graph500 TEPS counts, for each search key, the undirected input edges of
the key's connected component: stored (doubled) edges whose source end is
reached, halved (the ``m / 2`` convention).
"""
from __future__ import annotations

import numpy as np

UNREACHED = 1 << 30


class Graph:
    """CSR view of an undirected edge list ``(n, src, dst)``."""

    def __init__(self, n: int, src: np.ndarray, dst: np.ndarray):
        self.n = int(n)
        order = np.argsort(src, kind="stable")
        self.cols = dst[order].astype(np.int64)
        self.offsets = np.zeros(self.n + 1, dtype=np.int64)
        np.cumsum(np.bincount(src, minlength=self.n), out=self.offsets[1:])
        self._comp = None

    def levels(self, sources) -> dict:
        """Hop distances from each source: ``{source: int32 [n]}``.

        A level-synchronous BFS from up to 64 sources at once, one bit of a
        uint64 word per source: each level, every vertex ORs the frontier
        words of its neighbours, and a bit set there for the first time
        puts the vertex at that level for that bit's source."""
        out: dict = {}
        srcs = list(dict.fromkeys(int(s) for s in sources))
        for i in range(0, len(srcs), 64):
            out.update(self._levels64(srcs[i:i + 64]))
        return out

    def _levels64(self, srcs: list) -> dict:
        n, k = self.n, len(srcs)
        deg = np.diff(self.offsets)
        rows = np.flatnonzero(deg)
        starts = self.offsets[rows]
        bit = np.uint64(1) << np.arange(k, dtype=np.uint64)
        seen = np.zeros(n, dtype=np.uint64)
        seen[srcs] = bit
        front = seen.copy()
        lev = np.full((k, n), UNREACHED, dtype=np.int32)
        lev[np.arange(k), srcs] = 0
        depth = 0
        while rows.size:
            depth += 1
            nxt = np.zeros(n, dtype=np.uint64)
            nxt[rows] = np.bitwise_or.reduceat(front[self.cols], starts)
            new = nxt & ~seen
            hit = np.flatnonzero(new)
            if not hit.size:
                break
            seen[hit] |= new[hit]
            front = np.zeros(n, dtype=np.uint64)
            front[hit] = new[hit]
            for b in range(k):
                lev[b, hit[(new[hit] & bit[b]) != 0]] = depth
        return {s: lev[b] for b, s in enumerate(srcs)}

    def components(self) -> np.ndarray:
        """Component id per vertex (scipy's union of the edge list)."""
        if self._comp is None:
            from scipy.sparse import csr_matrix
            from scipy.sparse.csgraph import connected_components

            a = csr_matrix((np.ones(self.cols.size, dtype=np.int8),
                            self.cols, self.offsets), shape=(self.n, self.n))
            self._comp = connected_components(a, directed=False)[1]
        return self._comp

    def teps_edges(self, keys) -> np.ndarray:
        """Graph500 traversed edges for each search key (m / 2)."""
        lab = self.components()
        deg = np.diff(self.offsets)
        per = np.bincount(lab, weights=deg, minlength=self.n)
        return (per[lab[np.asarray(keys, dtype=np.int64)]] // 2).astype(
            np.int64)


def answer(kind: str, levels: np.ndarray, max_depth=None, targets=()):
    """The answer of one query kind, derived from the source's levels."""
    if kind == "levels":
        return levels
    if kind == "reachability":
        return levels != UNREACHED
    if kind == "distance_limited":
        return np.where(levels <= max_depth, levels, UNREACHED).astype(
            np.int32)
    if kind == "multi_target":
        return {int(t): int(levels[int(t)]) for t in targets}
    raise ValueError(f"unknown query kind {kind!r}")


def same(got, want) -> bool:
    """Exact equality of two answers (arrays by value and dtype kind,
    target maps by key and value)."""
    if isinstance(want, dict):
        return isinstance(got, dict) and {int(k): int(v) for k, v in
                                          got.items()} == want
    got = np.asarray(got)
    return (got.shape == want.shape and got.dtype.kind == want.dtype.kind
            and bool(np.array_equal(got, want)))
