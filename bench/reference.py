"""Plain reference for maximal biclique enumeration, and its control.

``enumerate_many(graphs)`` runs the paper's Algorithm 1 (Zhang et al.'s
MBEA with the degeneracy candidate order, as the repository's serial
oracle transcribes it) over Python big-int bitmasks, copied so that the
benchmark's verdict depends on nothing the program ships.  Each search
node takes a candidate ``x`` from ``P`` (fewest neighbours in ``L``
first), forms ``L' = L & N(x)``, rejects it when a vertex of ``Q`` covers
all of ``L'``, and otherwise reports ``(L', R + x + the P vertices that
cover L')`` and recurses on the rest of ``P`` that meets ``L'``.

The first level is split the way ParMBE and cuMBE split it: root ``i``
(in the fixed root order) owns one subtree with ``Q`` = the roots before
it and ``P`` = the roots after it.  Subtrees run in worker processes
(spawned; they import NumPy only) when ``workers > 1``.

Each graph's answer is the number of maximal bicliques (both sides
non-empty) and their order-independent fingerprint: the wrapping uint32
sum over bicliques of the program's documented pair hash of the packed
``(L over V, R over U)`` bitsets.  Zero words hash to zero, so the
fingerprint does not depend on how far a side is padded.

``roots_share < 1`` is the control: only the first part of the root
subtrees is searched, and the partial answer is reported as complete.  It
breaks the configuration's guarantee (exact enumeration, no partial
answer), and the comparison in ``check.py`` must call it wrong.
"""
from __future__ import annotations

import multiprocessing
import sys
from concurrent.futures import ProcessPoolExecutor

import numpy as np

_CHUNK = 4096
_U32 = np.uint32


def _checksum(words: np.ndarray) -> np.ndarray:
    """Per-row hash of packed uint32 sets, ``(k, nw) -> (k,)``."""
    nw = words.shape[-1]
    mult = np.arange(nw, dtype=_U32) * _U32(0x9E3779B9) + _U32(0x85EBCA6B)
    h = words * mult
    h ^= h >> _U32(15)
    h *= _U32(0x2545F491)
    h ^= h >> _U32(13)
    return h.sum(axis=-1, dtype=_U32)


def _pair_hash(l_words: np.ndarray, r_words: np.ndarray) -> np.ndarray:
    x = (_checksum(l_words) * _U32(0x85EBCA6B)) \
        ^ (_checksum(r_words) * _U32(0xC2B2AE35))
    x ^= x >> _U32(16)
    x *= _U32(0x7FEB352D)
    return x ^ (x >> _U32(15))


def _words(masks: list[int], n_bits: int) -> np.ndarray:
    nbytes = 4 * ((n_bits + 31) // 32)
    buf = b"".join(m.to_bytes(nbytes, "little") for m in masks)
    return np.frombuffer(buf, dtype="<u4").reshape(len(masks), -1)


class _Fingerprint:
    def __init__(self, n_u: int, n_v: int):
        self.n_u, self.n_v = n_u, n_v
        self.n = 0
        self.cs = 0
        self._l: list[int] = []
        self._r: list[int] = []

    def add(self, l_mask: int, r: tuple) -> None:
        self.n += 1
        self._l.append(l_mask)
        self._r.append(sum(1 << x for x in r))
        if len(self._l) >= _CHUNK:
            self.flush()

    def flush(self) -> None:
        if self._l:
            h = _pair_hash(_words(self._l, self.n_v),
                           _words(self._r, self.n_u))
            self.cs = (self.cs + int(h.sum(dtype=np.uint64))) % (1 << 32)
            self._l, self._r = [], []


def _expand(adj: list[int], Lp: int, R: tuple, P: list, Q: list, sink
            ) -> None:
    """Maximality check of ``(Lp, R)`` against ``Q``; on success report
    it with the ``P`` vertices that cover ``Lp`` and recurse."""
    nLp = Lp.bit_count()
    Qp = []
    for v in Q:
        c = (adj[v] & Lp).bit_count()
        if c == nLp:
            return
        if c > 0:
            Qp.append(v)
    Pp, extra = [], []
    for v in P:
        c = (adj[v] & Lp).bit_count()
        if c == nLp:
            extra.append(v)
        elif c > 0:
            Pp.append(v)
    Rp = R + tuple(extra)
    sink(Lp, Rp)
    if Pp:
        _level(adj, Lp, Rp, Pp, Qp, sink)


def _level(adj: list[int], L: int, R: tuple, P: list, Q: list, sink
           ) -> None:
    """One level of Algorithm 1: ``P`` sorted by descending ``|N(v) & L|``
    on entry, so ``pop()`` takes the fewest first."""
    P = sorted(P, key=lambda v: -((adj[v] & L).bit_count()))
    Q = list(Q)
    while P:
        x = P.pop()
        Lp = L & adj[x]
        if Lp:
            _expand(adj, Lp, R + (x,), P, Q, sink)
        Q.append(x)


def _root_order(adj: list[int]) -> list[int]:
    """The order the root level pops its candidates in."""
    P = sorted(range(len(adj)), key=lambda v: -adj[v].bit_count())
    return P[::-1]


def _subtrees(args) -> tuple[int, int]:
    """``(n, cs)`` of the root subtrees ``picks`` of one graph."""
    adj, n_v, order, picks = args
    sys.setrecursionlimit(max(10000, 4 * len(adj) + 100))
    fp = _Fingerprint(len(adj), n_v)
    for i in picks:
        x = order[i]
        if adj[x]:
            _expand(adj, adj[x], (x,), order[:i:-1], order[:i], fp.add)
    fp.flush()
    return fp.n, fp.cs


def row_masks(rows: np.ndarray) -> list[int]:
    """Each U row of a boolean ``(n_u, n_v)`` adjacency as a big int."""
    packed = np.packbits(rows, axis=1, bitorder="little")
    return [int.from_bytes(r.tobytes(), "little") for r in packed]


def enumerate_many(graphs: list[np.ndarray], workers: int = 1,
                   roots_share: float = 1.0) -> list[tuple[int, int]]:
    """``(n_max, cs)`` of each graph, given as its U x V boolean
    adjacency.  ``roots_share < 1`` searches only that leading share of
    each graph's root subtrees (the control)."""
    tasks, owner = [], []
    for gi, rows in enumerate(graphs):
        adj = row_masks(rows)
        order = _root_order(adj)
        n_roots = int(len(order) * roots_share)
        k = max(1, min(workers * 4, n_roots))
        for j in range(k):
            tasks.append((adj, rows.shape[1], order,
                          list(range(j, n_roots, k))))
            owner.append(gi)
    if workers > 1:
        ctx = multiprocessing.get_context("spawn")
        with ProcessPoolExecutor(workers, mp_context=ctx) as ex:
            parts = list(ex.map(_subtrees, tasks))
    else:
        parts = [_subtrees(t) for t in tasks]
    out = [[0, 0] for _ in graphs]
    for gi, (n, cs) in zip(owner, parts):
        out[gi][0] += n
        out[gi][1] = (out[gi][1] + cs) % (1 << 32)
    return [tuple(o) for o in out]
