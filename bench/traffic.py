"""The one traffic generator: reads a mix file ``bench/traffic/<mix>.json``
and turns ``--seed`` into the cell's requests.

A request is a plain bipartite edge list, ``Graph(name, n_u, n_v, rows)``
with ``rows`` the ``(n_u, n_v)`` boolean adjacency, in the canonical
orientation (``n_u <= n_v``).  Nothing here imports the program: the
harness packs the rows into the program's graph type, and the reference
reads the same rows.

A mix lists its graph kinds under ``graphs``: each a published shape
(``n_u``, ``n_v``, ``edges``: exactly that many distinct edges, every
vertex of degree 1 or more, as in the published dataset), the family its
edges are drawn from, and its ``share`` of the requests.

Steadiness: the requests come in rounds, each round holding every kind
``share`` times in a seed-shuffled order, so any prefix of the sequence
(what one window serves) has the kinds in their shares to within one
round.  Only the order within a round and each graph's edges come from
the seed.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Graph:
    name: str
    n_u: int
    n_v: int
    rows: np.ndarray            # (n_u, n_v) bool adjacency, U x V


def load_mix(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


# -- families: exactly ``edges`` distinct edges, min degree >= 1 ------------

def _cover(n_u, n_v, rng) -> np.ndarray:
    """One edge at each vertex: every V vertex takes a U partner, then
    every U vertex still bare takes a V partner."""
    rows = np.zeros((n_u, n_v), bool)
    rows[rng.integers(n_u, size=n_v), np.arange(n_v)] = True
    bare = np.flatnonzero(~rows.any(axis=1))
    rows[bare, rng.integers(n_v, size=len(bare))] = True
    return rows


def uniform(n_u, n_v, edges, rng):
    """The cover, then distinct edges uniformly at random up to ``edges``
    (the published shape and density, no degree skew assumed)."""
    rows = _cover(n_u, n_v, rng)
    flat = rows.reshape(-1)
    have = int(flat.sum())
    while have < edges:
        code = np.unique(rng.integers(n_u * n_v, size=2 * (edges - have)))
        new = code[~flat[code]]
        new = new[rng.permutation(len(new))][: edges - have]
        flat[new] = True
        have += len(new)
    return rows


FAMILIES = dict(uniform=uniform)
_SHAPE = ("label", "family", "n_u", "n_v", "edges", "share", "source")


def make_graph(kind: dict, seed: int, name: str) -> Graph:
    """One graph of a mix's kind, its edges drawn from ``seed``."""
    params = {k: v for k, v in kind.items() if k not in _SHAPE}
    n_u, n_v, edges = kind["n_u"], kind["n_v"], kind["edges"]
    if not (n_u <= n_v and max(n_u, n_v) <= edges <= n_u * n_v):
        raise ValueError(f"no graph of shape {n_u}x{n_v} with {edges} edges "
                         f"and every degree >= 1 in canonical orientation")
    rows = FAMILIES[kind["family"]](n_u, n_v, edges,
                                    np.random.default_rng(seed), **params)
    return Graph(name, n_u, n_v, rows)


def generate(mix: dict, seed: int, n: int) -> list[Graph]:
    """The run's first ``n`` requests for ``seed``: rounds of every kind
    in its share, shuffled within each round."""
    ss = np.random.SeedSequence(int(seed))
    order_rng, graph_seed = ss.spawn(2)
    order_rng = np.random.default_rng(order_rng)
    one_round = [k for k in mix["graphs"] for _ in range(k.get("share", 1))]
    seeds = graph_seed.generate_state(n, dtype=np.uint64)
    out: list[Graph] = []
    while len(out) < n:
        for j in order_rng.permutation(len(one_round)):
            if len(out) == n:
                break
            kind, i = one_round[j], len(out)
            out.append(make_graph(kind, int(seeds[i]),
                                  f"r{i}-{kind['label']}"))
    return out


def warm_graph(n_u: int, n_v: int, name: str) -> Graph:
    """A graph of the given shape with next to no search work (a
    staircase of single edges), for warming a bucket's executables."""
    rows = np.zeros((n_u, n_v), bool)
    rows[np.arange(n_u), np.arange(n_u) % n_v] = True
    rows[np.arange(n_v) % n_u, np.arange(n_v)] = True
    return Graph(name, n_u, n_v, rows)


def slow_graph(n_u: int, n_v: int, crown: int = 12) -> Graph:
    """A warm graph that keeps its lane busy for some rounds: a crown
    (complete bipartite minus a perfect matching, ``2**crown - 2``
    maximal bicliques) on the first ``crown`` vertices of each side, the
    staircase of ``warm_graph`` elsewhere."""
    g = warm_graph(n_u, n_v, f"warm-slow-{n_u}x{n_v}")
    t = min(crown, n_u, n_v)
    g.rows[:t, :t] = ~np.eye(t, dtype=bool)
    return g
