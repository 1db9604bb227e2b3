"""What a mix may state beyond shapes and shares: the ``powerlaw``
family, per-kind caps on requests in flight (``loop.max_in_flight``) and
kinds whose every answer is checked (``check.every``)."""
import contextlib

import pytest

from bench import run, traffic


def _kind(family="powerlaw", n_u=60, n_v=90, edges=500, **params):
    return dict(label="k", family=family, n_u=n_u, n_v=n_v, edges=edges,
                **params)


@pytest.mark.parametrize("n_u,n_v,edges,a_u,a_v,seed", [
    (60, 90, 500, 0.6, 0.6, 3),
    (20, 24, 99, 1.0, 0.3, 2 ** 33 + 5),
    (254, 614, 1255, 0.6, 0.8, 7),
    (30, 31, 900, 0.6, 0.6, 11),             # near full: 900 of 930 cells
])
def test_powerlaw_shape(n_u, n_v, edges, a_u, a_v, seed):
    """Exactly ``edges`` distinct edges, no bare vertex, canonical
    orientation, and the same rows again from the same seed."""
    kind = _kind(n_u=n_u, n_v=n_v, edges=edges, a_u=a_u, a_v=a_v)
    g = traffic.make_graph(kind, seed, "r0-k")
    assert g.rows.shape == (n_u, n_v) and g.n_u <= g.n_v
    assert int(g.rows.sum()) == edges
    assert g.rows.any(axis=0).all() and g.rows.any(axis=1).all()
    again = traffic.make_graph(kind, seed, "r0-k")
    assert (again.rows == g.rows).all()
    other = traffic.make_graph(kind, seed + 1, "r0-k")
    assert (other.rows != g.rows).any()


@pytest.mark.parametrize("side", [0, 1])
def test_powerlaw_max_degree_rises_with_a(side):
    """A heavier exponent on a side concentrates its degrees."""
    peaks = []
    for a in (0.0, 0.5, 1.0):
        g = traffic.make_graph(_kind(n_u=300, n_v=500, edges=4000, a_u=a,
                                     a_v=a), 5, "r0-k")
        peaks.append(int(g.rows.sum(axis=1 - side).max()))
    assert peaks[0] < peaks[1] < peaks[2]


@pytest.mark.parametrize("family,params", [
    ("uniform", dict(a_u=0.6)),                 # uniform takes none
    ("powerlaw", dict(a_u=0.6)),                # both exponents are stated
])
def test_family_params_come_from_the_kind(family, params):
    with pytest.raises(TypeError):
        traffic.make_graph(_kind(family=family, **params), 9, "r0-k")


# -- the closed loop with per-kind caps, on a client that needs no program ---

SMALL_POLLS, BIG_POLLS = 1, 5


class _Answer:
    status, n_max, cs = "done", 1, 0


class _Future:
    def __init__(self, rid):
        self.rid = rid

    @staticmethod
    def result():
        return _Answer()


class _Stub:
    """Answers a ``big`` request five polls after its submission and any
    other one poll after; keeps the submission order and the most ``big``
    requests it ever held at once."""

    def __init__(self):
        self.t = 0
        self.due = {}
        self.names = []
        self.most_big = 0

    def _big_out(self):
        return sum("big" in self.names[r] for r in self.due)

    def submit(self, g):
        rid = len(self.names)
        self.names.append(g.name)
        self.due[rid] = self.t + (BIG_POLLS if "big" in g.name
                                  else SMALL_POLLS)
        self.most_big = max(self.most_big, self._big_out())
        return _Future(rid)

    def poll(self):
        self.t += 1
        fin = [r for r, t in self.due.items() if t <= self.t]
        for r in fin:
            del self.due[r]
        return {r: None for r in fin}

    @staticmethod
    def stats():
        return {}


MIX = dict(graphs=[
    dict(label="small-a", family="uniform", n_u=4, n_v=5, edges=8),
    dict(label="small-b", family="uniform", n_u=5, n_v=6, edges=9),
    dict(label="big", family="powerlaw", n_u=6, n_v=7, edges=12, a_u=0.6,
         a_v=0.6, share=2)])


def _window(loop, seed=2 ** 34 + 1):
    graphs = traffic.generate(MIX, seed, 40)
    client = _Stub()
    win = run.serve_window(client, [(g, g) for g in graphs], loop, 0.05,
                           5.0, lambda _name: contextlib.nullcontext())
    return graphs, client, win


@pytest.mark.parametrize("cap", [1, 2])
def test_cap_holds_and_order_is_deterministic(cap):
    loop = dict(outstanding=6, max_in_flight={"big": cap})
    graphs, a, win = _window(loop)
    assert a.most_big == cap
    assert win["missing"] == 0 and win["attempted"] == len(a.names)
    # a held request keeps its place among its kind: each kind is
    # submitted in the generated order
    order = [g.name for g in graphs]
    for kind in ("small-a", "small-b", "big"):
        mine = [n for n in a.names if n.endswith("-" + kind)]
        want = [n for n in order if n.endswith("-" + kind)]
        assert mine == (want * (len(mine) // len(want) + 1))[: len(mine)]
    _, b, _ = _window(loop)
    n = min(len(a.names), len(b.names))
    assert n > 3 * len(graphs) and a.names[:n] == b.names[:n]


def test_no_cap_keeps_generated_order():
    graphs, client, win = _window(dict(outstanding=6))
    order = [g.name for g in graphs]
    assert client.names == [order[i % len(order)]
                            for i in range(len(client.names))]
    assert client.most_big > 2 and win["attempted"] == len(client.names)


def test_check_every_listed_kind():
    """Every answer of a kind under ``every`` is compared; the seeded
    sample is drawn from the others, without repeats."""
    graphs = traffic.generate(MIX, 3, 60)
    done = [dict(graph=g) for g in graphs]
    pick = run.check_sample(done, dict(sample=5, every=["big"]), 8)
    big = [i for i, g in enumerate(graphs) if traffic.label(g) == "big"]
    assert set(big) <= set(pick) and len(pick) == len(big) + 5
    assert pick == sorted(set(pick))
    assert run.check_sample(done, dict(sample=5, every=["big"]), 8) == pick
    assert len(run.check_sample(done[:3], dict(sample=5), 8)) == 3
