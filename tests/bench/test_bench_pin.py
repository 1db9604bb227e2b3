"""The inputs of the benchmark's cells, pinned: for a fixed seed, the
generated requests, the warm graphs ``warm`` submits and the indices the
check samples have the digests recorded from the harness before the mix
keys ``max_in_flight`` and ``check.every``, the ``powerlaw`` family and the
route-aware warm-up came in.  A cell whose mix uses none of them reads
the same inputs as it did then."""
import contextlib
import hashlib

import numpy as np
import pytest

from _tiny import CELLS

from bench import run, traffic

SEED = 2 ** 33 + 123
REQUESTS = "a1eef99349a9d080338bbe9afc0ccb3ade7291ad70121848e3676a29be6ea5c1"
WARM = (168, "e98639e699a065d558d388bcc05da0eb"
             "4a2785a1de6776a013c8f919129120ed")
SAMPLES = "e553ac59f003fcf130529b5a918cdd7a4cd0cfb6451362a71a2773e8266488b1"


class _Done:
    rid = 0

    @staticmethod
    def done():
        return True


class _Recorder:
    """A client that answers every request at once and keeps what it was
    given."""

    def __init__(self, options):
        self.options = options
        self.seen = []

    def submit(self, g):
        self.seen.append(g)
        return _Done()

    def poll(self):
        return {}


@pytest.fixture(scope="module")
def requests():
    mix = traffic.load_mix("konect-small-closed")
    return traffic.generate(mix, SEED, mix["requests"])


def test_requests_pinned(requests):
    h = hashlib.sha256()
    for g in requests:
        h.update(f"{g.name}:{g.n_u}x{g.n_v};".encode())
        h.update(np.packbits(g.rows).tobytes())
    assert h.hexdigest() == REQUESTS


@pytest.mark.parametrize("workload", CELLS)
def test_warm_graphs_pinned(requests, workload):
    from repro import MBEOptions
    config = run.load_cell(workload)[2]
    client = _Recorder(MBEOptions(**config["options"]))
    n = run.warm(client, [run.to_program(g) for g in requests],
                 lambda _name: contextlib.nullcontext())
    h = hashlib.sha256()
    for g in client.seen:
        h.update(f"{g.name}:{g.n_u}x{g.n_v};".encode())
        h.update(np.ascontiguousarray(g.adj_u).tobytes())
    assert (n, h.hexdigest()) == WARM


@pytest.mark.parametrize("workload", CELLS)
def test_check_sample_pinned(requests, workload):
    check_cfg = run.load_cell(workload)[3]["check"]
    h = hashlib.sha256()
    for n_done in range(0, 200, 7):
        done = [dict(graph=g) for g in requests[:n_done]]
        pick = run.check_sample(done, check_cfg, SEED + n_done)
        h.update(np.asarray(pick, np.int64).tobytes())
    assert h.hexdigest() == SAMPLES
