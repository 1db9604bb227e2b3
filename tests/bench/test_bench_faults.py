"""The comparison that decides ``correct`` has to fail what it guards
against.

* The control: the plain reference with the configuration's guarantee
  broken (half of each graph's root subtrees searched, the partial
  answer reported as complete) is called wrong.
* A run at test size with the timed path broken underneath, during the
  window only, comes out ``correct == False``, once for each fault a
  one-chip cell of this service can have: a round that returns its state
  unchanged, half of a pool's lanes left out of the round, and an answer
  altered where the engine produces it.  (No cell spans chips, so there
  is no exchange between chips to leave out.)
"""
import numpy as np
import pytest

from _tiny import tiny_copy

from bench import check, reference, run, traffic


def _graphs(seed, n=12):
    """The cell's own requests, at their published shapes."""
    return traffic.generate(traffic.load_mix("konect-small-closed"), seed, n)


@pytest.mark.parametrize("seed", [3, 2 ** 35 + 1])
def test_control_is_called_wrong(seed):
    rows = [g.rows for g in _graphs(seed)]
    ref = reference.enumerate_many(rows)
    sound = check.compare([(r, n, cs) for r, (n, cs) in zip(rows, ref)],
                          missing=0, not_done=0)
    assert check.passes(sound)
    ctl = reference.enumerate_many(rows, roots_share=0.5)
    checks = check.compare([(r, n, cs) for r, (n, cs) in zip(rows, ctl)],
                           missing=0, not_done=0)
    assert not check.passes(checks)
    assert checks["wrong_count"][0] > 0 and checks["wrong_fingerprint"][0] > 0


def test_reference_workers_agree():
    rows = [g.rows for g in _graphs(11, n=8)]
    assert reference.enumerate_many(rows, workers=2) \
        == reference.enumerate_many(rows)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_copy(str(tmp_path_factory.mktemp("bench")))


def _unchanged(mp):
    from repro.serving.executor import LocalExecutor, RoundTelemetry

    def pool_round(self, pool, cache, budget, unroll=1):
        return RoundTelemetry(wall_s=0.0, compile_s=0.0,
                              adv=np.zeros(pool.B, np.int64))
    mp.setattr(LocalExecutor, "run_round", pool_round)


def _half_left_out(mp):
    import jax
    from repro.serving.executor import LocalExecutor
    real = LocalExecutor.run_round

    def pool_round(self, pool, cache, budget, unroll=1):
        before = pool.state
        tel = real(self, pool, cache, budget, unroll)
        keep = (pool.B + 1) // 2
        pool.state = jax.tree.map(lambda new, old: new.at[keep:].set(
            old[keep:]), pool.state, before)
        return tel
    mp.setattr(LocalExecutor, "run_round", pool_round)


def _answer_altered(mp):
    from repro.core.engine import Engine
    finish, finish_workers = Engine.finish, Engine.finish_workers

    def alter(out):
        return dict(out, n_max=out["n_max"] + 1)
    mp.setattr(Engine, "finish",
               lambda self, *a, **k: alter(finish(self, *a, **k)))
    mp.setattr(Engine, "finish_workers",
               lambda self, *a, **k: alter(finish_workers(self, *a, **k)))


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("dense.konect-small-sat", _unchanged, "missing"),
    ("dense.konect-small-sat", _half_left_out, "missing"),
    ("dense.konect-small-sat", _answer_altered, "wrong_count"),
], ids=lambda x: getattr(x, "__name__", x))
def test_broken_timed_path_is_not_correct(tiny, monkeypatch, workload,
                                          fault, caught_by):
    window = run.serve_window

    def broken_window(*a, **k):
        fault(monkeypatch)          # warm-up ran on the sound program
        return window(*a, **k)
    monkeypatch.setattr(run, "serve_window", broken_window)
    result, checks = run.run_cell(workload, 2 ** 32 + 9, 1.5, False,
                                  root=tiny, need_chip=False, grace_s=1.0)
    assert result["correct"] is False
    value, rule, limit = checks[caught_by]
    assert value > limit
