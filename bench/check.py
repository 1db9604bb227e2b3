"""The comparison that decides ``correct``.

The configuration's guarantee is exact enumeration: every request the
service completes carries the exact number of maximal bicliques and
their exact fingerprint, with no partial answer.  A run is correct when

* every request submitted in the window was answered within the grace
  after the window closed (``missing``),
* every answer has status ``done`` (``not_done``: failed, timed out or
  step-capped are not answers),
* a sample of the answers, drawn from the seed, agrees exactly with the
  plain reference on the count (``wrong_count``) and the fingerprint
  (``wrong_fingerprint``),
* and the sample is not empty (``checked``).

Every limit is exact (0 wrong), since the program computes integer bit
operations; the readings behind them are in PERF.md.
"""
from __future__ import annotations

from bench.reference import enumerate_many

# name -> (rule, limit)
LIMITS = {
    "checked": (">=", 1),
    "missing": ("<=", 0),
    "not_done": ("<=", 0),
    "wrong_count": ("<=", 0),
    "wrong_fingerprint": ("<=", 0),
}


def compare(answers, *, missing: int, not_done: int, workers: int = 1
            ) -> dict:
    """``answers``: ``(rows, n_max, cs)`` for each sampled request, with
    ``rows`` its boolean U x V adjacency.  Returns ``{name: (value, rule,
    limit)}``."""
    ref = enumerate_many([rows for rows, _, _ in answers], workers=workers)
    wrong_n = wrong_cs = 0
    for (_, n_max, cs), (ref_n, ref_cs) in zip(answers, ref):
        wrong_n += int(n_max) != ref_n
        wrong_cs += (int(cs) & 0xFFFFFFFF) != ref_cs
    values = dict(checked=len(answers), missing=missing, not_done=not_done,
                  wrong_count=wrong_n, wrong_fingerprint=wrong_cs)
    return {k: (values[k], *LIMITS[k]) for k in LIMITS}


def passes(checks: dict) -> bool:
    return all(v >= lim if rule == ">=" else v <= lim
               for v, rule, lim in checks.values())


def as_json(checks: dict) -> dict:
    return {k: {"value": v, "rule": rule, "limit": lim}
            for k, (v, rule, lim) in checks.items()}


def as_lines(checks: dict) -> list[str]:
    return [f"check {k}={v} (limit {rule} {lim})"
            for k, (v, rule, lim) in checks.items()]
