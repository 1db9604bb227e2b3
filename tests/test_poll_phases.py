"""The poll's phase spans and host time counters (DESIGN.md §12).

``MBEServer`` times each poll, each lane-pool refill and each demux on the
host, as spans on the profiler's clock (``mbe.poll``, ``mbe.refill``,
``mbe.demux``, with ``mbe.round``, ``mbe.round.wait``, ``mbe.install`` and
``mbe.compile`` beside them) and as cumulative ``stats()`` counters
(``poll_s``, ``refill_s``, ``demux_s``, and ``exec_s``, the time blocked on
round executables).  These tests pin the counters' arithmetic, the spans'
nesting in a real profiler trace, and that tracing changes no result.
"""
import dataclasses
import glob
import os

import jax
import pytest
from _graphs import random_graph

from repro import MBEClient, MBEOptions
from repro.serving import MONOTONIC_STATS, STATS_SCHEMA

COUNTERS = ("poll_s", "refill_s", "demux_s", "exec_s")
TIMING = {"latency_s", "queue_s", "service_s", "compile_s"}


def _graphs(n=6):
    return [random_graph(6 + i % 3, 12 + i % 4, 0.35, 40 + i,
                         canonical=True) for i in range(n)]


def _client():
    return MBEClient(MBEOptions(max_batch=2, steps_per_round=8))


def _serve(client, graphs, each_poll=None):
    futs = [client.submit(g) for g in graphs]
    while not all(f.done() for f in futs):
        client.poll()
        if each_poll is not None:
            each_poll(client.stats())
    return [f.result() for f in futs]


def _payload(res):
    return {k: v for k, v in dataclasses.asdict(res).items()
            if k not in TIMING}


def test_counters_are_contract_keys():
    for key in COUNTERS:
        assert STATS_SCHEMA[key] is float
        assert key in MONOTONIC_STATS


def test_counters_grow_and_nest():
    """Over a served stream the four counters only grow, the phases fit
    inside the polls that ran them, and ``reset_stats`` zeroes them."""
    client = _client()
    seen = []
    results = _serve(client, _graphs(), seen.append)
    assert all(r.status == "done" for r in results)
    assert len(seen) >= 3
    for a, b in zip(seen, seen[1:]):
        for key in COUNTERS:
            assert b[key] >= a[key], key
    last = seen[-1]
    assert all(last[key] > 0 for key in COUNTERS)
    assert last["refill_s"] + last["demux_s"] <= last["poll_s"]
    assert last["exec_s"] <= last["poll_s"]           # the local path
    client.server.reset_stats()
    after = client.stats()
    assert all(after[key] == 0.0 for key in COUNTERS)


def test_drain_counts_each_poll():
    """``drain`` times every scheduling round it runs as a poll."""
    client = _client()
    client.enumerate_many(_graphs(4))
    s = client.stats()
    assert s["poll_s"] > 0 and s["exec_s"] <= s["poll_s"]
    assert s["refill_s"] + s["demux_s"] <= s["poll_s"]


def _host_spans(log_dir):
    """Every ``mbe.*`` event of the trace as ``(line, start, end, name)``,
    host planes only."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mbe."):
                    assert plane.name.startswith("/host:"), plane.name
                    out.append(((plane.name, line.name), ev.start_ns,
                                ev.end_ns, ev.name))
    return out


def test_spans_nest_in_the_poll_on_one_host_line(tmp_path):
    """One traced poll: the refill, round, round-wait and demux spans lie
    inside the ``mbe.poll`` span, on the same host line."""
    client = _client()
    _serve(client, _graphs(2))                  # compile outside the trace
    futs = [client.submit(g) for g in _graphs(2)]
    jax.profiler.start_trace(str(tmp_path))
    try:
        client.poll()
    finally:
        jax.profiler.stop_trace()
    while not all(f.done() for f in futs):
        client.poll()
    spans = _host_spans(str(tmp_path))
    polls = [s for s in spans if s[3] == "mbe.poll"]
    assert len(polls) == 1
    line, lo, hi, _ = polls[0]
    inner = {s[3] for s in spans
             if s[0] == line and lo <= s[1] and s[2] <= hi}
    assert {"mbe.refill", "mbe.install", "mbe.round", "mbe.round.wait",
            "mbe.demux"} <= inner
    assert "mbe.compile" not in inner           # warm: nothing compiled


def test_results_identical_with_profiler_on_and_off(tmp_path):
    graphs = _graphs()
    off = _serve(_client(), graphs)
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = _serve(_client(), graphs)
    finally:
        jax.profiler.stop_trace()
    assert [_payload(r) for r in on] == [_payload(r) for r in off]
