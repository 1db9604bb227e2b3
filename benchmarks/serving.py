"""Serving-layer benchmark: throughput vs per-graph latency across bucket
policies on a mixed-size request stream, a skewed-stream comparison of
whole-batch flush vs continuous lane refill, and a mixed big+small stream
served across a multi-device host mesh through the pluggable executors.
Every mode drives the serving stack through the unified client
(``repro.api.MBEClient``) and takes ``--engine NAME`` for any registered
engine (``repro.core.engine``): the policy sweep is engine-generic
(``--engine count`` checks the counting engine against per-graph runs;
``--engine mce`` serves a unipartite stream), while the skewed and
mixed-mesh modes exercise the MBE-result engines (dense, compact).

Part 1 (``run``) — three serving configurations against the
one-compile-per-graph baseline (a fresh jitted per-graph run — what a
naive service would do, so its compile count equals the request count):

* ``exact``  — batching without bucketing: graphs batch only when their
  exact shapes collide.
* ``linear`` — coarse linear buckets.
* ``pow2``   — power-of-two buckets (fewest executables).

For every policy the harness checks the served results are *byte-identical*
to the baseline per-graph runs — same biclique sets (decoded from the
collect buffer), same order-independent fingerprints — and that the
bucketed policies compile at least 2x fewer executables than
one-compile-per-graph (the cache's miss counter is an honest compile
count; see ``repro.serving.cache``).  A final cross-engine pass serves the
SAME stream through the *other* engine and asserts the biclique sets are
byte-identical between engines (the ``engines_identical`` column; the
``--json`` summary records which engine ran).

Part 2 (``run_skewed``) — one HEAVY graph plus many light ones, all in the
same pow2 bucket (the serving analog of cuMBE's workload imbalance): under
whole-batch flush the light lanes of the heavy graph's batch idle until it
finishes; the continuous scheduler refills them mid-flight from the queue.
The harness asserts the two modes are result-identical to per-graph runs
(same ``(n_max, cs)`` per request) and that continuous mode achieves
STRICTLY higher lane occupancy (busy-steps / total lane-steps) with no new
executable compiles beyond one round-mode entry and one install
executable per (bucket, batch) pair.

Part 3 (``run_mixed_mesh``) — ONE heavy graph above the big-graph routing
threshold plus >= 16 small graphs, served through the sharded executor
(lane pools sharded over every visible device) with the heavy request
routed to the work-stealing big-graph lane.  The harness asserts the
mesh-served results are byte-identical to the local executor and to
per-graph runs (same biclique sets, counts, and fingerprints), and reports
per-worker busy-step occupancy for the big lane — asserting the heavy
graph's root tasks actually spread across >= 2 workers.  Run it on a
forced host mesh:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m benchmarks.serving --mixed-mesh --big-graph-threshold 16

``--json out.json`` (any mode) writes the result rows plus a summary
(requests / wall_s / occupancy / compiles / engine) as a machine-readable
artifact — CI uploads it per run to seed the perf trajectory.

  python -m benchmarks.serving --requests 32
  python -m benchmarks.serving --requests 16 --engine compact
  python -m benchmarks.serving --skewed --requests 12 --steps-per-round 64
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax

from repro.api import MBEClient, MBEOptions
from repro.baselines import bicliques_to_key_set
from repro.core.engine import get_engine, list_engines
from repro.core.results import MBEResult
from repro.data.generators import (dense_small, random_bipartite,
                                   random_graph_stream, random_unipartite)

COLLECT_CAP = 4096


def _stream(engine: str, n_requests: int, seed: int) -> list:
    """The mixed-size request stream matched to the engine's workload:
    unipartite engines (mce) get symmetric embeds."""
    if get_engine(engine).unipartite:
        rng = np.random.default_rng(seed)
        return [random_unipartite(int(rng.integers(8, 24)),
                                  float(rng.uniform(0.2, 0.5)),
                                  seed=int(rng.integers(1 << 30)),
                                  name=f"req{i}-uni")
                for i in range(n_requests)]
    return random_graph_stream(n_requests, seed=seed)


def _baseline(graphs, engine: str) -> tuple[list, list, float, int]:
    """One fresh jit per graph: per-request latencies + reference results
    (+ total engine steps, for the steps/sec column).  References are
    engine-generic: headline metric + fingerprint (when the result type
    carries one) + decoded biclique set for MBE-result engines."""
    eng = get_engine(engine)
    collect_sets = issubclass(eng.result_type, MBEResult)
    refs, lats = [], []
    steps = 0
    t0 = time.perf_counter()
    for g in graphs:
        t1 = time.perf_counter()
        kw = dict(collect_cap=COLLECT_CAP) if collect_sets else {}
        out = eng.enumerate(g, **kw)
        lats.append(time.perf_counter() - t1)
        steps += int(out.steps)
        cfg = eng.make_config(g, **kw)
        payload = eng.finish(cfg, out, n_u=g.n_u, n_v=g.n_v,
                             collect=collect_sets)
        res = eng.make_result(rid=-1, name=g.name, latency_s=0.0,
                              **payload)
        ref_set = (bicliques_to_key_set(res.bicliques)
                   if collect_sets else None)
        refs.append((int(res.metric), int(getattr(res, "cs", 0)), ref_set))
    return refs, lats, time.perf_counter() - t0, steps


def run(n_requests: int = 32, seed: int = 0, max_batch: int = 8,
        engine: str = "dense") -> list:
    eng = get_engine(engine)
    collect_sets = issubclass(eng.result_type, MBEResult)
    graphs = _stream(engine, n_requests, seed)
    refs, base_lats, base_wall, base_steps = _baseline(graphs, engine)
    rows = [dict(policy="per-graph", engine=engine,
                 wall_s=round(base_wall, 3),
                 graphs_per_s=round(n_requests / base_wall, 2),
                 mean_latency_s=round(sum(base_lats) / len(base_lats), 4),
                 compiles=n_requests, cache_hits=0, batches=n_requests,
                 pad_lanes=0, occupancy=1.0, idle_lane_steps=0,
                 # one "poll" per graph: the whole-run jit call — and
                 # exactly one kernel-loop launch per poll
                 steps_per_s=round(base_steps / base_wall, 1),
                 steps_per_poll=round(base_steps / n_requests, 1),
                 launches_per_poll=1.0)]
    print(f"[serving] baseline ({engine}): {n_requests} graphs, "
          f"{n_requests} compiles, {base_wall:.2f}s")

    pow2_results = None
    for mode in ("exact", "linear", "pow2"):
        client = MBEClient(MBEOptions(
            engine=engine, bucket_mode=mode, max_batch=max_batch,
            collect=collect_sets, collect_cap=COLLECT_CAP))
        t0 = time.perf_counter()
        results = client.enumerate_many(graphs)
        wall = time.perf_counter() - t0
        st = client.stats()
        if mode == "pow2":
            pow2_results = results
        # --- byte-identical results, graph by graph -------------------
        for g, r, (ref_m, ref_cs, ref_set) in zip(graphs, results, refs):
            assert r.metric == ref_m, (mode, g.name, r.metric, ref_m)
            assert getattr(r, "cs", 0) == ref_cs, (mode, g.name)
            if collect_sets:
                assert bicliques_to_key_set(r.bicliques) == ref_set, \
                    (mode, g.name)
        # per-request service + compile charge: the baseline timings above
        # include each request's jit compile, so the comparison column
        # must too (the scheduler reports the split per request)
        mean_lat = sum(r.service_s + r.compile_s
                       for r in results) / len(results)
        row = dict(policy=mode, engine=engine, wall_s=round(wall, 3),
                   graphs_per_s=round(n_requests / wall, 2),
                   mean_latency_s=round(mean_lat, 4),
                   compiles=st["misses"], cache_hits=st["hits"],
                   batches=st["batches"], pad_lanes=st["pad_lanes"],
                   occupancy=round(st["occupancy"], 3),
                   idle_lane_steps=st["idle_lane_steps"],
                   # kernel-level vs scheduler-level wins, separable:
                   # steps/s moves with the kernel path, occupancy and
                   # steps/poll with the scheduler, launches/poll with
                   # the pool-kernel layout (1 launch per segment when
                   # the multi-lane resident pool is active, B otherwise)
                   steps_per_s=round(st["busy_steps"] / wall, 1),
                   steps_per_poll=round(st["steps_per_poll"], 1),
                   launches_per_poll=round(st["launches_per_poll"], 1))
        rows.append(row)
        print(f"[serving] {mode}: {st['misses']} compiles "
              f"({st['hits']} hits), {st['batches']} batches, "
              f"occupancy {st['occupancy']:.2f}, "
              f"{st['busy_steps'] / wall:.0f} steps/s "
              f"({st['steps_per_poll']:.0f} steps/poll, "
              f"{st['launches_per_poll']:.1f} launches/poll), "
              f"{wall:.2f}s, results byte-identical to per-graph runs")
        if mode in ("linear", "pow2"):
            # round executables against the baseline's one per graph (each
            # pool also compiles its small install executable)
            rounds = sum(k[0] != "install"
                         for k in client.server.cache._entries)
            assert 2 * rounds <= n_requests, \
                (f"{mode}: {rounds} round compiles vs {n_requests} "
                 f"one-per-graph — bucketing failed to amortize")

    # --- cross-engine identity: the SAME stream through every OTHER
    # engine computing the same result type (dense <-> compact) must
    # yield byte-identical biclique sets.  Engines with a different
    # result schema (count, mce) answer a different question and are
    # checked against their own oracles in tests/, not here. ------------
    others = [e for e in list_engines()
              if e != engine
              and get_engine(e).result_type is eng.result_type
              and get_engine(e).unipartite == eng.unipartite]
    for other in others:
        cross = MBEClient(MBEOptions(
            engine=other, bucket_mode="pow2", max_batch=max_batch,
            collect=collect_sets,
            collect_cap=COLLECT_CAP)).enumerate_many(graphs)
        for g, a, b in zip(graphs, pow2_results, cross):
            assert (a.metric, getattr(a, "cs", 0)) == \
                (b.metric, getattr(b, "cs", 0)), (engine, other, g.name)
            if collect_sets:
                assert bicliques_to_key_set(a.bicliques) == \
                    bicliques_to_key_set(b.bicliques), \
                    (engine, other, g.name)
        print(f"[serving] cross-engine: {engine} == {other} "
              f"byte-identical on {n_requests} requests")
    for r in rows:
        # the asserts above passed (vacuously when no same-schema peer)
        r["engines_identical"] = bool(others)
    return rows


# ---------------------------------------------------------------------------
# skewed stream: flush vs continuous refill
# ---------------------------------------------------------------------------

def skewed_graph_stream(n_requests: int, seed: int = 0) -> list:
    """One heavy dense graph + (n-1) light sparse ones, ALL in the same
    pow2 bucket (16, 32) — the imbalance regime continuous refill targets."""
    rng = np.random.default_rng(seed)
    heavy = dense_small(14, 28, p=0.55, seed=seed, name="req0-heavy")
    out = [heavy]
    for i in range(1, n_requests):
        n_u = int(rng.integers(9, 13))
        n_v = int(rng.integers(17, 29))
        out.append(random_bipartite(n_u, n_v, p=0.12,
                                    seed=int(rng.integers(1 << 30)),
                                    name=f"req{i}-light"))
    return out


def run_skewed(n_requests: int = 12, seed: int = 0, max_batch: int = 4,
               steps_per_round: int = 64, engine: str = "dense") -> list:
    graphs = skewed_graph_stream(n_requests, seed=seed)
    eng = get_engine(engine)
    refs = []
    for g in graphs:
        out = eng.enumerate(g)
        refs.append((int(out.n_max), int(out.cs)))

    rows = []
    occ = {}
    for label, spr in (("flush", 0), ("continuous", steps_per_round)):
        client = MBEClient(MBEOptions(
            engine=engine, bucket_mode="pow2", max_batch=max_batch,
            steps_per_round=spr))
        t0 = time.perf_counter()
        results = client.enumerate_many(graphs)
        wall = time.perf_counter() - t0
        st = client.stats()
        for g, r, (ref_n, ref_cs) in zip(graphs, results, refs):
            assert (r.n_max, r.cs) == (ref_n, ref_cs), \
                (label, g.name, (r.n_max, r.cs), (ref_n, ref_cs))
        occ[label] = st["occupancy"]
        rows.append(dict(mode=label, engine=engine, steps_per_round=spr,
                         wall_s=round(wall, 3),
                         rounds=st["batches"], compiles=st["misses"],
                         busy_steps=st["busy_steps"],
                         total_lane_steps=st["total_lane_steps"],
                         idle_lane_steps=st["idle_lane_steps"],
                         occupancy=round(st["occupancy"], 3),
                         steps_per_s=round(st["busy_steps"] / wall, 1),
                         steps_per_poll=round(st["steps_per_poll"], 1),
                         launches_per_poll=round(
                             st["launches_per_poll"], 1)))
        print(f"[serving-skewed] {label}: occupancy {st['occupancy']:.3f} "
              f"({st['busy_steps']}/{st['total_lane_steps']} lane-steps, "
              f"{st['idle_lane_steps']} idle), "
              f"{st['busy_steps'] / wall:.0f} steps/s "
              f"({st['steps_per_poll']:.0f} steps/poll, "
              f"{st['launches_per_poll']:.1f} launches/poll), "
              f"{st['misses']} compiles, "
              f"{st['batches']} rounds, results identical to per-graph runs")
        if label == "continuous":
            # one bucket, one lane count -> exactly one round-mode
            # executable and that pool's install executable
            assert st["misses"] == st["entries"] == 2, \
                f"continuous mode leaked executables: {st}"
    assert occ["continuous"] > occ["flush"], \
        (f"mid-flight refill failed to lift occupancy: "
         f"{occ['continuous']:.3f} <= {occ['flush']:.3f}")
    print(f"[serving-skewed] refill lifts occupancy "
          f"{occ['flush']:.3f} -> {occ['continuous']:.3f}")
    return rows


# ---------------------------------------------------------------------------
# mixed big+small stream across a multi-device host mesh
# ---------------------------------------------------------------------------

def mixed_mesh_stream(n_small: int, threshold: int, seed: int = 0) -> list:
    """ONE heavy graph at/above the routing threshold + ``n_small`` light
    graphs strictly below it (so exactly one request routes big)."""
    if threshold < 9:
        raise SystemExit(
            f"--big-graph-threshold must be >= 9 for the mixed-mesh "
            f"stream (small graphs draw n_u from [6, threshold-2)); "
            f"got {threshold}")
    rng = np.random.default_rng(seed)
    heavy = dense_small(threshold + 2, 2 * threshold + 4, p=0.5, seed=seed,
                        name="req0-heavy")
    assert heavy.n_u >= threshold
    out = [heavy]
    for i in range(1, n_small + 1):
        n_u = int(rng.integers(6, threshold - 2))
        n_v = int(rng.integers(n_u, 2 * n_u + 8))
        out.append(random_bipartite(n_u, n_v, p=0.18,
                                    seed=int(rng.integers(1 << 30)),
                                    name=f"req{i}-small"))
    assert all(g.n_u < threshold for g in out[1:])
    return out


def run_mixed_mesh(n_small: int = 16, seed: int = 0, max_batch: int = 8,
                   steps_per_round: int = 32, threshold: int = 16,
                   engine: str = "dense") -> list:
    n_dev = jax.device_count()
    if n_dev < 2:
        print(f"[serving-mesh] WARNING: only {n_dev} visible device(s); "
              f"force a host mesh with XLA_FLAGS="
              f"--xla_force_host_platform_device_count=8 (running anyway "
              f"— the big lane still over-decomposes via vmap workers)")
    graphs = mixed_mesh_stream(n_small, threshold, seed=seed)
    eng = get_engine(engine)
    refs = []
    for g in graphs:
        out = eng.enumerate(g, collect_cap=COLLECT_CAP)
        assert int(out.n_max) <= COLLECT_CAP, g.name
        cfg = eng.make_config(g, collect_cap=COLLECT_CAP)
        refs.append((int(out.n_max), int(out.cs),
                     bicliques_to_key_set(
                         eng.collected(cfg, out, g.n_u, g.n_v))))

    # total big-lane stealing workers >= 8 regardless of mesh width, so
    # the spread assertion is meaningful even on narrow hosts
    wpd = max(1, 8 // n_dev)
    base = MBEOptions(engine=engine, bucket_mode="pow2",
                      max_batch=max_batch, steps_per_round=steps_per_round,
                      big_graph_threshold=threshold,
                      collect=True, collect_cap=COLLECT_CAP)
    import dataclasses
    configs = [
        ("local", dataclasses.replace(base, mesh=None, big_workers=8)),
        ("sharded", dataclasses.replace(base, mesh="auto",
                                        workers_per_device=wpd)),
    ]
    rows = []
    for label, opts in configs:
        client = MBEClient(opts)
        t0 = time.perf_counter()
        results = client.enumerate_many(graphs)
        wall = time.perf_counter() - t0
        st = client.stats()
        # --- byte-identical to per-graph runs, graph by graph ---------
        for g, r, (ref_n, ref_cs, ref_set) in zip(graphs, results, refs):
            assert (r.n_max, r.cs) == (ref_n, ref_cs), (label, g.name)
            assert bicliques_to_key_set(r.bicliques) == ref_set, \
                (label, g.name)
        busy = np.array(st["big_busy_per_worker"], dtype=np.int64)
        spread = int((busy > 0).sum())
        assert spread >= 2, \
            f"{label}: heavy graph's root tasks not spread: {busy}"
        rows.append(dict(executor=label, engine=engine, devices=n_dev,
                         requests=len(graphs), wall_s=round(wall, 3),
                         rounds=st["batches"], compiles=st["misses"],
                         occupancy=round(st["occupancy"], 3),
                         steps_per_s=round(st["busy_steps"] / wall, 1),
                         steps_per_poll=round(st["steps_per_poll"], 1),
                         launches_per_poll=round(
                             st["launches_per_poll"], 1),
                         big_workers=len(busy), big_workers_busy=spread,
                         big_imbalance=round(st["big_imbalance"], 3),
                         big_busy_per_worker=busy.tolist()))
        print(f"[serving-mesh] {label} ({n_dev} dev): occupancy "
              f"{st['occupancy']:.3f}, {st['misses']} compiles, "
              f"{wall:.2f}s; heavy graph busy-steps/worker {busy.tolist()}"
              f" ({spread}/{len(busy)} workers busy) — results "
              f"byte-identical to per-graph runs")
    routed_big = sum(1 for e in client.routing_log
                     if e["event"] == "route" and e["route"] == "big")
    assert routed_big == 1, f"expected exactly 1 big route, {routed_big}"
    print(f"[serving-mesh] sharded == local == per-graph on "
          f"{len(graphs)} requests (1 routed big, {n_small} small)")
    return rows


def _write_json(path: str, mode: str, rows: list, requests: int,
                seed: int = 0) -> None:
    """Machine-readable bench artifact: rows + a flat summary of the
    headline series (the last row = the configuration under test).
    ``seed`` is recorded so the artifact names the exact stream it
    measured — re-running with the recorded seed reproduces the same
    request mix (the trace-replay CI smoke relies on this)."""
    head = rows[-1]
    summary = dict(
        mode=mode,
        requests=requests,
        seed=seed,
        engine=head.get("engine"),
        wall_s=head.get("wall_s"),
        occupancy=head.get("occupancy"),
        steps_per_s=head.get("steps_per_s"),
        steps_per_poll=head.get("steps_per_poll"),
        launches_per_poll=head.get("launches_per_poll"),
        compiles=head.get("compiles"),
        graphs_per_s=head.get("graphs_per_s"),
        engines_identical=head.get("engines_identical"),
    )
    with open(path, "w") as f:
        json.dump(dict(benchmark="serving", mode=mode, summary=summary,
                       rows=rows), f, indent=2, sort_keys=True)
    print(f"[serving] wrote {path}")


def _print_table(rows: list) -> None:
    keys = list(rows[0])
    print("\n" + "  ".join(f"{k:>16}" for k in keys))
    for r in rows:
        print("  ".join(f"{str(r[k]):>16}" for k in keys))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="dense",
                    help="workload engine by registry name "
                         "(repro.core.engine; e.g. dense, compact, count, "
                         "mce); the policy sweep also cross-checks every "
                         "other engine with the same result schema is "
                         "byte-identical; --skewed/--mixed-mesh take the "
                         "MBE-result engines (dense, compact)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="lanes per batch (default: 8, or 4 with --skewed)")
    ap.add_argument("--skewed", action="store_true",
                    help="skewed-stream flush-vs-continuous comparison "
                         "instead of the bucket-policy sweep")
    ap.add_argument("--mixed-mesh", action="store_true",
                    help="mixed big+small stream across the host mesh: "
                         "sharded executor + big-graph work-stealing lane "
                         "vs local executor vs per-graph runs")
    ap.add_argument("--big-graph-threshold", type=int, default=16,
                    help="mixed-mesh mode: routing threshold (root tasks)")
    ap.add_argument("--steps-per-round", type=int, default=64)
    ap.add_argument("--json", type=str, default=None, metavar="OUT",
                    help="write rows + summary (requests/wall_s/occupancy/"
                         "compiles/engine) as a machine-readable artifact")
    args = ap.parse_args()
    if args.mixed_mesh:
        mode = "mixed-mesh"
        n_small = max(args.requests - 1, 16)     # >= 16 small + 1 heavy
        rows = run_mixed_mesh(n_small, seed=args.seed,
                              max_batch=args.max_batch or 8,
                              steps_per_round=args.steps_per_round,
                              threshold=args.big_graph_threshold,
                              engine=args.engine)
        requests = n_small + 1
    elif args.skewed:
        mode = "skewed"
        rows = run_skewed(args.requests, seed=args.seed,
                          max_batch=args.max_batch or 4,
                          steps_per_round=args.steps_per_round,
                          engine=args.engine)
        requests = args.requests
    else:
        mode = "policies"
        rows = run(args.requests, seed=args.seed,
                   max_batch=args.max_batch or 8,
                   engine=args.engine)
        requests = args.requests
    _print_table(rows)
    if args.json:
        _write_json(args.json, mode, rows, requests, seed=args.seed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
