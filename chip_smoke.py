"""Bring-up smoke run: the served MBE path on TPU, checked against oracles.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: the mesh phase only

Everything goes through the front door a user calls — ``MBEClient`` ->
``MBEServer`` -> executor -> Pallas kernels — and every answer is checked:

* Phase A, a served stream: the 8 ``dataset_suite("bench")`` graphs and
  the 3 ``"large"`` ones through one dense-engine client (pow2 buckets,
  bounded rounds, multi-step segments), then the bench graphs through the
  compact engine.  Each ``n_max`` must equal the serial oracle's, and each
  ``(n_max, cs, nodes)`` the same stream served on the ``jnp`` path.
* Phase B, the paper's scale class: one graph at the ``cumbe-16k`` shape
  (configs/cumbe.py) on the big-graph route, stopped by the step cap after
  a few rounds; the partial counters of the Pallas and ``jnp`` paths must
  be identical.
* ``--chips 4``: the bench stream through a four-device ``ShardedExecutor``
  and two graphs on the four-device big-graph route, each compared with
  the same request on a one-device mesh.

The script refuses any backend but a TPU, catches no failure, and prints
one JSON object as its last line.  Its phase functions take the kernel
path and sizes as arguments, so the CPU test suite runs them small.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro import MBEClient, MBEOptions, get_engine  # noqa: E402
from repro.baselines import enumerate_mbea  # noqa: E402
from repro.configs.cumbe import CONFIG as CUMBE  # noqa: E402
from repro.data import dataset_suite, random_bipartite  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# Phase A serving knobs: bounded rounds with mid-flight refill and
# multi-step compiled segments (the continuous-batching path)
STREAM = dict(bucket_mode="pow2", steps_per_round=512, steps_per_call=8)

# Phase B: the cumbe-16k graph on the big-graph route.  A round is
# BIG_ROUND steps per worker; the cap stops the request after 3 rounds.
# Two workers, not the default four: compiled for v5e, the round needs
# args 2.28 + out 2.25 + temp 7.05 GiB with two workers and 21.8 GiB
# with four, against 16 GB of HBM.
BIG_ROUND = 64
BIG_CAP = 3 * BIG_ROUND
BIG_WORKERS = 2
BIG_SEED = 2024


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def expect(cond: bool, *what) -> None:
    """A check that holds under ``python -O`` too (unlike ``assert``)."""
    if not cond:
        raise AssertionError(what)


def counters(res) -> tuple[int, int, int]:
    return int(res.n_max), int(res.cs), int(res.nodes)


def serve(graphs, **opts):
    """Serve ``graphs`` through one fresh client; (results, stats, log)."""
    client = MBEClient(MBEOptions(**opts))
    results = client.enumerate_many(graphs)
    return results, client.stats(), client.routing_log


def bucket_paths(engine: str, kernel_impl: str, routing_log,
                 stats: dict) -> None:
    """Print, per bucket pool, which kernel path advanced it."""
    eng = get_engine(engine)
    for e in routing_log:
        if e["event"] not in ("pool", "pool-grow"):
            continue
        nu, nv = e["bucket"]
        cfg = eng.config(nu, nv, nu + 2, kernel_impl=kernel_impl)
        width = eng.pool_lanes(cfg, e["lanes"])
        log(f"  bucket {nu}x{nv} lanes={e['lanes']} "
            f"resident_lanes={width or 'off'} "
            f"launches/segment={1 if width else e['lanes']}")
    log(f"  stream: resident_lanes={stats['resident_lanes']} "
        f"launches_per_poll={stats['launches_per_poll']:.2f} "
        f"compiles={stats['misses']}")


def phase_a(dense_graphs, compact_graphs, kernel_impl: str = "auto"
            ) -> dict:
    """Serve the stream on ``kernel_impl`` (which must resolve to
    pallas) and on ``jnp``; check both against each other and the oracle."""
    oracle = {}
    t0 = time.perf_counter()
    for g in dense_graphs + compact_graphs:
        if g.name not in oracle:
            oracle[g.name] = enumerate_mbea(g, collect=False)
    log(f"phase A: serial oracle on {len(oracle)} graphs "
        f"{time.perf_counter() - t0:.1f}s")
    out = {}
    for engine, graphs in (("dense", dense_graphs),
                           ("compact", compact_graphs)):
        runs = {}
        for impl in (kernel_impl, "jnp"):
            t0 = time.perf_counter()
            res, st, rlog = serve(graphs, engine=engine, kernel_impl=impl,
                                  **STREAM)
            wall = time.perf_counter() - t0
            log(f"phase A {engine}/{st['kernel_impl']}: {len(graphs)} "
                f"requests, wall {wall:.1f}s (compile included)")
            bucket_paths(engine, impl, rlog, st)
            runs[st["kernel_impl"]] = res
        expect(set(runs) == {"pallas", "jnp"}, sorted(runs))
        for g, rp, rj in zip(graphs, runs["pallas"], runs["jnp"]):
            expect(rp.status == rj.status == "done",
                   g.name, rp.status, rj.status)
            log(f"  {engine} {g.name:<16} {g.n_u}x{g.n_v} "
                f"n_max={rp.n_max} oracle={oracle[g.name]} "
                f"cs={rp.cs} nodes={rp.nodes}")
            expect(rp.n_max == oracle[g.name],
                   engine, g.name, rp.n_max, oracle[g.name])
            expect(counters(rp) == counters(rj),
                   engine, g.name, counters(rp), counters(rj))
        out[engine] = {g.name: counters(r)
                       for g, r in zip(graphs, runs["pallas"])}
    return out


def phase_b(g, kernel_impl: str = "auto", *, workers: int = BIG_WORKERS,
            round_steps: int = BIG_ROUND, cap: int = BIG_CAP,
            steps_per_call: int = 4) -> tuple[int, int, int, int]:
    """One step-capped big-graph request on ``kernel_impl`` and on
    ``jnp``; the partial counters ``(n_max, cs, nodes, steps)`` must be
    identical."""
    runs = {}
    for impl in (kernel_impl, "jnp"):
        client = MBEClient(MBEOptions(
            kernel_impl=impl, bucket_mode="exact", big_graph_threshold=1,
            steps_per_round=round_steps, steps_per_call=steps_per_call,
            max_graph_steps=cap, big_workers=workers))
        t0 = time.perf_counter()
        res = client.submit(g).result()
        wall = time.perf_counter() - t0
        st = client.stats()
        expect(res.status == "step_capped", res.status)
        runs[st["kernel_impl"]] = (int(res.n_max), int(res.cs),
                                   int(res.nodes), int(res.steps))
        log(f"phase B {st['kernel_impl']}: {g.name} {g.n_u}x{g.n_v} "
            f"|E|={g.n_edges} workers={workers} "
            f"partial (n_max, cs, nodes, steps)={runs[st['kernel_impl']]} "
            f"rounds={st['batches']} wall {wall:.1f}s")
        log(f"  bring-up reading: compile_s={res.compile_s:.2f} "
            f"rounds={st['batches']} "
            f"busy_per_worker={st['big_busy_per_worker']}")
        del client
        gc.collect()
    expect(set(runs) == {"pallas", "jnp"}, sorted(runs))
    expect(runs["pallas"] == runs["jnp"], runs)
    return runs["pallas"]


def phase_mesh(graphs, big_runs, n_chips: int, kernel_impl: str = "auto",
               round_steps: int = BIG_ROUND) -> dict:
    """The bench stream on an ``n_chips`` serving mesh and big graphs on
    the ``n_chips`` big-graph route, each against a one-device mesh with
    the same number of stealing workers (hence the same decomposition).
    ``big_runs`` holds ``(graph, max_graph_steps)`` pairs (None = run to
    completion)."""
    opts = dict(kernel_impl=kernel_impl, **STREAM)
    wide, st, _ = serve(graphs, mesh=n_chips, **opts)
    expect(st["kernel_impl"] == "pallas", st["kernel_impl"])
    narrow, _, _ = serve(graphs, mesh=1, **opts)
    for g, a, b in zip(graphs, wide, narrow):
        expect(a.status == b.status == "done", g.name, a.status, b.status)
        expect((a.n_max, a.cs) == (b.n_max, b.cs), g.name, a, b)
    log(f"mesh: {len(graphs)}-graph stream on {n_chips} devices == "
        f"1 device (n_max, cs)")
    out = {}
    for g, cap in big_runs:
        runs = {}
        for mesh, wpd in ((n_chips, 1), (1, n_chips)):
            client = MBEClient(MBEOptions(
                kernel_impl=kernel_impl, bucket_mode="exact",
                big_graph_threshold=1, steps_per_round=round_steps,
                steps_per_call=4, max_graph_steps=cap, mesh=mesh,
                workers_per_device=wpd))
            t0 = time.perf_counter()
            res = client.submit(g).result()
            st = client.stats()
            busy = st["big_busy_per_worker"]
            runs[mesh] = (res.status, int(res.n_max), int(res.cs), busy)
            log(f"mesh big {g.name} {g.n_u}x{g.n_v} on {mesh} device(s) x "
                f"{wpd} worker(s): status={res.status} n_max={res.n_max} "
                f"cs={res.cs} rounds={st['batches']} "
                f"big_busy_per_worker={busy} "
                f"wall {time.perf_counter() - t0:.1f}s")
            del client
            gc.collect()
        expect(runs[n_chips][:3] == runs[1][:3], g.name, runs)
        expect(len(runs[n_chips][3]) == n_chips, runs[n_chips][3])
        expect(min(runs[n_chips][3]) > 0, "idle device", runs[n_chips][3])
        out[g.name] = runs[n_chips]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs only the four-chip mesh phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    if n_dev < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {n_dev}", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    log(f"device kind={dev.device_kind!r} platform={dev.platform} "
        f"count={n_dev} jax={jax.__version__} compile cache={cache}")
    t_all = time.perf_counter()

    if args.chips == 1:
        bench = list(dataset_suite("bench").values())
        large = list(dataset_suite("large").values())
        phase_a(bench + large, bench)
        gc.collect()
        t0 = time.perf_counter()
        g = random_bipartite(CUMBE.n_u, CUMBE.n_v, CUMBE.density,
                             seed=BIG_SEED, name=CUMBE.name)
        log(f"phase B: generated {g.name} {g.n_u}x{g.n_v} |E|={g.n_edges} "
            f"in {time.perf_counter() - t0:.1f}s")
        phase_b(g)
        count = 1
    else:
        bench = list(dataset_suite("bench").values())
        # the one-device reference runs all four stealing workers on one
        # chip: at n_u = 16384 that round needs 21.8 GiB, so this phase
        # cuts the cumbe-16k shape to n_u = n_v = 8192 (same density)
        half = random_bipartite(CUMBE.n_u // 2, CUMBE.n_v // 2,
                                CUMBE.density, seed=BIG_SEED,
                                name=f"{CUMBE.name}-half")
        phase_mesh(bench, [(dataset_suite("large")["powerlaw-large"], None),
                           (half, BIG_CAP)], n_chips=args.chips)
        count = n_dev
    stats = dev.memory_stats() or {}
    log(f"bring-up reading: peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use')} total wall "
        f"{time.perf_counter() - t_all:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
