"""Distributed MBE driver — the paper's workload, end to end.

Enumerates all maximal bicliques of a generated or Konect-format graph
through the unified client (``repro.api.MBEClient``): the whole run is
ONE request routed to the work-stealing big-graph lane
(``big_graph_threshold=1``), which spreads root tasks over every local
device x ``--workers`` stealing workers — exactly the decomposition the
old hand-wired ``make_distributed_runner`` path built, now behind the
same front door the serving stack uses.  (The multi-device run is
exercised with simulated devices in tests; ``chip_smoke.py`` serves the
cumbe-16k class on the chip.)

Usage:
  python -m repro.launch.mbe_run --dataset marvel-like --workers 2
  python -m repro.launch.mbe_run --suite test --engine compact
  python -m repro.launch.mbe_run --file graph.tsv --no-work-stealing
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro.api import (MBEClient, MBEOptions, get_engine, imbalance,
                       unipartite_graph)
from repro.configs.cumbe import SMOKE
from repro.data import dataset_suite, load_konect
from repro.launch.compile_cache import use_compile_cache

# per-suite default dataset: the bench suite keeps the historical
# marvel-like default; the test suite (CI smoke) uses its tiny power-law
_DEFAULT_DATASET = {"bench": "marvel-like", "test": "powerlaw-tiny"}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default=None,
                    help="name from repro.data.dataset_suite "
                         "(default: per-suite)")
    ap.add_argument("--suite", default="bench", choices=["test", "bench"])
    ap.add_argument("--file", default=None,
                    help="Konect-format edge list instead of --dataset")
    ap.add_argument("--engine", default="dense",
                    help="workload engine by registry name "
                         "(repro.core.engine; e.g. dense, compact, "
                         "count, mce — unknown names raise ValueError "
                         "listing the available engines)")
    ap.add_argument("--count-p", type=int, default=2,
                    help="count engine: p of the (p,q)-biclique count")
    ap.add_argument("--count-q", type=int, default=2,
                    help="count engine: q of the (p,q)-biclique count")
    ap.add_argument("--workers", type=int, default=None,
                    help="stealing workers per device (default: cumbe "
                         "SMOKE)")
    ap.add_argument("--steps-per-round", type=int, default=4096)
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="engine-loop inner unroll per compiled round "
                         "segment (byte-identical results)")
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="step-kernel path: fused Pallas kernels vs "
                         "unfused jnp ops ('auto' = pallas on TPU)")
    ap.add_argument("--resident-lanes",
                    type=lambda v: v if v == "auto" else int(v),
                    default="auto",
                    help="pallas path: multi-lane resident pool kernel — "
                         "'auto' = one launch per worker pool whenever "
                         "the VMEM gate admits it, int k caps the pool "
                         "width, 0/1 pins the legacy vmap layout")
    ap.add_argument("--no-work-stealing", action="store_true")
    ap.add_argument("--order", default="deg", choices=["deg", "input"])
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.file:
        g = load_konect(args.file)
    else:
        name = args.dataset or _DEFAULT_DATASET[args.suite]
        g = dataset_suite(args.suite)[name]
    if get_engine(args.engine).unipartite:
        # unipartite engines (mce) take symmetric embeds: serve the
        # dataset's incidence graph (U ∪ V vertices, one undirected edge
        # per bipartite edge)
        g = unipartite_graph(g.n_u + g.n_v,
                             [(int(u), g.n_u + int(v)) for u, v in g.edges],
                             name=f"{g.name}-incidence")
    print(f"[mbe] graph {g.name}: |U|={g.n_u} |V|={g.n_v} "
          f"|E|={len(g.edges)}")

    n_dev = jax.device_count()
    workers = args.workers or SMOKE.dist.workers_per_device
    client = MBEClient(MBEOptions(
        engine=args.engine, order_mode=args.order,
        count_p=args.count_p, count_q=args.count_q,
        kernel_impl=args.kernel_impl,
        resident_lanes=args.resident_lanes,
        bucket_mode="exact",            # one graph: no padding wanted
        big_graph_threshold=1,          # the whole run IS the big route
        steps_per_round=args.steps_per_round,
        steps_per_call=args.steps_per_call,
        mesh="auto" if n_dev > 1 else None,
        workers_per_device=workers, big_workers=workers,
        work_stealing=not args.no_work_stealing))
    t0 = time.time()
    fut = client.submit(g)
    while not fut.done():
        client.poll()
        if args.verbose:
            st = client.stats()
            print(f"round {st['batches']}: busy/worker = "
                  f"{st['big_busy_per_worker']}")
    res = fut.result()
    dt = time.time() - t0
    st = client.stats()
    per_worker = np.asarray(st["big_busy_per_worker"], dtype=np.int64)
    # max/mean with the mean guarded against zero WITHOUT clamping it to
    # 1 (the old `max(mean, 1)` silently understated imbalance whenever
    # mean busy-steps < 1); the client reports the same number as
    # stats()['big_imbalance']
    imb = imbalance(per_worker)
    assert abs(imb - st["big_imbalance"]) < 1e-12
    print(f"[mbe] metric={res.metric} nodes={res.nodes} "
          f"rounds={st['batches']} time={dt:.2f}s "
          f"engine={st['engine']} "
          f"imbalance(max/mean)={imb:.3f}")
    out = dict(metric=res.metric, nodes=res.nodes, rounds=st["batches"],
               seconds=dt, imbalance=imb, engine=st["engine"])
    if hasattr(res, "n_max"):       # back-compat key for MBE/MCE callers
        out["n_max"] = res.n_max
    return out


if __name__ == "__main__":
    main()
