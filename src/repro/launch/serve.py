"""Batched multi-graph MBE serving front end.

Serves a stream of bipartite graphs through the unified client
(``repro.api.MBEClient`` over ``repro.serving``) — shape-bucketed,
vmap-batched enumeration with a compiled-executable cache (see those
docstrings for the model); ``--engine compact`` serves the paper's
compact-array engine through the same stack.  ``--continuous`` switches
the scheduler into bounded-round slot mode (``--steps-per-round`` engine
steps per round): finished lanes are demuxed and refilled mid-flight from
the pending queue, lifting lane occupancy on skewed streams.

Execution backends: ``--mesh N`` serves through ``ShardedExecutor`` on a
1-D serving mesh over N host devices (force host devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``); the default is
the single-device ``LocalExecutor``.  ``--big-graph-threshold K`` routes
requests with >= K root tasks to the work-stealing big-graph lane.  Every
request's routing decision and every pool's lane placement is printed
(``[route]``/``[pool]`` lines) so operators can see why a request queued
where it did.

Usage:
  python -m repro.launch.serve --requests 32 --policy pow2
  python -m repro.launch.serve --continuous --steps-per-round 64
  python -m repro.launch.serve --mesh 8 --big-graph-threshold 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.api import MBEClient, MBEOptions
from repro.launch.compile_cache import use_compile_cache


def _print_routing(server) -> None:
    """Per-request routing decisions + per-bucket placements, so operators
    can see which executor served what, with how many lanes, where.
    Accepts anything with a ``routing_log`` (MBEClient or MBEServer)."""
    for e in server.routing_log:
        if e["event"] == "route":
            print(f"[route] rid={e['rid']} {e['graph']}: -> {e['route']} "
                  f"(bucket {e['bucket']}, executor={e['executor']}) — "
                  f"{e['reason']}")
        elif e["event"] in ("pool", "pool-grow"):
            grew = (f" (grown from {e['was']})"
                    if e["event"] == "pool-grow" else "")
            print(f"[pool]  bucket {e['bucket']}: {e['lanes']} lanes on "
                  f"{e['placement']}{grew}")
        elif e["event"] == "big-lane":
            print(f"[big]   rid={e['rid']} {e['graph']}: {e['placement']}")


def _request_stream(engine_name: str, n_requests: int, seed: int):
    """The synthetic request stream matched to the engine's workload:
    unipartite engines (``mce``) get symmetric embeds, everything else
    the mixed-size bipartite stream."""
    from repro.core.engine import get_engine
    from repro.data.generators import random_graph_stream, random_unipartite
    if get_engine(engine_name).unipartite:
        rng = np.random.default_rng(seed)
        return [random_unipartite(int(rng.integers(8, 24)),
                                  float(rng.uniform(0.2, 0.5)),
                                  seed=int(rng.integers(1 << 30)),
                                  name=f"req{i}-uni")
                for i in range(n_requests)]
    return random_graph_stream(n_requests, seed=seed)


def _retry_policy(args):
    """Build the ``RetryPolicy`` requested on the command line, or None
    when ``--retry 0`` (the default — no recovery machinery at all)."""
    if not args.retry:
        return None
    from repro.serving import RetryPolicy
    return RetryPolicy(max_attempts=args.retry,
                       checkpoint_interval=args.checkpoint_interval)


def _fault_plan(args):
    """Build the chaos-testing ``FaultPlan``, or None when no fault flag
    was given (no injector wrapper at all)."""
    if not args.fault_launch_rate and args.fault_device_lost_at is None:
        return None
    from repro.serving import FaultPlan
    return FaultPlan(seed=args.fault_seed,
                     launch_rate=args.fault_launch_rate,
                     device_lost_after=args.fault_device_lost_at)


def _admission_policy(args):
    """Build the ``AdmissionPolicy`` requested on the command line, or
    None when no admission flag was given (the default — the SLO layer
    stays entirely out of the serving path)."""
    if args.admit_max_pending is None and not args.admit_shed:
        return None
    from repro.serving.slo import AdmissionPolicy
    return AdmissionPolicy(max_pending=args.admit_max_pending,
                           shed_on_deadline=args.admit_shed,
                           shed_slack=args.shed_slack)


def serve(argv=None) -> dict:
    """Serve a synthetic mixed-size request stream through the unified
    client (``repro.api.MBEClient``), with any registered engine, and
    return the run's summary (requests, metric total, wall time and the
    client's ``stats()``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--policy", default="pow2",
                    choices=["pow2", "linear", "exact"])
    ap.add_argument("--engine", default="dense",
                    help="workload engine by registry name "
                         "(repro.core.engine; e.g. dense, compact, "
                         "count, mce — unknown names raise ValueError "
                         "listing the available engines)")
    ap.add_argument("--count-p", type=int, default=2,
                    help="count engine: p of the (p,q)-biclique count")
    ap.add_argument("--count-q", type=int, default=2,
                    help="count engine: q of the (p,q)-biclique count")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--continuous", action="store_true",
                    help="bounded-round slot scheduling with "
                         "mid-flight lane refill")
    ap.add_argument("--steps-per-round", type=int, default=64,
                    help="continuous mode: engine steps per round")
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="engine-loop inner unroll (candidate steps "
                         "per while-loop iteration in one compiled round "
                         "segment; byte-identical results)")
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="step-kernel path — 'pallas' = fused "
                         "fused_select/fused_check kernels (interpret "
                         "off-TPU), 'auto' = pallas on TPU, jnp elsewhere")
    ap.add_argument("--resident-lanes",
                    type=lambda v: v if v == "auto" else int(v),
                    default="auto",
                    help="pallas path: multi-lane resident pool "
                         "kernel — 'auto' = one launch per pool whenever "
                         "the VMEM gate admits it, int k caps the pool "
                         "width, 0/1 pins the legacy vmap layout")
    ap.add_argument("--mesh", type=int, default=0,
                    help="serve through ShardedExecutor on a 1-D "
                         "mesh over N host devices (0 = LocalExecutor)")
    ap.add_argument("--big-graph-threshold", type=int, default=None,
                    help="route graphs with >= K root tasks to the "
                         "work-stealing big-graph lane")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a JSONL request trace "
                         "(serving.slo.trace schema v1) — replay it with "
                         "repro.serving.slo.replay / benchmarks/slo.py")
    ap.add_argument("--admit-max-pending", type=int, default=None,
                    help="admission control: bounded-queue "
                         "backpressure — reject (typed 'rejected' "
                         "result) once this many requests are pending")
    ap.add_argument("--admit-shed", action="store_true",
                    help="admission control: shed-on-deadline — "
                         "reject at admit when the simulated completion "
                         "time exceeds the request deadline")
    ap.add_argument("--shed-slack", type=float, default=1.0,
                    help="shed-on-deadline: admit while "
                         "est_completion <= deadline * slack (values >1 "
                         "admit optimistically, <1 shed early)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline in "
                         "seconds (enables timed_out, and with "
                         "--admit-shed, at-admit shedding)")
    ap.add_argument("--retry", type=int, default=0,
                    help="fault tolerance: retry failed round "
                         "launches up to N attempts (with checkpointing, "
                         "quarantine and failover; 0 = recovery off)")
    ap.add_argument("--checkpoint-interval", type=int, default=4,
                    help="fault tolerance: polls between lane-state "
                         "checkpoints (0 = no checkpointing)")
    ap.add_argument("--fault-launch-rate", type=float, default=0.0,
                    help="chaos testing: inject transient launch "
                         "faults at this per-launch rate (deterministic "
                         "per-site schedule from --fault-seed)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="chaos testing: fault-schedule seed")
    ap.add_argument("--fault-device-lost-at", type=int, default=None,
                    help="chaos testing: the Nth launch raises a "
                         "persistent DeviceLostError (exercises "
                         "checkpoint-restore failover)")
    ap.add_argument("--strict-step-cap", action="store_true",
                    help="restore the legacy max_graph_steps "
                         "behaviour (evict + raise) instead of typed "
                         "status=='step_capped' results")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    graphs = _request_stream(args.engine, args.requests, args.seed)
    spr = args.steps_per_round if args.continuous else 0
    client = MBEClient(MBEOptions(
        engine=args.engine, count_p=args.count_p, count_q=args.count_q,
        bucket_mode=args.policy,
        kernel_impl=args.kernel_impl,
        resident_lanes=args.resident_lanes,
        max_batch=args.max_batch, steps_per_round=spr,
        steps_per_call=args.steps_per_call,
        big_graph_threshold=args.big_graph_threshold,
        mesh=args.mesh or None,
        admission=_admission_policy(args),
        trace_path=args.trace,
        retry=_retry_policy(args),
        fault_injector=_fault_plan(args),
        strict_step_cap=args.strict_step_cap))
    t0 = time.perf_counter()
    if args.deadline_s is not None:
        futs = [client.submit(g, deadline_s=args.deadline_s)
                for g in graphs]
        client.drain()
        results = [f.result() for f in futs]
    else:
        results = client.enumerate_many(graphs)
    dt = time.perf_counter() - t0
    stats = client.stats()
    # engine-agnostic headline: bicliques/cliques found, or the count
    metric = sum(r.metric for r in results)
    mode = f"continuous(r={spr})" if args.continuous else "flush"
    _print_routing(client)
    slo = ""
    if _admission_policy(args) is not None:
        slo = (f"admitted {stats['admitted']}, "
               f"rejected {stats['rejected']} "
               f"(shed {stats['shed']}, "
               f"backpressure {stats['rejected_backpressure']}), "
               f"timed_out {stats['timed_out']}, ")
    ft = ""
    if _retry_policy(args) is not None or _fault_plan(args) is not None:
        ft = (f"faults {stats['faults_injected']}, "
              f"retries {stats['retries']}, "
              f"checkpoints {stats['checkpoints']}, "
              f"quarantined {stats['quarantined']}, "
              f"failovers {stats['failovers']}, "
              f"failed {stats['failed']}, ")
    print(f"[serve-mbe] {args.requests} graphs, policy={args.policy}, "
          f"engine={stats['engine']}, executor={stats['executor']}, "
          f"kernels={stats['kernel_impl']} "
          f"(x{stats['steps_per_call']}/call), "
          f"{mode}: metric total {metric}, "
          f"{stats['batches']} rounds, "
          f"{stats['misses']} compiles ({stats['hits']} cache hits), "
          f"{slo}{ft}"
          f"occupancy {stats['occupancy']:.2f}, "
          f"{stats['busy_steps'] / dt:.0f} steps/s "
          f"({stats['steps_per_poll']:.0f} steps/poll, "
          f"{stats['launches_per_poll']:.1f} launches/poll), "
          f"{dt:.2f}s ({args.requests / dt:.1f} graphs/s)")
    if args.trace:
        client.server.close_trace()
        print(f"[trace] wrote {args.trace}")
    return dict(requests=args.requests, metric=metric, wall_s=dt, **stats)


if __name__ == "__main__":
    serve()
