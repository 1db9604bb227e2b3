"""Batched serving drivers: LM continuous-batching decode loop + the
batched multi-graph MBE front end.

LM mode: real decode steps on local devices (production-mesh serving is
proven by dryrun.py). The loop implements the serving pattern the
inference shapes describe: a fixed-slot batch, each slot holding one
request's KV state; finished requests leave, queued requests take their
slot (continuous batching with static shapes — the cuMBE static-memory
discipline again).

MBE mode (``--mbe``): serves a stream of bipartite graphs through the
unified client (``repro.api.MBEClient`` over ``repro.serving``) —
shape-bucketed, vmap-batched enumeration with a compiled-executable
cache (see those docstrings for the model); ``--engine compact`` serves
the paper's compact-array engine through the same stack.
``--continuous`` switches the scheduler into bounded-round slot mode
(``--steps-per-round`` engine steps per round): finished lanes are demuxed
and refilled mid-flight from the pending queue, lifting lane occupancy on
skewed streams — the same slot model the LM decode loop below uses.

Execution backends: ``--mesh N`` serves through ``ShardedExecutor`` on a
1-D serving mesh over N host devices (force host devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``); the default is
the single-device ``LocalExecutor``.  ``--big-graph-threshold K`` routes
requests with >= K root tasks to the work-stealing big-graph lane.  Every
request's routing decision and every pool's lane placement is printed
(``[route]``/``[pool]`` lines) so operators can see why a request queued
where it did.

Usage:
  python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --requests 8 --max-new 32
  python -m repro.launch.serve --mbe --requests 32 --policy pow2
  python -m repro.launch.serve --mbe --continuous --steps-per-round 64
  python -m repro.launch.serve --mbe --mesh 8 --big-graph-threshold 16
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro import configs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models import model as M
from repro.models.config import ShapeSpec
from repro.models.layers import init_params
from repro.sharding import axes as A
from repro.sharding.auto import make_rules


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


def serve_mbe(args) -> dict:
    """Serve a synthetic mixed-size request stream through the unified
    client (``repro.api.MBEClient``), with any registered engine."""
    from repro.api import MBEClient, MBEOptions
    graphs = _request_stream(args.engine, args.requests, args.seed)
    spr = args.steps_per_round if args.continuous else 0
    client = MBEClient(MBEOptions(
        engine=args.engine, count_p=args.count_p, count_q=args.count_q,
        bucket_mode=args.policy,
        kernel_impl=args.kernel_impl,
        resident_lanes=args.resident_lanes,
        resident_rebalance=args.resident_rebalance,
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


def serve(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mbe", action="store_true",
                    help="serve bipartite graphs (MBE) instead of LM decode")
    ap.add_argument("--policy", default="pow2",
                    choices=["pow2", "linear", "exact"])
    ap.add_argument("--engine", default="dense",
                    help="MBE: workload engine by registry name "
                         "(repro.core.engine; e.g. dense, compact, "
                         "count, mce — unknown names raise ValueError "
                         "listing the available engines)")
    ap.add_argument("--count-p", type=int, default=2,
                    help="count engine: p of the (p,q)-biclique count")
    ap.add_argument("--count-q", type=int, default=2,
                    help="count engine: q of the (p,q)-biclique count")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--continuous", action="store_true",
                    help="MBE: bounded-round slot scheduling with "
                         "mid-flight lane refill")
    ap.add_argument("--steps-per-round", type=int, default=64,
                    help="MBE continuous mode: engine steps per round")
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="MBE: engine-loop inner unroll (candidate steps "
                         "per while-loop iteration in one compiled round "
                         "segment; byte-identical results)")
    ap.add_argument("--kernel-impl", default="auto",
                    choices=["auto", "jnp", "pallas"],
                    help="MBE: step-kernel path — 'pallas' = fused "
                         "fused_select/fused_check kernels (interpret "
                         "off-TPU), 'auto' = pallas on TPU, jnp elsewhere")
    ap.add_argument("--resident-lanes",
                    type=lambda v: v if v == "auto" else int(v),
                    default="auto",
                    help="MBE pallas path: multi-lane resident pool "
                         "kernel — 'auto' = one launch per pool whenever "
                         "the VMEM gate admits it, int k caps the pool "
                         "width, 0/1 pins the legacy vmap layout")
    ap.add_argument("--resident-rebalance", action="store_true",
                    help="MBE pool path: rebalance surplus step budget "
                         "from finished to busy lanes at segment "
                         "boundaries (scoreboard-driven)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="MBE: serve through ShardedExecutor on a 1-D "
                         "mesh over N host devices (0 = LocalExecutor)")
    ap.add_argument("--big-graph-threshold", type=int, default=None,
                    help="MBE: route graphs with >= K root tasks to the "
                         "work-stealing big-graph lane")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="MBE: record a JSONL request trace "
                         "(serving.slo.trace schema v1) — replay it with "
                         "repro.serving.slo.replay / benchmarks/slo.py")
    ap.add_argument("--admit-max-pending", type=int, default=None,
                    help="MBE admission control: bounded-queue "
                         "backpressure — reject (typed 'rejected' "
                         "result) once this many requests are pending")
    ap.add_argument("--admit-shed", action="store_true",
                    help="MBE admission control: shed-on-deadline — "
                         "reject at admit when the simulated completion "
                         "time exceeds the request deadline")
    ap.add_argument("--shed-slack", type=float, default=1.0,
                    help="MBE shed-on-deadline: admit while "
                         "est_completion <= deadline * slack (values >1 "
                         "admit optimistically, <1 shed early)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="MBE: per-request wall-clock deadline in "
                         "seconds (enables timed_out, and with "
                         "--admit-shed, at-admit shedding)")
    ap.add_argument("--retry", type=int, default=0,
                    help="MBE fault tolerance: retry failed round "
                         "launches up to N attempts (with checkpointing, "
                         "quarantine and failover; 0 = recovery off)")
    ap.add_argument("--checkpoint-interval", type=int, default=4,
                    help="MBE fault tolerance: polls between lane-state "
                         "checkpoints (0 = no checkpointing)")
    ap.add_argument("--fault-launch-rate", type=float, default=0.0,
                    help="MBE chaos testing: inject transient launch "
                         "faults at this per-launch rate (deterministic "
                         "per-site schedule from --fault-seed)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="MBE chaos testing: fault-schedule seed")
    ap.add_argument("--fault-device-lost-at", type=int, default=None,
                    help="MBE chaos testing: the Nth launch raises a "
                         "persistent DeviceLostError (exercises "
                         "checkpoint-restore failover)")
    ap.add_argument("--strict-step-cap", action="store_true",
                    help="MBE: restore the legacy max_graph_steps "
                         "behaviour (evict + raise) instead of typed "
                         "status=='step_capped' results")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.mbe:
        return serve_mbe(args)
    if args.arch is None:
        ap.error("--arch is required unless --mbe is given")

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get_config(args.arch))
    mesh = make_local_mesh(model=args.model_parallel)
    shape = ShapeSpec("serve", args.max_seq, args.slots, "decode")
    rules = make_rules(cfg, mesh, shape)
    specs = M.param_specs(cfg)
    params = init_params(specs, jax.random.key(args.seed))

    rng = np.random.default_rng(args.seed)
    cb = (cfg.n_codebooks,) if cfg.n_codebooks else ()
    prompts = [rng.integers(0, cfg.vocab,
                            (args.prompt_len,) + cb).astype(np.int32)
               for _ in range(args.requests)]

    B = args.slots

    @jax.jit
    def decode_one(params, cache, tok, pos_vec):
        """Per-slot positions: decode one token for every active slot."""
        # scan the batch as a whole at a shared pos is the fast path; the
        # per-slot pos variant uses vmap'd single-slot decode.
        def one(p, c, t, pos):
            # c: per-slot cache leaves (L, S, ...) -> re-insert batch=1
            c1 = jax.tree.map(lambda x: x[:, None], c)
            lg, c1 = M.decode_step(cfg, p, c1, t[None], pos)
            return lg[0], jax.tree.map(lambda x: x[:, 0], c1)
        logits, cache = jax.vmap(one, in_axes=(None, 1, 0, 0),
                                 out_axes=(0, 1))(params, cache, tok,
                                                  pos_vec)
        return logits.argmax(-1).astype(jnp.int32), cache

    with mesh, A.use_rules(rules):
        cache = M.init_cache(cfg, B, args.max_seq)
        slot_req = [-1] * B           # request id per slot
        slot_pos = np.zeros(B, np.int32)
        slot_new = np.zeros(B, np.int32)
        cur_tok = np.zeros((B,) + cb, np.int32)
        queue = list(range(args.requests))
        done, outputs = 0, {i: [] for i in range(args.requests)}
        t0 = time.time()
        steps = 0

        def admit(s):
            rid = queue.pop(0)
            slot_req[s] = rid
            # prefill by replaying the prompt through decode steps (simple
            # and exact; a production server would batch-prefill)
            nonlocal cache, cur_tok
            for j, t in enumerate(prompts[rid]):
                tokv = np.array(cur_tok)
                tokv[s] = t
                cur_tok = tokv
                posv = np.array(slot_pos)
                posv[s] = j
                nxt, cache = decode_one(params, cache,
                                        jnp.asarray(cur_tok),
                                        jnp.asarray(posv))
            slot_pos[s] = len(prompts[rid])
            slot_new[s] = 0
            tokv = np.array(cur_tok)
            tokv[s] = np.asarray(nxt)[s]
            cur_tok = tokv

        while done < args.requests:
            for s in range(B):
                if slot_req[s] < 0 and queue:
                    admit(s)
            nxt, cache = decode_one(params, cache, jnp.asarray(cur_tok),
                                    jnp.asarray(slot_pos))
            nxt = np.asarray(nxt)
            steps += 1
            for s in range(B):
                rid = slot_req[s]
                if rid < 0:
                    continue
                outputs[rid].append(nxt[s].tolist())
                slot_pos[s] += 1
                slot_new[s] += 1
                cur_tok[s] = nxt[s]
                if slot_new[s] >= args.max_new or \
                        slot_pos[s] >= args.max_seq - 1:
                    slot_req[s] = -1
                    slot_pos[s] = 0
                    done += 1
        dt = time.time() - t0
    toks = sum(len(v) for v in outputs.values())
    print(f"[serve] {args.requests} requests, {toks} tokens, "
          f"{steps} batch steps, {toks / dt:.1f} tok/s")
    return dict(requests=args.requests, tokens=toks, steps=steps,
                tok_per_s=toks / dt, outputs=outputs)


if __name__ == "__main__":
    serve()
